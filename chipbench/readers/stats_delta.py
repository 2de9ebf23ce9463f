"""From `TPUEngine.stats()` read before the window and after the answers
still on their way have come; `stats_span_s` is the time between the two
readings. params["kind"]: `occupancy` — mean active rows per decode step;
`period_ms` — that span / steps of `counter` (idle time included);
`rate` — `counter` per second of that span."""


def read(facts: dict, params: dict):
    s0, s1 = facts.get("stats0"), facts.get("stats1")
    if not s0 or not s1 or not facts.get("stats_span_s"):
        return None
    if params["kind"] == "occupancy":
        steps = s1["decode_steps"] - s0["decode_steps"]
        rows = (s1["decode_occupancy"] * s1["decode_steps"]
                - s0["decode_occupancy"] * s0["decode_steps"])
        return rows / steps if steps else None
    if params["counter"] not in s1 or params["counter"] not in s0:
        return None
    delta = s1[params["counter"]] - s0[params["counter"]]
    if params["kind"] == "period_ms":
        return 1e3 * facts["stats_span_s"] / delta if delta else None
    if params["kind"] == "rate":
        return delta / facts["stats_span_s"]
    return None
