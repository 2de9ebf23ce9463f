"""One small reader per kind of per-layer metric: `read(facts, params)`
returns the number, or None when there is nothing to read."""


def dig(facts: dict, path: str):
    """`facts["a"]["b"]` for the path "a.b"; None when any part is missing."""
    node = facts
    for key in path.split("."):
        if not isinstance(node, dict) or node.get(key) is None:
            return None
        node = node[key]
    return node


def launch_seconds(facts: dict, op: str) -> list:
    """Device seconds of every op `<op>.<n>` among the traced Pallas launches
    (`trace.kernel_calls`: all of them, whatever their rank), largest first."""
    calls = dig(facts, "trace.kernel_calls") or {}
    return sorted((c["seconds"] for name, c in calls.items()
                   if name.split(".")[0] == op), reverse=True)


def slice_delta(facts: dict, path: str):
    """What the counter at `path` of a `stats()` reading moved by over the
    TRACED slice: its delta between `stats_t0` and `stats_t1`, read at the
    slice's two ends, brought from the seconds those two readings lie apart
    on the engine's own clock (`loop.thread_s`: a replica with a queue
    answers late) to the seconds the device's trace holds. Work that is
    divided by traced seconds is counted over those seconds and no others."""
    t0, t1 = facts.get("stats_t0") or {}, facts.get("stats_t1") or {}
    ends = [dig(t0, path), dig(t1, path)]
    if None in ends:
        return None
    clock = [dig(t0, "loop.thread_s"), dig(t1, "loop.thread_s")]
    traced = dig(facts, "trace.window_s")
    if None in clock or not traced or clock[1] <= clock[0]:
        return ends[1] - ends[0]
    return (ends[1] - ends[0]) * traced / (clock[1] - clock[0])
