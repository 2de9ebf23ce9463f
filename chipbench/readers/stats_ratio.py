"""scale * (sum of deltas of `num`) / (sum of deltas of `den`) between the
two `TPUEngine.stats()` readings: params {"num": [paths], "den": [paths],
optional "scale"}, each path dotted into a reading ("loop.seconds.decode").
None when a reading lacks a path (a program without that counter) or the
denominator did not move."""

from chipbench.readers import dig


def read(facts: dict, params: dict):
    s0, s1 = facts.get("stats0"), facts.get("stats1")
    if not s0 or not s1:
        return None
    sums = []
    for paths in (params["num"], params["den"]):
        ends = [(dig(s0, p), dig(s1, p)) for p in paths]
        if any(a is None or b is None for a, b in ends):
            return None
        sums.append(sum(b - a for a, b in ends))
    num, den = sums
    return params.get("scale", 1.0) * num / den if den else None
