"""100 * (1 - steps * median step time / window wall): the share of the
window that the median step does not account for — a late batch, a GC pause,
a slow `train.report`, the `block_until_ready` at the end of each block."""


def read(facts: dict, params: dict):
    if not facts.get("steps") or not facts.get("window_s"):
        return None
    step = facts["block_median_s"] / facts["steps_per_block"]
    return 100.0 * (1.0 - facts["steps"] * step / facts["window_s"])
