"""A per-head (grouped-query) decode-attention launch's share of its roofline
over the traced slice: the least time the chip could take for the positions
the engine counted between the slice's two ends / the device time of the
launch's ops, found by `pallas_call(name=...)` among the trace's Pallas
launches (`readers.launch_seconds`). None when no op of that name ran (no
trace, or a program without the launch, as the parent of the PR that named it).

params: {"op": the kernel's name, "work": the dotted path of the counter in
a `stats()` reading that the positions are the delta of (positions ONE layer
of the kind attended over, summed over rows and decode steps), "layers": the
layers of that kind, "heads", "kv_heads", "head_dim"}.

The decode step traces each kind of layer once (the scan of periods, and
inside it the scan of a period's window layers), so a launch is ONE op
(`<op>.<n>`) that every layer of its kind runs; the ops of that name are
summed (one of each compiled decode program)."""

from chipbench import flops, harness
from chipbench.readers import launch_seconds, slice_delta


def gqa_decode_attention_cost(positions: float, heads: int, kv_heads: int,
                              head_dim: int, bytes_per_el: int = 2) -> dict:
    """`positions`: cached positions attended over, summed over rows, decode
    steps and layers. A position costs every query head one dot of
    `head_dim` for its score and one weighted sum of `head_dim`; the least
    traffic reads its K and its V once, shared by the heads of a group.
    Queries, results and masked lanes of a page count nothing."""
    return {"flops": positions * heads * 2.0 * (head_dim + head_dim),
            "bytes": positions * 2.0 * kv_heads * head_dim * bytes_per_el}


def read(facts: dict, params: dict):
    spent = launch_seconds(facts, params["op"])
    work = slice_delta(facts, params["work"])
    if work is None or not sum(spent):
        return None
    cost = gqa_decode_attention_cost(work * params["layers"], params["heads"],
                                     params["kv_heads"], params["head_dim"])
    peaks = harness.peaks_for(facts["stats1"]["device"]["kind"])
    least, bound = flops.roofline_seconds(cost["flops"], cost["bytes"], peaks)
    facts[params["op"] + "_bound"] = bound
    return 100.0 * least / sum(spent)
