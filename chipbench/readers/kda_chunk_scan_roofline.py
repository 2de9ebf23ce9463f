"""The chunked delta rule's share of its roofline over the traced slice: the
least time the chip could take for the REAL positions the prefill programs
scanned between the slice's two ends (a layer's positions each: the counter
at `work` less the counter at `padded`, both already summed over the
recurrent layers) / the device time of the launch's ops, found by
`pallas_call(name=...)` among the trace's Pallas launches
(`readers.launch_seconds`). None when no op of that name ran (no trace, or a
program that scans in plain XLA, as the parent of the PR that named the
launch) or a reading lacks a counter.

params: {"op": the launch's name, "work" and "padded": dotted paths of the
two counters in a `stats()` reading, "heads", "dk", "dv", "chunk",
"bytes_per_el"}.

The cost is the LEAST work of the chunked form whatever implements it, so no
launch can read over 100 %: every operand once, every multiply-add once (not
once a pass of a float32 product). Positions a bucket pads are time of the
launch and no work of its own."""

from chipbench import flops, harness
from chipbench.readers import launch_seconds, slice_delta


def kda_chunk_scan_cost(positions: float, heads: int, dk: int, dv: int, chunk: int,
                        bytes_per_el: int = 4) -> dict:
    """`positions`: real positions scanned, summed over the recurrent layers.
    A position of a head reads q, k and g (dk each), v (dv) and beta, and
    writes o (dv): once. Its multiply-adds, Q = `chunk`: its rows of the lower
    triangles of the two decayed Gram matrices, q's with the diagonal and k's
    without (Q dk); its row of the unit-triangular solve against dk + dv
    columns ((Q - 1) / 2 of each); and the three products with the carried
    state, (W_k | q exp(G)) S (2 dk dv), tril(B) U ((Q + 1) / 2 dv) and (k
    exp(G_Q - G))^T U (dk dv). Two operations a multiply-add. The states
    themselves (in and out once a launch) count nothing."""
    macs = chunk * dk + (chunk - 1) / 2 * (dk + dv) + 3 * dk * dv + (chunk + 1) / 2 * dv
    per_head = bytes_per_el * (3 * dk + 2 * dv + 1)
    return {"flops": 2.0 * macs * heads * positions, "bytes": float(per_head * heads * positions)}


def read(facts: dict, params: dict):
    spent = launch_seconds(facts, params["op"])
    ran, padded = slice_delta(facts, params["work"]), slice_delta(facts, params["padded"])
    if ran is None or padded is None or not sum(spent):
        return None
    cost = kda_chunk_scan_cost(ran - padded, params["heads"], params["dk"], params["dv"],
                               params["chunk"], params["bytes_per_el"])
    peaks = harness.peaks_for(facts["stats1"]["device"]["kind"])
    least, bound = flops.roofline_seconds(cost["flops"], cost["bytes"], peaks)
    facts[params["op"] + "_bound"] = bound
    facts[params["op"] + "_positions"] = ran - padded
    return 100.0 * least / sum(spent)
