"""The held experts' grouped product's share of its roofline over the traced
slice: the least time the chip could take for the routed slots and the experts
the engine counted between the slice's two ends / the device time of the
kernel's ops, found by `pallas_call(name=...)` among the trace's Pallas
launches (`readers.launch_seconds`). None when no op of that name ran or the
program has no such counters (no trace, or an expert layer that holds every
expert: the parent of the PR that brought the share).

params: {"op": the kernel's name, "slots": the dotted path in a `stats()`
reading of the routed slots whose expert is held (summed over the expert
layers' calls), "groups": that of the held experts that had a row (summed over
the calls), "d_model", "d_ff", "bytes_per_el"}.

An expert layer is three launches (gate, up, down) at each of its call sites
(the scan of a period's window layers, its full layer; in every compiled
program), each an op `<op>.<n>`: the ops of that name are summed."""

from chipbench import flops, harness
from chipbench.readers import launch_seconds, slice_delta


def expert_grouped_matmul_cost(slots: float, groups: float, d_model: int, d_ff: int,
                               bytes_per_el: int = 2) -> dict:
    """`slots`: routed slots computed, summed over the calls; `groups`:
    experts with at least one row, summed over the calls. A slot costs the
    three products of a SwiGLU expert, 2 x d_model x d_ff operations each;
    the least traffic reads each such expert's three matrices once a call.
    Activations (a slot's row in and out) count nothing."""
    return {"flops": slots * 6.0 * d_model * d_ff,
            "bytes": groups * 3.0 * d_model * d_ff * bytes_per_el}


def read(facts: dict, params: dict):
    spent = launch_seconds(facts, params["op"])
    slots = slice_delta(facts, params["slots"])
    groups = slice_delta(facts, params["groups"])
    if slots is None or groups is None or not sum(spent):
        return None
    cost = expert_grouped_matmul_cost(slots, groups, params["d_model"], params["d_ff"],
                                      params.get("bytes_per_el", 2))
    peaks = harness.peaks_for(facts["stats1"]["device"]["kind"])
    least, bound = flops.roofline_seconds(cost["flops"], cost["bytes"], peaks)
    facts[params["op"] + "_bound"] = bound
    return 100.0 * least / sum(spent)
