"""The recurrent-state update's share of its roofline over the traced slice:
the least time the chip could take for the row-steps the engine counted
between the slice's two ends / the device time of the kernel's ops, found by
`pallas_call(name=...)` among the trace's Pallas launches
(`readers.launch_seconds`). None when no op of that name ran (no trace, or a
program without the kernel, as the parent of the PR that named it).

params: {"op": the kernel's name, "work": the dotted path of the counter in a
`stats()` reading that the row-steps are the delta of (LIVE rows summed over
decode steps: a row a step is a row of the update in each layer), "layers":
the state-space layers, "heads", "head_dim", "d_state", "state_bytes_per_el"}.

The decode step traces the state-space layers twice (the scan of those
before a period's attention layer and of those after it), so the kernel is
two ops (`<op>.<n>`) that between them run every layer; all ops of that name
are summed (two of each compiled decode program). The launch walks the live
rows only, so dead slots are no traffic of its own."""

from chipbench import flops, harness
from chipbench.readers import launch_seconds, slice_delta


def ssm_state_update_cost(row_steps: float, heads: int, head_dim: int, d_state: int,
                          state_bytes_per_el: int = 4) -> dict:
    """`row_steps`: rows stepped, summed over decode steps and layers. A row's
    state is heads x head_dim x d_state elements; the least traffic reads it
    once and writes it once (it cannot be kept anywhere else between steps).
    An element costs a decay multiply, the outer product's multiply and its
    add, and the output's multiply and add: 5 operations. The step's inputs
    and outputs (a row's x, B, C, dt, y: kilobytes) count nothing."""
    elements = row_steps * heads * head_dim * d_state
    return {"flops": 5.0 * elements, "bytes": 2.0 * state_bytes_per_el * elements}


def read(facts: dict, params: dict):
    spent = launch_seconds(facts, params["op"])
    work = slice_delta(facts, params["work"])
    if work is None or not sum(spent):
        return None
    cost = ssm_state_update_cost(work * params["layers"], params["heads"],
                                 params["head_dim"], params["d_state"],
                                 params["state_bytes_per_el"])
    peaks = harness.peaks_for(facts["stats1"]["device"]["kind"])
    least, bound = flops.roofline_seconds(cost["flops"], cost["bytes"], peaks)
    facts[params["op"] + "_bound"] = bound
    facts[params["op"] + "_row_steps"] = work
    return 100.0 * least / sum(spent)
