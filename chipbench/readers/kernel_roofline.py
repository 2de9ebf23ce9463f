"""A named serving kernel's share of its roofline over the traced slice:
the least time the chip could take for the work the engine counted between
the slice's two ends (chipbench/kernel_costs.py) / the device time of the
kernel's ops, found by `pallas_call(name=...)` among the trace's Pallas
launches (`readers.launch_seconds`). None when no op of that name ran (no
trace, or a program without the kernel).

params: {"op": the kernel's name, "cost": a function of kernel_costs.py,
"work": the dotted path of the counter in a `stats()` reading that the work
is the delta of, "stacks": layers of each layer scan that calls the kernel,
"args": the function's other arguments}. Every scan is a call site with an
op of its own (`<op>.<n>`); where fewer ops ran than there are scans, the
ops found are taken to be the scans with the most layers, and only their
layers' work is counted."""

from chipbench import flops, harness, kernel_costs
from chipbench.readers import launch_seconds, slice_delta


def read(facts: dict, params: dict):
    spent = launch_seconds(facts, params["op"])
    work = slice_delta(facts, params["work"])
    if work is None or not sum(spent):
        return None
    layers = sum(sorted(params["stacks"], reverse=True)[:len(spent)])
    cost = getattr(kernel_costs, params["cost"])(work, layers, **params["args"])
    peaks = harness.peaks_for(facts["stats1"]["device"]["kind"])
    least, bound = flops.roofline_seconds(cost["flops"], cost["bytes"], peaks)
    facts[params["op"] + "_bound"] = bound
    return 100.0 * least / sum(spent)
