"""A named serving kernel's share of its roofline over the traced window:
the least time the chip could take for the work the engine counted
(chipbench/kernel_costs.py) / the device time of the kernel's ops, found by
`pallas_call(name=...)` among `facts["trace"]["breakdown"]["device_ops"]`.
None when no op of that name is there (no trace, or a program without the
kernel).

params: {"op": the kernel's name, "cost": a function of kernel_costs.py,
"work": the dotted path of the counter in a `stats()` reading that the work
is the delta of, "stacks": layers of each layer scan that calls the kernel,
"args": the function's other arguments}. Every scan is a call site with an
op of its own (`<op>.<n>`) and the list of device ops holds the ten largest,
so the smaller scans' ops may be missing: the ops found are taken to be the
scans with the most layers (a scan's kernel time grows with its layers), and
only their layers' work is counted."""

from chipbench import flops, harness, kernel_costs
from chipbench.readers import dig


def read(facts: dict, params: dict):
    ops = dig(facts, "trace.breakdown.device_ops") or []
    spent = sorted((s for name, s in ops
                    if name.split(":")[-1].split(".")[0] == params["op"]), reverse=True)
    ends = [dig(facts.get(k) or {}, params["work"]) for k in ("stats0", "stats1")]
    if not spent or None in ends or not sum(spent):
        return None
    layers = sum(sorted(params["stacks"], reverse=True)[:len(spent)])
    cost = getattr(kernel_costs, params["cost"])(ends[1] - ends[0], layers, **params["args"])
    peaks = harness.peaks_for(facts["stats1"]["device"]["kind"])
    least, bound = flops.roofline_seconds(cost["flops"], cost["bytes"], peaks)
    facts[params["op"] + "_bound"] = bound
    return 100.0 * least / sum(spent)
