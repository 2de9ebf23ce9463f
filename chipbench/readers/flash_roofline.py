"""The flash kernel's share of its roofline in the traced window: the least
time the chip could take for the calls made (chipbench/flops.py: required
FLOPs and least bytes, forward calls and backward passes counted from the
trace) / the device time of the kernel's events. Names its bound in
facts["flash_bound"]. params: the signature label (operands in, results out,
trace_reduce.kernel_ops_from_hlo) of the forward, dk/dv and dq kernels."""

from chipbench import flops


def read(facts: dict, params: dict):
    calls = (facts.get("trace") or {}).get("kernel_calls") or {}
    names = [params[k] for k in ("fwd", "dkv", "dq")]
    if not set(names) <= set(calls):
        return None
    fwd_calls, _, dq_calls = (calls[n]["calls"] for n in names)
    sizes = facts["sizes"]
    cost = flops.flash_attention_cost(
        facts["batch"] // facts["chips"], sizes["n_heads"], facts["seq"],
        sizes["d_head"])
    peaks = {"bf16_flops_per_s": facts["peak_flops_per_s"],
             "hbm_bytes_per_s": facts["peak_hbm_bytes_per_s"]}
    fwd, bound = flops.roofline_seconds(cost["fwd_flops"], cost["fwd_bytes"], peaks)
    bwd, _ = flops.roofline_seconds(cost["bwd_flops"], cost["bwd_bytes"], peaks)
    facts["flash_bound"] = bound
    least = fwd_calls * fwd + dq_calls * bwd
    spent = sum(calls[n]["seconds"] for n in names)
    return 100.0 * least / spent if spent else None
