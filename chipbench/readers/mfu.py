"""Model FLOP/s utilization: the FLOPs forward and backward require per
token (chipbench/flops.py: matmuls and causal attention, tied head once, no
gather, no recompute) x tokens per chip-second / the chip's bf16 peak."""


def read(facts: dict, params: dict):
    if not facts.get("train_tok_s_per_chip"):
        return None
    return (100.0 * facts["train_tok_s_per_chip"] * facts["flops_per_token"]
            / facts["peak_flops_per_s"])
