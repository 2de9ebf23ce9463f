"""Operations and bytes the algorithms need, computed from shapes.

`sizes` is the "sizes" group of a configuration file: d_model, n_layers,
n_heads, n_kv_heads, d_head, d_ff, vocab_size, act ("gelu" | "swiglu"),
tie_embeddings, and for sparse experts num_experts / top_k.
"""

from __future__ import annotations


def matmul_params_per_token(sizes: dict) -> int:
    """Weights a token is multiplied with in one forward pass: attention
    projections, the MLP (the top_k experts a token is routed to, and the
    router), and the output head once. The input embedding is a gather and
    counts nothing, whether or not it is tied to the head."""
    d, L = sizes["d_model"], sizes["n_layers"]
    h, hkv, dh = sizes["n_heads"], sizes["n_kv_heads"], sizes["d_head"]
    attn = d * (h * dh + 2 * hkv * dh) + h * dh * d
    mats = 3 if sizes["act"] == "swiglu" else 2
    mlp = mats * d * sizes["d_ff"]
    if sizes.get("num_experts"):
        mlp = mlp * sizes["top_k"] + d * sizes["num_experts"]
    return L * (attn + mlp) + d * sizes["vocab_size"]


def attention_flops_per_token(sizes: dict, seq: int, causal: bool = True) -> float:
    """QK^T and PV of one forward pass, per token of a sequence of `seq`
    tokens: 4 * seq * heads * d_head when every key is visited, half of that
    under a causal mask (the mean query sees seq/2 keys)."""
    full = 4.0 * seq * sizes["n_heads"] * sizes["d_head"] * sizes["n_layers"]
    return full / 2 if causal else full


def forward_flops_per_token(sizes: dict, seq: int) -> float:
    return 2.0 * matmul_params_per_token(sizes) + attention_flops_per_token(sizes, seq)


def train_flops_per_token(sizes: dict, seq: int) -> float:
    """Forward plus backward (twice the forward: gradients with respect to
    inputs and to weights). Recomputation under remat is not counted."""
    return 3.0 * forward_flops_per_token(sizes, seq)


def flash_attention_cost(batch: int, heads: int, seq: int, d_head: int, *,
                         causal: bool = True, bytes_per_el: int = 2,
                         backward: bool = True) -> dict:
    """FLOPs and HBM bytes of flash attention on [batch, heads, seq, d_head].

    Forward: QK^T and PV = 4*seq^2*d per head, half under a causal mask.
    Backward (dq and dk/dv kernels, ops/flash_attention.py): recompute S,
    dP = dO V^T, dV = P^T dO, dK = dS^T Q, dQ = dS K: five matmuls where the
    forward has two, so 2.5 times the forward's FLOPs.
    Bytes: the least traffic — each operand read once, each result written
    once (forward reads q, k, v and writes o + f32 lse; backward reads q, k,
    v, o, do, lse and writes dq, dk, dv)."""
    bh = batch * heads
    fwd = 4.0 * seq * seq * d_head * bh * (0.5 if causal else 1.0)
    tile = bh * seq * d_head * bytes_per_el
    lse = bh * seq * 4
    out = {"fwd_flops": fwd, "fwd_bytes": 4 * tile + lse}
    if backward:
        out["bwd_flops"] = 2.5 * fwd
        out["bwd_bytes"] = 8 * tile + lse
    return out


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> tuple:
    """(least seconds the chip could take, which bound sets it)."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
