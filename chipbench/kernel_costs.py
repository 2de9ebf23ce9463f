"""Operations and bytes of the serving kernels, computed from what the
engine counted: what a kernel's share of its roofline is measured against."""

from __future__ import annotations


def latent_decode_attention_cost(context_tokens: float, layers: int, heads: int,
                                 row_values: int, value_values: int,
                                 bytes_per_el: int = 2) -> dict:
    """Absorbed latent attention of the decode steps
    (ops/ragged_paged_attention.py `ragged_latent_attention`).
    `context_tokens`: cached positions attended over, summed over rows and
    decode steps (`stats()["cache"]["context_tokens"]`), each in every one
    of `layers` layers. A position costs every head one dot of `row_values`
    (c | k_rope) for its score and one weighted sum of `value_values` (c);
    the least traffic reads its row once, shared by all heads. Queries,
    results and padding lanes count nothing."""
    positions = context_tokens * layers
    return {"flops": positions * heads * 2.0 * (row_values + value_values),
            "bytes": positions * row_values * bytes_per_el}
