"""Mellum 2 (JetBrains/Mellum2-12B-A2.5B-Instruct `config.json`, model_type
`mellum`): grouped-query attention in periods of four layers — three
`sliding_attention` layers over a window of 1024 positions with the plain
rope, then one `full_attention` layer with YaRN (factor 16 over 8192 original
positions) — and a mixture of experts in every layer: 64 of width 896, the 8
best by softmax, gates renormalised, nothing shared, nothing dropped.
`intermediate_size` (7168) is unused: every `mlp_layer_types` entry is
`sparse`.

Not built: the multi-token-prediction head the model card speaks of (the
config has no key for it and serving does not run it), and q/k norms (the
config has no key for them)."""

from __future__ import annotations

import jax.numpy as jnp

from ray_tpu.models.transformer import MoEConfig, TransformerConfig
from ray_tpu.ops import Yarn

SIZES = {
    "tiny": dict(d_model=64, n_layers=8, n_heads=4, n_kv_heads=2, d_head=16,
                 d_ff=32, num_experts=8, top_k=3, window=128, yarn_original=256),
    "12b-a2.5b": dict(d_model=2304, n_layers=28, n_heads=32, n_kv_heads=4, d_head=128,
                      d_ff=896, num_experts=64, top_k=8, window=1024,
                      yarn_original=8192),
}


def mellum_config(size: str = "12b-a2.5b", *, vocab_size: int = 98304,
                  max_seq_len: int = 131072, dtype=jnp.bfloat16,
                  **overrides) -> TransformerConfig:
    base = dict(SIZES[size])
    moe = MoEConfig(num_experts=base.pop("num_experts"), top_k=base.pop("top_k"),
                    capacity_factor=None, aux_coef=0.0)
    base.update(
        vocab_size=vocab_size,
        max_seq_len=max_seq_len,
        norm="rms",
        norm_eps=1e-6,
        act="swiglu",
        pos="rope",
        rope_theta=500000.0,
        bias=False,
        tie_embeddings=False,
        window_period=4,
        yarn=Yarn(factor=16.0, original_max_position=base.pop("yarn_original"),
                  beta_fast=32.0, beta_slow=1.0, attention_factor=1.2772588722239782),
        moe=moe,
        dtype=dtype,
    )
    base.update(overrides)
    return TransformerConfig(**base)
