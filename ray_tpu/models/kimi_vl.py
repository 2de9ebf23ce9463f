"""Kimi-VL's language model (moonshotai/Kimi-VL-A3B-Instruct `config.json`,
`text_config`): a DeepSeek-V3-style decoder — multi-head latent attention
(the cache is one row of kv_lora_rank + qk_rope_head_dim values a token a
layer), a leading dense SwiGLU layer, then mixture-of-experts layers with
sigmoid scores, selection on score + a per-expert bias (`noaux_tc`, one
group), renormalised weights times `routed_scaling_factor`, and shared
experts for every token. The vision tower is not built: text in, text out.

Departure from the published code: rotary positions rotate halves
(`ops/rope.py`), the published code interleaved pairs: a fixed permutation of
the rope columns of W_q and W_dkv."""

from __future__ import annotations

import jax.numpy as jnp

from ray_tpu.models.transformer import MoEConfig, TransformerConfig

SIZES = {
    "tiny": dict(d_model=64, n_layers=3, n_heads=4, d_ff=32, d_ff_dense=96,
                 kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                 v_head_dim=16, num_experts=8, top_k=3, n_shared_experts=2),
    "a3b": dict(d_model=2048, n_layers=27, n_heads=16, d_ff=1408, d_ff_dense=11264,
                kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
                v_head_dim=128, num_experts=64, top_k=6, n_shared_experts=2),
}


def kimi_vl_config(size: str = "a3b", *, vocab_size: int = 163840,
                   max_seq_len: int = 131072, dtype=jnp.bfloat16,
                   **overrides) -> TransformerConfig:
    base = dict(SIZES[size])
    moe = MoEConfig(num_experts=base.pop("num_experts"), top_k=base.pop("top_k"),
                    n_shared_experts=base.pop("n_shared_experts"),
                    capacity_factor=None, aux_coef=0.0, score_func="sigmoid",
                    routed_scaling_factor=2.446)
    base.update(
        vocab_size=vocab_size,
        max_seq_len=max_seq_len,
        norm="rms",
        norm_eps=1e-5,
        act="swiglu",
        pos="rope",
        rope_theta=800000.0,
        bias=False,
        tie_embeddings=False,
        n_dense_layers=1,
        moe=moe,
        dtype=dtype,
    )
    base.update(overrides)
    return TransformerConfig(**base)
