"""Ouro (ByteDance/Ouro-2.6B `config.json`, model_type `ouro`; the Ouro
1.4B / 2.6B LoopLM family): a dense decoder whose stack of layers is run
`total_ut_steps` times over the SAME weights. Each layer application has keys
and values of its own (cache plane pass * num_hidden_layers + layer), a block
has four norms (one on each sublayer's input, one on each sublayer's output
before the residual add), the final norm closes EVERY pass and feeds the
next, and a gate sigmoid(w . x_t + b) after every pass gives the probability
of leaving the loop there. Full multi-head attention (16 heads on 16 KV
heads), rope by halves at theta 1e6, SwiGLU, untied head.

The config gives the widths, `total_ut_steps` and `early_exit_threshold`;
where the norms sit, the norm between passes and the gate's bias follow the
family's published modelling code as the builder of this file knew it, with
no network to check against (chipbench/configs/ouro-2.6b.json `assumed`).

Random initialisation (no published weights are loaded here): as the other
families', and the norms on the sublayers' outputs start at
`OUTPUT_NORM_INIT`, which says why.

Not built: rows that leave the loop early. A threshold under 1 makes the
compute a row vary and needs a scheduler that tells the step which rows
still run; the published threshold is 1 (every token takes every pass) and
`ouro_config` refuses another. Nor the paper's memory-saving variant that
shares one pass's keys and values among the passes at decode time: the
published config does not ask for it."""

from __future__ import annotations

import jax.numpy as jnp

from ray_tpu.models.transformer import TransformerConfig

# Where a randomly initialised block's two output norms start. A norm on a
# sublayer's output erases the output projection's scale (0.02 / sqrt(2L) in
# `transformer._layer_params`), so the scale has to start in the norm's own
# weight: 0.02, what every other weight starts at and the RMS of an embedding
# row. At 1 a sublayer adds a vector as large as the whole residual and a
# randomly initialised deep stack amplifies a rounding of its weights by 1 to
# 30 times depending on the seed; at 0.02 by 4 to 13 (PERF.md section 6,
# PR 35). It is a property of random weights, not of the model: loaded
# weights bring their own.
OUTPUT_NORM_INIT = 0.02

SIZES = {
    # passes and layers differ, so that neither can stand in for the other
    "tiny": dict(d_model=64, n_layers=6, n_heads=4, n_kv_heads=4, d_head=16,
                 d_ff=96, n_passes=3),
    "2.6b": dict(d_model=2048, n_layers=48, n_heads=16, n_kv_heads=16, d_head=128,
                 d_ff=5632, n_passes=4),
}


def ouro_config(size: str = "2.6b", *, vocab_size: int = 49152,
                max_seq_len: int = 65536, dtype=jnp.bfloat16,
                early_exit_threshold: float = 1.0, **overrides) -> TransformerConfig:
    if early_exit_threshold != 1.0:
        raise ValueError(
            f"early_exit_threshold {early_exit_threshold}: rows that leave the "
            "loop before its last pass are not built (the compute a row would "
            "vary and the decode step runs every row through every pass); the "
            "published threshold is 1")
    base = dict(SIZES[size])
    base.update(
        vocab_size=vocab_size,
        max_seq_len=max_seq_len,
        norm="rms",
        norm_eps=1e-6,
        act="swiglu",
        pos="rope",
        rope_theta=1e6,
        bias=False,
        tie_embeddings=False,
        sandwich_norms=True,
        sandwich_norm_init=OUTPUT_NORM_INIT,
        exit_gate=True,
        dtype=dtype,
    )
    base.update(overrides)
    return TransformerConfig(**base)
