"""Arcee Trinity Large (arcee-ai/Trinity-Large-Preview `config.json`,
model_type `afmoe`, "400B-A13B"): 60 layers of hidden size 3072, grouped-query
attention (48 heads on 8 KV heads of 128) in periods of four layers, three
`sliding_attention` layers over a window of 4096 positions WITH the rope
(theta 10,000) and then one `full_attention` layer WITHOUT positions; in every
attention sublayer RMS norms over the head dimension on q and k (before the
rope) and an output gate, `(o * sigmoid(x W_g)) W_o`; four RMS norms a layer
(sandwich: on each sublayer's input and on its output before the residual
add); the embedding's output times sqrt(hidden size) (`mup_enabled`); 6
leading dense SwiGLU layers of width 12288, then 256 routed experts of width
3072, the 4 best of sigmoid(x W_r) + a bias an expert, their weights the
scores without the bias, renormalised over the four and times `route_scale`
2.448, and one shared expert of width 3072 on every token; an untied head.

What the program does not do as published, said where it matters:

- The periods of layer kinds count the layers AFTER the leading dense ones,
  and those are window layers all (`transformer.is_full_layer`). The
  published `layer_types` puts its full layers at 3, 7, ..., 59 whatever the
  kind of MLP, so at the published depth (6 dense layers) it has a full layer
  among the dense ones (layer 3) and its first period of expert layers starts
  two layers in. A cut whose dense layers are sliding ones and whose expert
  layers are whole periods (the benchmark's: chipbench/configs/
  trinity-large-preview.json) is the published stack; the `large-preview`
  size at all 60 layers is not, and no chip here holds it.
- The rope rotates halves (`ops/rope.py`), which is what the published
  `afmoe` code does.
- The select bias is drawn (`select_bias_init_std`): its published initial
  value 0 could not tell selection on score + bias from selection on score.
  `load_balance_coeff` and the bias's update rule are training's: not built.
- All norm gains start at 1. The model card's "depth-scaled sandwich norm" is
  an initial value, void under seeded weights.

An expert layer may hold one chip's SHARE of the experts
(`MoEConfig.experts_held`, `first_expert`): the deployment divides each
layer's 256 experts over 8 chips, and the router, its bias and the four a
token keep their width (ops/moe.py).

Not built: the prefix cache over the ring of the window layers, a
tensor-parallel mesh, LoRA and the PD transfer (llm/engine.py refuses each at
construction with its reason), and the exchange between chips that hold
different shares."""

from __future__ import annotations

import math

import jax.numpy as jnp

from ray_tpu.models.transformer import MoEConfig, TransformerConfig

SIZES = {
    # two leading dense layers, two whole periods of three window layers and
    # a full one; a window shorter than the test prompts; 64 routed experts
    # so that eight shares of 8 add up (tests/test_trinity.py); a group of 3
    # query heads a KV head (no power of two, as the published 6)
    "tiny": dict(d_model=64, n_layers=10, n_dense_layers=2, n_heads=6, n_kv_heads=2,
                 d_head=16, d_ff=32, d_ff_dense=96, num_experts=64, top_k=4,
                 window=32, window_period=4),
    "large-preview": dict(d_model=3072, n_layers=60, n_dense_layers=6, n_heads=48,
                          n_kv_heads=8, d_head=128, d_ff=3072, d_ff_dense=12288,
                          num_experts=256, top_k=4, window=4096, window_period=4),
}


def trinity_config(size: str = "large-preview", *, vocab_size: int = 200192,
                   max_seq_len: int = 262144, dtype=jnp.bfloat16,
                   experts_held: int | None = None, first_expert: int = 0,
                   select_bias_init_std: float = 0.0,
                   **overrides) -> TransformerConfig:
    base = dict(SIZES[size])
    moe = MoEConfig(num_experts=base.pop("num_experts"), top_k=base.pop("top_k"),
                    n_shared_experts=1, capacity_factor=None, aux_coef=0.0,
                    score_func="sigmoid", routed_scaling_factor=2.448,
                    select_bias_init_std=select_bias_init_std,
                    experts_held=experts_held, first_expert=first_expert)
    base.update(
        vocab_size=vocab_size,
        max_seq_len=max_seq_len,
        norm="rms",
        norm_eps=1e-5,
        act="swiglu",
        pos="rope",
        rope_theta=10000.0,
        full_layer_rope=False,
        bias=False,
        tie_embeddings=False,
        sandwich_norms=True,
        attn_gate=True,
        qk_norm=True,
        embedding_multiplier=math.sqrt(base["d_model"]),
        moe=moe,
        dtype=dtype,
    )
    base.update(overrides)
    return TransformerConfig(**base)
