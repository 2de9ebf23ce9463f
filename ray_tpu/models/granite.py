"""Granite 4.0-H (ibm-granite/granite-4.0-h-micro `config.json`, model_type
`granitemoehybrid`): a dense decoder of 40 layers in which 36 have a Mamba-2
mixer where a transformer has attention (`layer_types`: attention at layers
5, 15, 25, 35: one a period of ten, at place 5) and every layer a SwiGLU MLP
of width `shared_intermediate_size` (`num_local_experts` 0: no routed experts,
no router). The four attention layers are grouped-query (32 heads on 8 KV
heads of 64) WITHOUT positions (`position_embedding_type` "nope": no rope,
nothing learned) and scale their scores by `attention_multiplier` (1/64), not
by 1/sqrt(64). Four scalars: the embedding times `embedding_multiplier` (12),
every sublayer's output times `residual_multiplier` (0.22) before the
residual add, the logits divided by `logits_scaling` (8); the head is the
embedding (`tie_word_embeddings`). RMS norms with eps 1e-5.

The Mamba-2 mixer (ops/ssm.py, transformer.mamba_mixer): d_inner = 2 * 2048 =
64 heads of 64, one group of B and C of 128 states, a causal depthwise
convolution of width 4 with a bias over x | B | C, dt = softplus(. + dt_bias)
a head, A = -exp(A_log) a head, a skip D a head, the gate silu(z) applied
BEFORE the RMS norm over all of d_inner, no projection biases.

Where the config has no key (the gate before the norm, one norm group, no
limit on dt, the state's precision) the file of the benchmark's configuration
says what was assumed (chipbench/configs/granite-4.0-h-micro.json `assumed`).

Not built: the routed experts of the family's larger members
(`num_local_experts` > 0: since PR 50 experts stand beside recurrent layers of
the gated delta rule's kind, models/solar_open2.py, and `_check` still refuses
them beside Mamba-2's, which no test has run), the prefix cache, preemption and
resume of a row,
a tensor-parallel mesh, LoRA and the PD transfer for a recurrent state
(llm/engine.py refuses each at construction with its reason)."""

from __future__ import annotations

import jax.numpy as jnp

from ray_tpu.models.transformer import SSMConfig, TransformerConfig

SIZES = {
    # one whole period in the published order (5 state-space layers, the
    # attention layer, 4 more), a chunk shorter than the test prompts
    # (KV heads of 64 as published: two of them fill a packed row of the cache)
    "tiny": dict(d_model=64, n_layers=10, n_heads=4, n_kv_heads=2, d_head=64, d_ff=96,
                 ssm=SSMConfig(n_heads=8, d_head=16, d_state=16, chunk=8),
                 attention_multiplier=1 / 32),
    "4.0-h-micro": dict(d_model=2048, n_layers=40, n_heads=32, n_kv_heads=8, d_head=64,
                        d_ff=8192, ssm=SSMConfig(n_heads=64, d_head=64, d_state=128,
                                                 chunk=256),
                        attention_multiplier=0.015625),
}


def granite_config(size: str = "4.0-h-micro", *, vocab_size: int = 100352,
                   max_seq_len: int = 131072, dtype=jnp.bfloat16,
                   **overrides) -> TransformerConfig:
    base = dict(SIZES[size])
    base.update(
        vocab_size=vocab_size,
        max_seq_len=max_seq_len,
        norm="rms",
        norm_eps=1e-5,
        act="swiglu",
        pos="none",
        bias=False,
        tie_embeddings=True,
        # KV heads of 64: two a row of 128 lanes in the paged cache
        kv_packed=True,
        embedding_multiplier=12.0,
        residual_multiplier=0.22,
        logits_scaling=8.0,
        # randomly initialised weights for a family with these multipliers
        # (they stand for a checkpoint in tests and benchmark): the
        # projections back into the residual at 0.02 with no 1 / sqrt(2 L) on
        # top, because `residual_multiplier` IS the family's depth scaling
        # (with both, forty layers add a twentieth of what the embedding
        # times 12 brings and the model computes its last input token);
        # queries and keys at 0.09, so that scores times 1/64 spread by about
        # 2 and the softmax attends to some positions and not to all alike
        # (1/head_dim presumes queries and keys that have learned to align;
        # at 0.02 the scores spread by 0.1); values and the attention's
        # output at 0.06, so that the four attention layers together bring
        # about a fifth of what the forty layers add
        init_out_std=0.02,
        init_attn_std=(0.09, 0.06),
        dtype=dtype,
    )
    base.update(overrides)
    return TransformerConfig(**base)
