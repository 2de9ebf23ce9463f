"""Upstage Solar Open 2 (upstage/Solar-Open2-250B `config.json`, model_type
`solar_open2`, "250B-A15B"): 48 layers of hidden size 4096 in periods of four,
one grouped-query attention layer (`gqa_layers` 0, 4, ..., 44: place 0 of each
period) and then three gated delta-rule linear-attention layers (Kimi Delta
Attention, arXiv 2510.26692; the config's `kda_*` and `linear_attn_config`
keys), and in EVERY layer (`first_k_dense_replace` 0) 320 routed SwiGLU
experts of width 1280, the 8 best a token, beside one shared expert of the
same width; pre-norm RMS (eps 1e-5), two norms a layer, an untied head.

The attention layer: 64 heads on 8 KV heads of 128 WITHOUT positions
(`use_rope` false) and with an output gate (`use_gqa_gate`), `(softmax(q k^T /
sqrt(128)) v * sigmoid(x W_g)) W_o`: the program's `attn_gate` with
`pos="none"`; no q/k norms (the config has no key for one).

The KDA layer (ops/ssm.py, transformer.kda_mixer; 64 heads, keys and values of
128): q, k, v = silu(conv4(x W_.)) each through its own causal depthwise
convolution without a bias; q and k of unit length a head, q times 128^-1/2; a
log decay a KEY CHANNEL g = -exp(A_log[h]) softplus((x W_fa) W_fb + dt_bias)
through a low-rank pair of inner width 128 (`kda_use_full_proj` false); beta =
2 sigmoid(x W_b) in (0, 2) (`kda_allow_neg_eigval`); a head's state S [128,
128] float32, S_t = (I - beta k k^T) Diag(exp(g)) S_{t-1} + beta k v^T, o =
S^T q; y = (RMSNorm_128(o) * sigmoid((x W_ga) W_gb + b_g)) W_o.

The experts: s = sigmoid(x W_r) over all 320; the 8 best of s + b (b a bias an
expert, no groups); their weights the chosen s renormalised over the eight
(`norm_topk_prob`) times `routed_scaling_factor` 1. A layer may hold one
chip's SHARE (`MoEConfig.experts_held`, `first_expert`): the deployment
divides each layer's 320 experts over 8 chips, and the router, its bias and the
eight a token keep their width (ops/moe.py).

What the config has no key for is ASSUMED, and the benchmark's configuration
file says each with its reason (chipbench/configs/solar-open2-250b.json
`assumed`): sigmoid scores with a select bias and no groups (the DeepSeek-V3
convention of the config's key names), the shared expert's width
`n_shared_experts * moe_intermediate_size`, the gates' inner width 128, the
elementwise attention gate, the L2 norms' eps 1e-6, and how `A_log` and
`dt_bias` are drawn (`transformer._kda_params`: a step's decay spreads over
about 0.9-0.999). The select bias is drawn (`select_bias_init_std`): a
published initial value of 0 could not tell selection on s + b from selection
on s. The rope keys are void (`use_rope` false).

Not built: the prefix cache (a block is reusable only with the recurrent
state at its end: 13 MB a row here, and no snapshot is kept beside a block's
pages), preemption and resume of a row, a tensor-parallel mesh, LoRA and the PD
transfer for a recurrent state (llm/engine.py refuses each at construction
with its reason); the exchange between chips that hold different shares (no
code stands in for the absent chips or their traffic); the train path's
backward through the chunked delta rule has no test and no cell."""

from __future__ import annotations

import jax.numpy as jnp

from ray_tpu.models.transformer import KDAConfig, MoEConfig, TransformerConfig

SIZES = {
    # two whole periods (attention, KDA, KDA, KDA); a scan chunk shorter than
    # the test prompts with two sub-blocks; 64 routed experts so that eight
    # shares of 8 add up (tests/test_solar_open2.py)
    "tiny": dict(d_model=64, n_layers=8, n_heads=4, n_kv_heads=2, d_head=16, d_ff=32,
                 num_experts=64, top_k=4,
                 ssm=KDAConfig(n_heads=4, d_head=16, gate_rank=8, chunk=32)),
    "250b": dict(d_model=4096, n_layers=48, n_heads=64, n_kv_heads=8, d_head=128, d_ff=1280,
                 num_experts=320, top_k=8,
                 ssm=KDAConfig(n_heads=64, d_head=128, gate_rank=128, chunk=64)),
}


def solar_open2_config(size: str = "250b", *, vocab_size: int = 196608,
                       max_seq_len: int = 1048576, dtype=jnp.bfloat16,
                       experts_held: int | None = None, first_expert: int = 0,
                       select_bias_init_std: float = 0.0,
                       **overrides) -> TransformerConfig:
    base = dict(SIZES[size])
    moe = MoEConfig(num_experts=base.pop("num_experts"), top_k=base.pop("top_k"),
                    n_shared_experts=1, capacity_factor=None, aux_coef=0.0,
                    score_func="sigmoid", routed_scaling_factor=1.0,
                    select_bias_init_std=select_bias_init_std,
                    experts_held=experts_held, first_expert=first_expert)
    base.update(
        vocab_size=vocab_size,
        max_seq_len=max_seq_len,
        norm="rms",
        norm_eps=1e-5,
        act="swiglu",
        pos="none",
        bias=False,
        tie_embeddings=False,
        attn_gate=True,
        moe=moe,
        dtype=dtype,
    )
    base.update(overrides)
    return TransformerConfig(**base)
