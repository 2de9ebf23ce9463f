"""Decoder-only transformer core shared by the GPT-2 / Llama / Mixtral /
Kimi-VL (DeepSeek-V3-style) / Mellum / Ouro / Granite-hybrid families.
Pure-functional: params are pytrees (layers stacked on a leading
dim and consumed by lax.scan — compile-fast and pipeline-ready), logical axis
trees drive mesh sharding, compute runs in bf16 with f32 accumulators.

A stack may mix two kinds of attention layer (`TransformerConfig.window`):
window layers, whose queries see the last `window` positions and which take
the plain rope, and full layers (every `window_period`-th), which see
everything and take the YaRN rope where `yarn` is set. The layers stay
stacked [L, ...]; `scan_layers` scans whole periods and tells each block its
kind.

A stack may mix attention layers with state-space layers
(`TransformerConfig.ssm`, a Mamba-2 mixer in place of attention: ops/ssm.py):
one attention layer at a fixed place in every period of layers. The two
kinds are stacked apart (`params["layers"]`, `params["ssm_layers"]`: no layer
carries weights of the other kind) and `scan_layers` scans whole periods,
each kind static in its own trace of the block. The recurrent layers may be
of a second kind (`KDAConfig` in place of `SSMConfig`: a gated delta-rule
linear attention, `kda_mixer`), and a stack of THAT kind may have routed
experts in every layer and the attention layer's output gate.

A stack may be looped (`TransformerConfig.n_passes`): the same stacked
weights applied several times, the final norm closing every pass;
`scan_layers` scans the passes outside the layers.

The reference framework contains no model code (models live in user code /
vLLM); these families exist so the framework's train/serve/bench paths are
self-contained (BASELINE.md configs 1, 2, 4).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu import ops


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8
    top_k: int = 2
    # capacity of the one-hot dispatch, C = ceil(k*N/E * capacity_factor),
    # tokens over it dropped. None, or E/k and more (C >= N: nothing could be
    # dropped), is dropless: the sorted grouped dispatch (ops.moe_sorted)
    capacity_factor: float | None = 1.25
    aux_coef: float = 0.01
    score_func: str = "softmax"            # "softmax" | "sigmoid" (+ select bias)
    routed_scaling_factor: float = 1.0     # sigmoid routing: times the k weights
    n_shared_experts: int = 0              # one MLP of n * d_ff, every token
    select_bias_init_std: float = 0.0      # sigmoid routing: published init is 0
    # a layer that holds a SHARE of the experts (one chip's of a deployment
    # that divides each layer's experts over several): experts [first_expert,
    # first_expert + experts_held) of the num_experts the router scores. The
    # router, its bias and top_k keep their width; gate / up / down are the
    # held experts' alone; a routed slot whose expert is absent is not computed
    # and adds nothing (ops/moe.py). None: every expert is held
    experts_held: int | None = None
    first_expert: int = 0

    @property
    def dropless(self) -> bool:
        return (self.capacity_factor is None
                or self.capacity_factor >= self.num_experts / self.top_k)

    @property
    def share(self) -> bool:
        return self.experts_held is not None

    @property
    def held(self) -> int:
        """Experts whose weights the layer has."""
        return self.num_experts if self.experts_held is None else self.experts_held

    def slots_a_held_expert(self, n_tokens: int) -> float | None:
        """What `ops.sorted_pays` asks of a share beside the tokens of a call:
        the routed slots a held expert gets in the mean (None: all are held)."""
        return n_tokens * self.top_k / self.num_experts if self.share else None


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    """State-space (Mamba-2) layers in the stack: every layer but the one at
    `attn_at` of each `period` has the mixer below in place of attention.
    d_inner = n_heads * d_head; one group of B and C shared by the heads."""
    n_heads: int = 64
    d_head: int = 64
    d_state: int = 128
    d_conv: int = 4
    chunk: int = 256                       # the prefill scan's chunk
    period: int = 10
    attn_at: int = 5                       # layer l attends iff l % period == attn_at

    @property
    def d_inner(self) -> int:
        return self.n_heads * self.d_head

    @property
    def conv_dim(self) -> int:
        """Columns the convolution runs over: x | B | C."""
        return self.d_inner + 2 * self.d_state

    @property
    def in_dim(self) -> int:
        """Columns of the published in_proj: z | x B C | dt."""
        return self.d_inner + self.conv_dim + self.n_heads

    @property
    def state_shape(self) -> tuple:
        """A row's recurrent state of one layer (float32)."""
        return (self.n_heads, self.d_head, self.d_state)


@dataclasses.dataclass(frozen=True)
class KDAConfig:
    """Gated delta-rule linear-attention layers (Kimi Delta Attention, arXiv
    2510.26692) in the stack, in `TransformerConfig.ssm`'s place: every layer
    but the one at `attn_at` of each `period` has `kda_mixer` in place of
    attention. A head's state is a matrix [d_head (keys), d_head (values)],
    decayed a KEY CHANNEL and corrected by the delta rule; q, k and v each go
    through a causal depthwise convolution of width `d_conv` (no bias); the
    decay's and the output gate's projections are low-rank pairs of inner
    width `gate_rank`. A sibling of `SSMConfig`, not a mode of it: no field of
    the one means anything in the other's mixer, and what the cache and the
    layer scans ask of either is `period`, `attn_at`, `d_conv`, `chunk`,
    `conv_dim` and `state_shape`."""
    n_heads: int = 64
    d_head: int = 128                      # of keys and of values
    d_conv: int = 4
    gate_rank: int = 128
    chunk: int = 64                        # the prefill scan's chunk
    period: int = 4
    attn_at: int = 0                       # layer l attends iff l % period == attn_at

    @property
    def d_inner(self) -> int:
        return self.n_heads * self.d_head

    @property
    def conv_dim(self) -> int:
        """Columns the convolutions run over: q | k | v."""
        return 3 * self.d_inner

    @property
    def state_shape(self) -> tuple:
        """A row's recurrent state of one layer (float32)."""
        return (self.n_heads, self.d_head, self.d_head)


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int | None = None          # None → MHA
    d_head: int | None = None              # None → d_model // n_heads
    d_ff: int = 2048
    norm: str = "rms"                      # "rms" | "ln"
    act: str = "swiglu"                    # "swiglu" | "gelu"
    pos: str = "rope"                      # "rope" | "learned" | "none"
    rope_theta: float = 10000.0
    max_seq_len: int = 2048
    tie_embeddings: bool = False
    bias: bool = False                     # attn/mlp biases (GPT-2 style)
    moe: MoEConfig | None = None
    remat: bool = True                     # checkpoint each layer (HBM for FLOPs)
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    norm_eps: float = 1e-6                 # RMSNorm epsilon of the block norms
    # leading layers with a dense MLP of width d_ff_dense before the others
    # (stacked apart, under params["dense_layers"])
    n_dense_layers: int = 0
    d_ff_dense: int | None = None
    # multi-head latent attention (DeepSeek-V2/V3): set kv_lora_rank and the
    # cache is ONE row of kv_lora_rank + qk_rope_head_dim values a token a
    # layer, nothing per head; q/k heads are nope + rope wide, v heads v wide
    kv_lora_rank: int | None = None
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kv_norm_eps: float = 1e-6
    # window attention: query i sees key j iff 0 <= i - j < window, on every
    # layer but the last of each `window_period` (layer l is a full layer iff
    # l % window_period == window_period - 1). None: every layer is full.
    # The periods count the layers AFTER the leading dense ones, which are
    # window layers all (`is_full_layer`)
    window: int | None = None
    window_period: int = 4
    # YaRN on the full layers' rope (ops/rope.py Yarn); window layers take
    # the plain rope of the same theta
    yarn: ops.Yarn | None = None
    # a looped stack: the n_layers layers are applied n_passes times over the
    # SAME weights, the final norm closes every pass and its output is the
    # next pass's input. Each of the n_passes * n_layers layer applications
    # has keys and values of its own: cache plane t * n_layers + l
    n_passes: int = 1
    # a norm on each sublayer's OUTPUT before the residual add (post_attn_norm,
    # post_mlp_norm), beside norm1 / norm2 on its input; their weights start
    # at sandwich_norm_init, which is the family's to choose (models/ouro.py)
    sandwich_norms: bool = False
    sandwich_norm_init: float = 1.0
    # sigmoid(w . x_t + b) on every pass's closed output: the probability of
    # leaving the loop after pass t (`exit_distribution`)
    exit_gate: bool = False
    # recurrent layers in place of attention on all but one layer a period:
    # Mamba-2's (SSMConfig) or the gated delta rule's (KDAConfig)
    ssm: SSMConfig | KDAConfig | None = None
    # scalar multipliers, each inert at its default: the embedding's output
    # times `embedding_multiplier`, every sublayer's output times
    # `residual_multiplier` before the residual add, the logits DIVIDED by
    # `logits_scaling`, and `attention_multiplier` the softmax scale in place
    # of qk_dim ** -0.5 (`softmax_scale`)
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float | None = None
    logits_scaling: float = 1.0
    # the paged cache keeps a token's K (and V) heads packed 128 lanes wide,
    # [.., kv_heads * head_dim / 128, 128], instead of a row of head_dim a
    # head: for head_dim under 128, where the TPU stores [.., Hkv, 64] pools
    # pages-minor and the decode step re-lays both whole, padded to 128
    # lanes, in and out of every step (models/decoding_paged.py)
    kv_packed: bool = False
    # the initialiser's standard deviations where a family does not take the
    # shared ones (0.02, and 0.02 / sqrt(2 n_layers) on every projection back
    # into the residual): `init_out_std` for those projections (a family whose
    # `residual_multiplier` is its depth scaling), `init_attn_std` = (wq and
    # wk, wv and wo) of the attention layers. None: the shared ones
    init_out_std: float | None = None
    init_attn_std: tuple | None = None
    # three things in the attention sublayer, each inert at its default:
    # `attn_gate`: the heads' output times sigmoid(x W_g), W_g [E, H, Dh] (x
    # the sublayer's normed input), before the output projection; `qk_norm`:
    # an RMS norm over the head dimension on q and on k (one weight of
    # head_dim each a layer, shared by the heads; eps norm_eps), before the
    # rope; `full_layer_rope` False: in a stack with window layers the FULL
    # layers take no positions at all (the window layers keep the rope)
    attn_gate: bool = False
    qk_norm: bool = False
    full_layer_rope: bool = True

    @property
    def n_full_layers(self) -> int:
        if not self.window:
            return self.n_layers
        return (self.n_layers - self.n_dense_layers) // self.window_period

    @property
    def n_attn_layers(self) -> int:
        return self.n_layers // self.ssm.period if self.ssm else self.n_layers

    @property
    def n_ssm_layers(self) -> int:
        return self.n_layers - self.n_attn_layers

    @property
    def kda(self) -> bool:
        """Whether the recurrent layers are the gated delta rule's."""
        return isinstance(self.ssm, KDAConfig)

    @property
    def n_planes(self) -> int:
        """Layer applications of one token that hold K and V of their own."""
        return self.n_passes * self.n_attn_layers

    @property
    def kv_row(self) -> tuple:
        """A token's K (or V) of one layer as the paged cache stores it."""
        if self.kv_packed:
            return (self.kv_heads * self.head_dim // 128, 128)
        return (self.kv_heads, self.head_dim)

    @property
    def softmax_scale(self) -> float:
        """What the attention scores are multiplied by before the softmax:
        the one place that says it, for the prefill, a chunk's continuation
        and the decode step alike."""
        if self.attention_multiplier is not None:
            return self.attention_multiplier
        return self.qk_dim ** -0.5

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def head_dim(self) -> int:
        return self.d_head or self.d_model // self.n_heads

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank is not None

    @property
    def qk_dim(self) -> int:
        """Width the attention scores are scaled by."""
        return (self.qk_nope_head_dim + self.qk_rope_head_dim if self.mla
                else self.head_dim)

    @property
    def rope_dim(self) -> int:
        return self.qk_rope_head_dim if self.mla else self.head_dim

    @property
    def latent_lanes(self) -> int:
        """Columns of a cached MLA row: (c | k_rope) padded to whole lanes."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    def num_params(self) -> int:
        leaves = jax.tree.leaves(jax.eval_shape(lambda: init(jax.random.PRNGKey(0), self)))
        return sum(math.prod(l.shape) for l in leaves)


# ------------------------------------------------------------------ init

def _norm_params(cfg, key, scale: float = 1.0):
    p = {"w": jnp.full((cfg.d_model,), scale, cfg.param_dtype)}
    if cfg.norm == "ln":
        p["b"] = jnp.zeros((cfg.d_model,), cfg.param_dtype)
    return p


def _out_std(cfg) -> float:
    """Of a projection back into the residual stream."""
    if cfg.init_out_std is not None:
        return cfg.init_out_std
    return 0.02 / math.sqrt(2 * cfg.n_layers)


def _dense_mlp_params(cfg, key, d_ff=None):
    E, F = cfg.d_model, d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    std = 0.02
    out_std = _out_std(cfg)
    if cfg.act == "swiglu":
        p = {
            "wi_gate": jax.random.normal(k1, (E, F), cfg.param_dtype) * std,
            "wi_up": jax.random.normal(k2, (E, F), cfg.param_dtype) * std,
            "wo": jax.random.normal(k3, (F, E), cfg.param_dtype) * out_std,
        }
    else:
        p = {
            "wi": jax.random.normal(k1, (E, F), cfg.param_dtype) * std,
            "wo": jax.random.normal(k3, (F, E), cfg.param_dtype) * out_std,
        }
        if cfg.bias:
            p["bi"] = jnp.zeros((F,), cfg.param_dtype)
            p["bo"] = jnp.zeros((E,), cfg.param_dtype)
    return p


def _moe_params(cfg, key):
    E, F, X = cfg.d_model, cfg.d_ff, cfg.moe.num_experts
    H = cfg.moe.held  # the experts whose weights are here: all, or a share
    k0, k1, k2, k3 = jax.random.split(key, 4)
    std = 0.02
    out_std = 0.02 / math.sqrt(2 * cfg.n_layers)
    p = {
        "router": jax.random.normal(k0, (E, X), cfg.param_dtype) * std,
        "gate": jax.random.normal(k1, (H, E, F), cfg.param_dtype) * std,
        "up": jax.random.normal(k2, (H, E, F), cfg.param_dtype) * std,
        "down": jax.random.normal(k3, (H, F, E), cfg.param_dtype) * out_std,
    }
    if cfg.moe.score_func == "sigmoid":
        p["router_bias"] = (jax.random.normal(jax.random.fold_in(k0, 1), (X,), jnp.float32)
                            * cfg.moe.select_bias_init_std)
    if cfg.moe.n_shared_experts:
        p["shared"] = _dense_mlp_params(cfg, jax.random.fold_in(k0, 2),
                                        cfg.moe.n_shared_experts * F)
    return p


def _mla_params(cfg, ks):
    E, H, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    std = 0.02
    out_std = 0.02 / math.sqrt(2 * cfg.n_layers)
    return {
        "wq": jax.random.normal(ks[0], (E, H, dn + dr), cfg.param_dtype) * std,
        "w_dkv": jax.random.normal(ks[1], (E, r + dr), cfg.param_dtype) * std,
        "kv_norm": jnp.ones((r,), cfg.param_dtype),
        "w_ukv": jax.random.normal(ks[2], (r, H, dn + dv), cfg.param_dtype) * std,
        "wo": jax.random.normal(ks[3], (H, dv, E), cfg.param_dtype) * out_std,
    }


def _layer_params(cfg, key, dense: bool = False):
    """One layer; `dense` makes it one of the leading dense layers."""
    E, H, Hkv, Dh = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim
    ks = jax.random.split(key, 6)
    qk_std, v_std, out_std = 0.02, 0.02, _out_std(cfg)
    if cfg.init_attn_std is not None:
        qk_std, v_std = cfg.init_attn_std
        out_std = v_std
    if cfg.mla:
        attn = _mla_params(cfg, ks)
    else:
        attn = {
            "wq": jax.random.normal(ks[0], (E, H, Dh), cfg.param_dtype) * qk_std,
            "wk": jax.random.normal(ks[1], (E, Hkv, Dh), cfg.param_dtype) * qk_std,
            "wv": jax.random.normal(ks[2], (E, Hkv, Dh), cfg.param_dtype) * v_std,
            "wo": jax.random.normal(ks[3], (H, Dh, E), cfg.param_dtype) * out_std,
        }
    if cfg.bias:
        attn["bq"] = jnp.zeros((H, Dh), cfg.param_dtype)
        attn["bk"] = jnp.zeros((Hkv, Dh), cfg.param_dtype)
        attn["bv"] = jnp.zeros((Hkv, Dh), cfg.param_dtype)
        attn["bo"] = jnp.zeros((E,), cfg.param_dtype)
    if cfg.attn_gate:
        attn["wg"] = jax.random.normal(jax.random.fold_in(ks[0], 1), (E, H, Dh),
                                       cfg.param_dtype) * qk_std
    if cfg.qk_norm:
        attn["q_norm"] = jnp.ones((Dh,), cfg.param_dtype)
        attn["k_norm"] = jnp.ones((Dh,), cfg.param_dtype)
    if dense:
        mlp = _dense_mlp_params(cfg, ks[5], cfg.d_ff_dense)
    else:
        mlp = _moe_params(cfg, ks[5]) if cfg.moe else _dense_mlp_params(cfg, ks[5])
    layer = {
        "norm1": _norm_params(cfg, ks[4]),
        "attn": attn,
        "norm2": _norm_params(cfg, ks[4]),
        "mlp": mlp,
    }
    if cfg.sandwich_norms:
        for name in ("post_attn_norm", "post_mlp_norm"):
            layer[name] = _norm_params(cfg, ks[4], cfg.sandwich_norm_init)
    return layer


def _mixer_params(cfg, key):
    """A Mamba-2 mixer: in_proj (z | x B C | dt, no bias), the depthwise
    convolution over x B C, a head's dt bias, A = -exp(A_log) and skip D, the
    gated norm's weight, out_proj. The published in_proj [E, in_dim] is kept
    as its three column blocks (`in_z`, `in_xbc`, `in_dt`): 2 d_inner + 2 N +
    H columns are no whole number of lanes (8,512 = 66.5 x 128 at the
    published size), and the compiler re-laid the one matrix of the whole
    stack, transposed, at the head of every decode step (2 x 1.17 GB of
    temporaries in the AOT account). dt_bias, A_log and D start as the
    published initialiser has them (dt log-uniform in [1e-3, 1e-1] through
    the inverse softplus, A uniform in [1, 16], D 1) and stay float32."""
    s, E = cfg.ssm, cfg.d_model
    ks = jax.random.split(key, 7)
    dt = jnp.exp(jax.random.uniform(ks[2], (s.n_heads,), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    return {
        "in_z": jax.random.normal(ks[0], (E, s.d_inner), cfg.param_dtype) * 0.02,
        "in_xbc": jax.random.normal(ks[5], (E, s.conv_dim), cfg.param_dtype) * 0.02,
        "in_dt": jax.random.normal(ks[6], (E, s.n_heads), cfg.param_dtype) * 0.02,
        "conv_w": jax.random.normal(ks[1], (s.d_conv, s.conv_dim), cfg.param_dtype)
                  * s.d_conv ** -0.5,
        "conv_b": jnp.zeros((s.conv_dim,), cfg.param_dtype),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(jax.random.uniform(ks[3], (s.n_heads,), jnp.float32, 1.0, 16.0)),
        "D": jnp.ones((s.n_heads,), jnp.float32),
        "norm": jnp.ones((s.d_inner,), cfg.param_dtype),
        "out_proj": jax.random.normal(ks[4], (s.d_inner, E), cfg.param_dtype) * _out_std(cfg),
    }


def _kda_params(cfg, key):
    """A KDA mixer: the published q_proj, k_proj and v_proj as the three
    column blocks of one matrix (`in_qkv`, q | k | v) and their three
    depthwise convolutions as the column blocks of one (`conv_w`, no bias);
    the decay's low-rank pair (`f_a`, `f_b`), its bias and a head's A =
    -exp(A_log); beta's projection; the output gate's low-rank pair and bias
    (`g_a`, `g_b`, `g_bias`); the gated norm's weight over a head's values;
    out_proj. `dt_bias` and `A_log` stay float32 and are drawn so that a
    step's decay exp(-exp(A_log) softplus(dt_bias)) spreads over about
    0.9-0.999 at a zero input: softplus(dt_bias) log-uniform in [1e-3, 1e-1]
    a channel (through the inverse softplus, as Mamba-2's dt) and exp(A_log)
    uniform in [0.5, 1.5] a head."""
    s, E = cfg.ssm, cfg.d_model
    ks = jax.random.split(key, 10)
    dt = jnp.exp(jax.random.uniform(ks[2], (s.d_inner,), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))

    def normal(k, shape, std=0.02):
        return jax.random.normal(k, shape, cfg.param_dtype) * std

    return {
        "in_qkv": normal(ks[0], (E, s.conv_dim)),
        "conv_w": normal(ks[1], (s.d_conv, s.conv_dim), s.d_conv ** -0.5),
        "f_a": normal(ks[5], (E, s.gate_rank)),
        "f_b": normal(ks[6], (s.gate_rank, s.d_inner)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(jax.random.uniform(ks[3], (s.n_heads,), jnp.float32, 0.5, 1.5)),
        "w_beta": normal(ks[7], (E, s.n_heads)),
        "g_a": normal(ks[8], (E, s.gate_rank)),
        "g_b": normal(ks[9], (s.gate_rank, s.d_inner)),
        "g_bias": jnp.zeros((s.d_inner,), cfg.param_dtype),
        "norm": jnp.ones((s.d_head,), cfg.param_dtype),
        "out_proj": normal(ks[4], (s.d_inner, E), _out_std(cfg)),
    }


def _ssm_layer_params(cfg, key):
    """One recurrent layer: the mixer where an attention layer has `attn`."""
    ks = jax.random.split(key, 6)
    mixer = _kda_params(cfg, ks[0]) if cfg.kda else _mixer_params(cfg, ks[0])
    mlp = _moe_params(cfg, ks[5]) if cfg.moe else _dense_mlp_params(cfg, ks[5])
    return {"norm1": _norm_params(cfg, ks[4]), "mixer": mixer,
            "norm2": _norm_params(cfg, ks[4]), "mlp": mlp}


def is_attn_layer(cfg: TransformerConfig, l: int) -> bool:
    return cfg.ssm is None or l % cfg.ssm.period == cfg.ssm.attn_at


def is_full_layer(cfg: TransformerConfig, l: int) -> bool:
    """Whether layer `l` sees every earlier position: every layer of a stack
    without a window; with one, the last of each period, the periods counted
    from the first layer after the leading dense ones (all window layers)."""
    if not cfg.window:
        return True
    j = l - cfg.n_dense_layers
    return j >= 0 and j % cfg.window_period == cfg.window_period - 1


def _check(cfg: TransformerConfig) -> None:
    if cfg.pos not in ("rope", "learned", "none"):
        raise ValueError(f"pos {cfg.pos!r}: 'rope', 'learned' or 'none'")
    # `embedding_multiplier` is one product in `embed_tokens`, which every path
    # takes, and goes with any stack; the other three are built with Granite's
    scaled = (cfg.residual_multiplier != 1.0 or cfg.logits_scaling != 1.0
              or cfg.attention_multiplier is not None)
    if (cfg.ssm or scaled) and (cfg.mla or cfg.window is not None or cfg.n_dense_layers
                                or cfg.n_passes > 1 or cfg.sandwich_norms
                                or cfg.bias or cfg.qk_norm):
        raise ValueError(
            "state-space layers (ssm: Mamba-2's, or the gated delta rule's) and the "
            "scalar multipliers of the residual, "
            "the logits and the softmax are built for a stack run once with "
            "per-head K and V and no biases: not with latent attention, window "
            "layers, leading dense layers, a looped stack, sandwich norms or q/k "
            "norms")
    if (cfg.ssm or scaled) and (cfg.moe or cfg.attn_gate) and (
            scaled or not cfg.kda or (cfg.moe and not cfg.moe.dropless)):
        raise ValueError(
            "experts in every layer and an attention gate beside state-space layers "
            "are built for the gated delta rule's layers (KDAConfig) with dropless "
            "experts and no scalar multipliers: Mamba-2 layers and the multipliers "
            "of the residual, the logits and the softmax go with a dense stack "
            "without the gate, which is the one stack that has them")
    if cfg.kv_packed and (cfg.mla or cfg.window is not None or 128 % cfg.head_dim
                          or cfg.kv_heads * cfg.head_dim % 128):
        raise ValueError(
            f"kv_packed packs whole KV heads of {cfg.head_dim} into rows of 128 "
            f"lanes: per-head K and V of one kind of layer, a head_dim that "
            f"divides 128 and {cfg.kv_heads} KV heads that fill whole rows")
    if cfg.ssm is not None:
        s = cfg.ssm
        if (cfg.act != "swiglu" or cfg.norm != "rms" or s.period < 2
                or not 0 <= s.attn_at < s.period or cfg.n_layers % s.period):
            raise ValueError(
                f"state-space layers in periods of {s.period} (attention at "
                f"{s.attn_at}) need whole periods in n_layers {cfg.n_layers}, "
                "RMS norms and SwiGLU")
        if cfg.kda and s.chunk % min(ops.ssm.KDA_SUB, s.chunk):
            raise ValueError(
                f"KDAConfig.chunk {s.chunk}: a multiple of {ops.ssm.KDA_SUB}, or under it")
    if cfg.mla and (cfg.bias or cfg.pos != "rope" or cfg.n_kv_heads is not None):
        raise ValueError("latent attention (kv_lora_rank) is built with rope, "
                         "without biases and without grouped KV heads")
    if not 0 <= cfg.n_dense_layers < cfg.n_layers:
        raise ValueError(f"n_dense_layers {cfg.n_dense_layers} must leave at "
                         f"least one of the {cfg.n_layers} layers")
    if cfg.n_dense_layers and (cfg.act != "swiglu" or cfg.bias):
        raise ValueError("leading dense layers are SwiGLU without biases")
    if cfg.moe and cfg.moe.score_func not in ("softmax", "sigmoid"):
        raise ValueError(f"MoEConfig.score_func {cfg.moe.score_func!r}: "
                         "'softmax' or 'sigmoid'")
    if cfg.moe and cfg.moe.score_func == "sigmoid" and not cfg.moe.dropless:
        raise ValueError("sigmoid routing is dropless: capacity_factor None")
    if cfg.yarn is not None and cfg.pos != "rope":
        raise ValueError("yarn rescales rotary positions: pos='rope'")
    if cfg.n_passes < 1:
        raise ValueError(f"n_passes {cfg.n_passes}: a stack is run at least once")
    if (cfg.n_passes > 1 or cfg.exit_gate) and (
            cfg.mla or cfg.window is not None or cfg.n_dense_layers):
        raise ValueError(
            "a looped stack (n_passes) and the exit gate are built for per-head "
            "K and V in a stack of one kind of layer: not with latent attention "
            "(kv_lora_rank), window layers or leading dense layers")
    if cfg.mla and (cfg.sandwich_norms or cfg.attn_gate or cfg.qk_norm):
        raise ValueError(
            "sandwich norms, the attention gate and q/k norms are built for "
            "per-head K and V: not with latent attention (kv_lora_rank), whose "
            "blocks add their sublayers' outputs as they are")
    if cfg.window is not None:
        if cfg.mla or cfg.pos != "rope":
            raise ValueError(
                "window layers are built for per-head K and V with rope (on the "
                "window layers at least: full_layer_rope): not with latent "
                "attention (kv_lora_rank) or learned positions")
        after_dense = cfg.n_layers - cfg.n_dense_layers
        if cfg.window < 1 or cfg.window_period < 2 or after_dense % cfg.window_period:
            raise ValueError(
                f"window {cfg.window} over periods of {cfg.window_period} layers "
                f"(the last of each a full layer) needs whole periods in the "
                f"{after_dense} layers after the {cfg.n_dense_layers} leading dense "
                f"ones (n_layers {cfg.n_layers})")
    elif not cfg.full_layer_rope:
        raise ValueError(
            "full_layer_rope False takes the positions off the full layers of a "
            "stack that has window layers too (window); a stack of full layers "
            "without positions is pos='none'")
    if cfg.moe and cfg.moe.share:
        moe = cfg.moe
        if not (moe.dropless and moe.experts_held >= 1 and moe.first_expert >= 0
                and moe.first_expert + moe.experts_held <= moe.num_experts):
            raise ValueError(
                f"a share of the experts, [{moe.first_expert}, {moe.first_expert} + "
                f"{moe.experts_held}) of {moe.num_experts}, lies within them and is "
                "dropless (capacity_factor None): a slot over a capacity and a "
                "slot of an absent expert are not told apart")


def scan_layers(block, carry, params, cfg: TransformerConfig, *per_layer,
                close=None, ssm_per_layer=()):
    """`block(carry, layer params)`, or with `per_layer` trees (all the
    layers on their leading dimension) `block(carry, (layer params, *their
    slices))`, over every layer in depth order: one lax.scan a stack of
    layers of one kind, the scans' outputs joined along the layer dimension.

    The routed experts of a dropless stack are not sliced by the scan: the
    body closes over them whole and is told its layer (`mlp["layer"]`), for
    `ops.moe_sorted` to multiply them where they lie.

    With window layers (cfg.window) the scan is over whole periods and the
    body calls `block(..., window=<bool>)`, the kind static: once for the
    period's window layers (a scan of their own), once for its full layer.

    With state-space layers (cfg.ssm) the scan is over whole periods too and
    the body calls `block(..., ssm=<bool>)`: a scan over the state-space
    layers before the period's attention layer, that layer, a scan over those
    after it. The two kinds are stacked apart, so everything is BY KIND:
    `per_layer` leads with the attention layers, `ssm_per_layer` with the
    state-space layers, and the outputs come back as (the attention layers',
    the state-space layers'), each in depth order.

    `close(carry, t) -> carry` ends pass `t` over the stack (the callers'
    final norm, `close_pass`). A looped stack (cfg.n_passes = T > 1) is an
    outer lax.scan over the passes whose body is the scan of the stack and
    then `close`: the weights are closed over whole, the same every pass;
    the `per_layer` trees and the outputs lead with the T * L planes, plane
    t * L + l being layer l's application in pass t, and the outer scan
    hands the inner one its [L, ...] slice."""
    T, L = cfg.n_passes, cfg.n_layers
    if cfg.ssm is not None:
        carry, out = _scan_hybrid(block, carry, params, cfg, per_layer, ssm_per_layer)
        return (carry if close is None else close(carry, 0)), out
    if T == 1:
        carry, out = _scan_stack(block, carry, params, cfg, per_layer)
        return (carry if close is None else close(carry, 0)), out
    if close is None:
        raise ValueError(
            f"a looped stack (n_passes {T}) needs `close`: the final norm "
            "ends every pass and its output is the next pass's input")

    def one_pass(c, xs):
        t, *planes = xs
        with jax.named_scope("ray_tpu:loop_pass"):
            c, out = _scan_stack(block, c, params, cfg, tuple(planes))
            return close(c, t), out

    carry, out = jax.lax.scan(one_pass, carry, (
        jnp.arange(T, dtype=jnp.int32),
        *(jax.tree.map(lambda a: a.reshape(T, L, *a.shape[1:]), t) for t in per_layer)))
    return carry, jax.tree.map(lambda a: a.reshape(T * L, *a.shape[2:]), out)


def _scan_stack(block, carry, params, cfg: TransformerConfig, per_layer):
    """One pass over the stack: `scan_layers` without the passes."""
    if cfg.window is not None:
        return _scan_periods(block, carry, params, cfg, per_layer)
    outs, dense = [], cfg.n_dense_layers
    # (stacked layer params, index of their first layer): the leading dense
    # layers, where the model has them, then the rest
    for stack, first in ([(params["dense_layers"], 0)] if dense else []) + [
            (params["layers"], dense)]:
        n = jax.tree.leaves(stack)[0].shape[0]
        sliced = tuple(
            t if n == cfg.n_layers else jax.tree.map(lambda a: a[first:first + n], t)
            for t in per_layer)
        mlp = stack["mlp"]
        if "router" in mlp and cfg.moe.dropless:
            whole = {k: mlp[k] for k in ("gate", "up", "down")}
            rest = {**stack, "mlp": {k: v for k, v in mlp.items() if k not in whole}}

            def body(c, xs, whole=whole):
                layer_p, layer, *more = xs
                layer_p = {**layer_p, "mlp": {**layer_p["mlp"], **whole, "layer": layer}}
                return block(c, (layer_p, *more) if more else layer_p)

            xs = (rest, jnp.arange(n, dtype=jnp.int32)) + sliced
        else:
            body, xs = block, (stack,) + sliced if sliced else stack
        carry, out = jax.lax.scan(body, carry, xs)
        outs.append(out)
    if len(outs) == 1:
        return carry, outs[0]
    return carry, jax.tree.map(lambda *a: jnp.concatenate(a, axis=0), *outs)


def _scan_periods(block, carry, params, cfg: TransformerConfig, per_layer):
    """One scan over the periods, and inside it one over the period's window
    layers, then its full layer: two traces of `block`, each with its kind
    static. Every layer is taken out of the stacked trees where they lie, by
    the scans' own counters: trees folded to [L / period, period, ...] for
    the scan to slice made the compiler re-lay whole stacked weights on
    every call."""
    dense, period = cfg.n_dense_layers, cfg.window_period
    L = cfg.n_layers - dense  # the layers the periods count
    stack, whole = params["layers"], {}
    if "router" in stack["mlp"] and cfg.moe.dropless:
        whole = {k: stack["mlp"][k] for k in ("gate", "up", "down")}
        stack = {**stack, "mlp": {k: v for k, v in stack["mlp"].items() if k not in whole}}

    def at(trees, i):
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), trees)

    def one(c, layer, window):
        # `layer` counts from the first layer after the leading dense ones;
        # the `per_layer` trees lead with every layer of the stack
        layer_p, more = at(stack, layer), at(per_layer, dense + layer if dense else layer)
        if whole:
            layer_p = {**layer_p, "mlp": {**layer_p["mlp"], **whole, "layer": layer}}
        return block(c, (layer_p, *more) if more else layer_p, window=window)

    def body(c, first):
        c, outs = jax.lax.scan(lambda c, i: one(c, first * period + i, True), c,
                               jnp.arange(period - 1, dtype=jnp.int32))
        c, out = one(c, first * period + period - 1, False)
        return c, jax.tree.map(lambda a, b: jnp.concatenate([a, b[None]]), outs, out)

    led = None
    if dense:  # the leading dense layers: window layers, a scan of their own

        def dense_one(c, i):
            layer_p, more = at(params["dense_layers"], i), at(per_layer, i)
            return block(c, (layer_p, *more) if more else layer_p, window=True)

        carry, led = jax.lax.scan(dense_one, carry, jnp.arange(dense, dtype=jnp.int32))
    carry, out = jax.lax.scan(body, carry, jnp.arange(L // period, dtype=jnp.int32))
    out = jax.tree.map(lambda a: a.reshape(L, *a.shape[2:]), out)
    if led is not None:
        out = jax.tree.map(lambda a, b: jnp.concatenate([a, b]), led, out)
    return carry, out


def _scan_hybrid(block, carry, params, cfg: TransformerConfig, per_layer, ssm_per_layer):
    """One scan over the periods; inside it the state-space layers before the
    period's attention layer (a scan of their own), that layer, the
    state-space layers after it: two traces of `block`, each with its kind
    static. Layers are taken out of the stack of their kind where they lie,
    by the scans' own counters (`_scan_periods` says why)."""
    period, at = cfg.ssm.period, cfg.ssm.attn_at
    # the routed experts of a dropless stack stay whole, a kind, and the
    # block is told its layer (`_scan_stack`)
    stacks, whole = {}, {}
    for ssm, name in ((False, "layers"), (True, "ssm_layers")):
        stacks[ssm], mlp = params[name], params[name]["mlp"]
        if "router" in mlp and cfg.moe.dropless:
            whole[ssm] = {k: mlp[k] for k in ("gate", "up", "down")}
            stacks[ssm] = {**params[name],
                           "mlp": {k: v for k, v in mlp.items() if k not in whole[ssm]}}

    def one(c, i, ssm):
        layer_p, *more = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
            (stacks[ssm], *(ssm_per_layer if ssm else per_layer)))
        if ssm in whole:
            layer_p = {**layer_p, "mlp": {**layer_p["mlp"], **whole[ssm], "layer": i}}
        return block(c, (layer_p, *more) if more else layer_p, ssm=ssm)

    def run(c, first, n):
        return jax.lax.scan(lambda c, i: one(c, first + i, True), c,
                            jnp.arange(n, dtype=jnp.int32))

    def body(c, p):
        c, before = run(c, p * (period - 1), at)
        c, out = one(c, p, False)
        c, after = run(c, p * (period - 1) + at, period - 1 - at)
        return c, (out, jax.tree.map(lambda a, b: jnp.concatenate([a, b]), before, after))

    carry, (out, ssm_out) = jax.lax.scan(
        body, carry, jnp.arange(cfg.n_layers // period, dtype=jnp.int32))
    return carry, (out, jax.tree.map(
        lambda a: a.reshape(cfg.n_ssm_layers, *a.shape[2:]), ssm_out))


def rope_tables(cfg: TransformerConfig, window: bool = False):
    """(cos, sin) of one kind of layer: YaRN's (cfg.yarn) on full layers."""
    return ops.rope_frequencies(cfg.rope_dim, cfg.max_seq_len, theta=cfg.rope_theta,
                                yarn=None if window else cfg.yarn)


def rope_by_kind(cfg: TransformerConfig) -> dict:
    """{window: (cos, sin)} for the kinds of layer the stack has, keyed as
    `scan_layers` tells a block its kind ((None, None) for a kind without
    rope: every kind where cfg.pos is not "rope", the full layers where
    cfg.full_layer_rope is off)."""
    kinds = (False, True) if cfg.window else (False,)
    return {w: rope_tables(cfg, w)
            if cfg.pos == "rope" and (w or cfg.full_layer_rope) else (None, None)
            for w in kinds}


def kind_index(cfg: TransformerConfig) -> list:
    """Each layer's index among the layers of its own kind, in depth order:
    where its pages, or its cached prefix, lie in the arrays of that kind."""
    index, seen = [], {False: 0, True: 0}
    for l in range(cfg.n_layers):
        full = is_full_layer(cfg, l)
        index.append(seen[full])
        seen[full] += 1
    return index


def close_pass(h, gates, t, params, cfg: TransformerConfig):
    """The end of pass `t` over the stack: (the final norm of h [..., E],
    which in a looped stack is the next pass's input; `gates` [T, ...] with
    the exit gate's sigmoid(w . x_t + b) of that output at `t`, float32:
    None, untouched, for a model without the gate)."""
    h = _norm(h, params["final_norm"], cfg)
    if gates is not None:
        gate = params["exit_gate"]
        lam = jax.nn.sigmoid(jnp.einsum("...e,e->...", h.astype(jnp.float32),
                                        gate["w"].astype(jnp.float32))
                             + gate["b"].astype(jnp.float32))
        gates = jax.lax.dynamic_update_index_in_dim(gates, lam, t, 0)
    return h, gates


def exit_distribution(gates):
    """The gates lambda [T, ...] of the T passes -> p [T, ...], the
    probability of leaving the loop after pass t: lambda_t times the
    probability of having stayed so far, and for the last pass all that is
    left. Sums to one over the passes; its running sum is the exit CDF, and
    a token leaves after the first pass at which that reaches the model's
    threshold (1 as published: every token takes every pass)."""
    stay = jnp.cumprod(1.0 - gates, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]])
    return jnp.concatenate([(gates * before)[:-1], before[-1:]])


def init(key, cfg: TransformerConfig):
    _check(cfg)
    k_emb, k_pos, k_layers, k_head = jax.random.split(key, 4)
    keys = jax.random.split(k_layers, cfg.n_layers)
    if cfg.ssm is None:
        attn_keys = keys[cfg.n_dense_layers:]
    else:  # a layer's key is its own whatever its kind
        attn = np.asarray([is_attn_layer(cfg, l) for l in range(cfg.n_layers)])
        attn_keys = keys[attn]
    params = {
        "embed": jax.random.normal(k_emb, (cfg.vocab_size, cfg.d_model), cfg.param_dtype) * 0.02,
        "layers": jax.vmap(lambda k: _layer_params(cfg, k))(attn_keys),
        "final_norm": _norm_params(cfg, k_head),
    }
    if cfg.ssm is not None:
        params["ssm_layers"] = jax.vmap(lambda k: _ssm_layer_params(cfg, k))(keys[~attn])
    if cfg.n_dense_layers:
        params["dense_layers"] = jax.vmap(lambda k: _layer_params(cfg, k, dense=True))(
            keys[:cfg.n_dense_layers])
    if cfg.pos == "learned":
        params["pos_embed"] = jax.random.normal(k_pos, (cfg.max_seq_len, cfg.d_model), cfg.param_dtype) * 0.02
    if not cfg.tie_embeddings:
        params["lm_head"] = jax.random.normal(k_head, (cfg.d_model, cfg.vocab_size), cfg.param_dtype) * 0.02
    if cfg.exit_gate:
        params["exit_gate"] = {
            "w": jax.random.normal(jax.random.fold_in(k_head, 1), (cfg.d_model,),
                                   cfg.param_dtype) * 0.02,
            "b": jnp.zeros((), cfg.param_dtype)}
    return params


def logical_axes(cfg: TransformerConfig):
    """Same tree shape as init(), leaves = tuples of logical dim names.
    Stacked layer params get a leading 'layers' dim."""
    norm = {"w": ("embed",)} if cfg.norm == "rms" else {"w": ("embed",), "b": ("embed",)}
    attn = {
        "wq": ("embed", "heads", "head_dim"),
        "wk": ("embed", "kv_heads", "head_dim"),
        "wv": ("embed", "kv_heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
    }
    if cfg.mla:
        attn = {"wq": ("embed", "heads", "head_dim"), "w_dkv": ("embed", None),
                "kv_norm": (None,), "w_ukv": (None, "heads", "head_dim"),
                "wo": ("heads", "head_dim", "embed")}
    if cfg.bias:
        attn.update({"bq": ("heads", "head_dim"), "bk": ("kv_heads", "head_dim"),
                     "bv": ("kv_heads", "head_dim"), "bo": ("embed",)})
    if cfg.attn_gate:
        attn["wg"] = ("embed", "heads", "head_dim")
    if cfg.qk_norm:
        attn.update({"q_norm": ("head_dim",), "k_norm": ("head_dim",)})
    swiglu = {"wi_gate": ("embed", "mlp"), "wi_up": ("embed", "mlp"), "wo": ("mlp", "embed")}
    if cfg.moe:
        mlp = {"router": ("embed", None), "gate": ("expert", "embed", "mlp"),
               "up": ("expert", "embed", "mlp"), "down": ("expert", "mlp", "embed")}
        if cfg.moe.score_func == "sigmoid":
            mlp["router_bias"] = (None,)
        if cfg.moe.n_shared_experts:
            mlp["shared"] = swiglu
    elif cfg.act == "swiglu":
        mlp = swiglu
    else:
        mlp = {"wi": ("embed", "mlp"), "wo": ("mlp", "embed")}
        if cfg.bias:
            mlp.update({"bi": ("mlp",), "bo": ("embed",)})

    def stacked(mlp):
        layer = {"norm1": norm, "attn": attn, "norm2": norm, "mlp": mlp}
        if cfg.sandwich_norms:
            layer.update(post_attn_norm=norm, post_mlp_norm=norm)
        return jax.tree.map(lambda t: ("layers",) + t, layer,
                            is_leaf=lambda x: isinstance(x, tuple))

    out = {
        "embed": ("vocab", "embed"),
        "layers": stacked(mlp),
        "final_norm": norm,
    }
    if cfg.n_dense_layers:
        out["dense_layers"] = stacked(swiglu)
    if cfg.ssm is not None:
        # the mixer's inner width splits like an MLP's; what x, B, C and dt
        # share a matrix with does not
        mixer = {"in_z": ("embed", "mlp"), "in_xbc": ("embed", None),
                 "in_dt": ("embed", None), "conv_w": (None, None), "conv_b": (None,),
                 "dt_bias": (None,), "A_log": (None,), "D": (None,),
                 "norm": ("mlp",), "out_proj": ("mlp", "embed")}
        if cfg.kda:  # the heads' width splits like an MLP's
            mixer = {"in_qkv": ("embed", "mlp"), "conv_w": (None, "mlp"),
                     "f_a": ("embed", None), "f_b": (None, "mlp"), "dt_bias": ("mlp",),
                     "A_log": (None,), "w_beta": ("embed", None), "g_a": ("embed", None),
                     "g_b": (None, "mlp"), "g_bias": ("mlp",), "norm": (None,),
                     "out_proj": ("mlp", "embed")}
        out["ssm_layers"] = jax.tree.map(
            lambda t: ("layers",) + t,
            {"norm1": norm, "mixer": mixer, "norm2": norm,
             "mlp": mlp if cfg.moe else swiglu},
            is_leaf=lambda x: isinstance(x, tuple))
    if cfg.pos == "learned":
        out["pos_embed"] = (None, "embed")
    if not cfg.tie_embeddings:
        out["lm_head"] = ("embed", "vocab")
    if cfg.exit_gate:
        out["exit_gate"] = {"w": ("embed",), "b": ()}
    return out


# ----------------------------------------------------------------- apply

def _norm(x, p, cfg):
    if cfg.norm == "rms":
        return ops.rms_norm(x, p["w"], eps=cfg.norm_eps)
    return ops.layer_norm(x, p["w"], p.get("b"))


def _residual(h, delta, layer_p, post: str, cfg):
    """h + delta, a sublayer's output: through the block's norm `post` first
    where the block has sandwich norms."""
    if cfg.sandwich_norms:
        delta = _norm(delta, layer_p[post], cfg)
    if cfg.residual_multiplier != 1.0:
        delta = delta * jnp.asarray(cfg.residual_multiplier, delta.dtype)
    return h + delta


def embed_tokens(params, tokens, cfg):
    """The embedding's rows of `tokens` in cfg.dtype, times the model's
    `embedding_multiplier`."""
    x = params["embed"].astype(cfg.dtype)[tokens]
    if cfg.embedding_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
    return x


def lm_logits(x, params, cfg):
    """x [..., E] (after the final norm) -> logits [..., V] in cfg.dtype: the
    tied embedding or the head, divided by the model's `logits_scaling`."""
    dt = cfg.dtype
    if cfg.tie_embeddings:
        logits = x @ params["embed"].astype(dt).T
    else:
        logits = x @ params["lm_head"].astype(dt)
    if cfg.logits_scaling != 1.0:
        logits = logits / jnp.asarray(cfg.logits_scaling, logits.dtype)
    return logits


def mixer_project(x, p, cfg):
    """Normed x [T, E] -> (z [T, d_inner] the gate, xBC [T, conv_dim] before
    the convolution, dt [T, H] float32 = softplus(. + dt_bias))."""
    dt = jax.nn.softplus((x @ p["in_dt"].astype(cfg.dtype)).astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    return x @ p["in_z"].astype(cfg.dtype), x @ p["in_xbc"].astype(cfg.dtype), dt


def mixer_split(xBC, cfg):
    """Convolved xBC [T, conv_dim] after its SiLU -> (x [T, H, P], B [T, N],
    C [T, N])."""
    s = cfg.ssm
    xBC = jax.nn.silu(xBC)
    return (xBC[..., :s.d_inner].reshape(*xBC.shape[:-1], s.n_heads, s.d_head),
            xBC[..., s.d_inner:s.d_inner + s.d_state], xBC[..., s.d_inner + s.d_state:])


def mixer_out(y, x, z, p, cfg):
    """y [T, H, P] float32 (h_t C_t) + D x, gated by silu(z) FIRST, then the
    RMS norm over all of d_inner (one group), then out_proj -> [T, E]."""
    y = y + p["D"].astype(jnp.float32)[:, None] * x.astype(jnp.float32)
    y = y.reshape(*y.shape[:-2], cfg.ssm.d_inner) * jax.nn.silu(z.astype(jnp.float32))
    y = ops.rms_norm(y, p["norm"], eps=cfg.norm_eps).astype(cfg.dtype)
    return y @ p["out_proj"].astype(cfg.dtype)


def mamba_mixer(x, p, cfg, length=None, state=None, tail=None):
    """The Mamba-2 mixer over one sequence, normed x [T, E] -> (its output
    before the residual [T, E], the state after position length - 1 [H, P, N]
    float32, the convolution's tail there [d_conv - 1, conv_dim]). `length`:
    the real positions (None: all T); past them dt is 0, so padding leaves
    the state as it was. `state` / `tail`: what came before position 0 (a
    chunk's continuation; None: a row's start)."""
    s, T = cfg.ssm, x.shape[0]
    z, xBC, dt = mixer_project(x, p, cfg)
    if tail is None:
        tail = jnp.zeros((s.d_conv - 1, s.conv_dim), xBC.dtype)
    if length is not None:
        dt = jnp.where((jnp.arange(T) < length)[:, None], dt, 0.0)
    xs, B, C = mixer_split(ops.causal_conv(xBC, tail, p["conv_w"], p["conv_b"]), cfg)
    y, state = ops.ssm_chunk_scan(xs, dt, -jnp.exp(p["A_log"].astype(jnp.float32)), B, C,
                                  chunk=s.chunk, state=state, dtype=cfg.dtype)
    return (mixer_out(y, xs, z, p, cfg), state,
            ops.conv_tail(xBC, tail, T if length is None else length))


def kda_project(x, p, cfg):
    """Normed x [T, E] -> (qkv [T, 3 H D] before the convolutions, g [T, H, D]
    float32 < 0: a key channel's log decay, -exp(A_log) softplus(x W_fa W_fb +
    dt_bias); beta [T, H] float32 = 2 sigmoid(x W_b), in (0, 2): negative
    eigenvalues allowed; the output gate before its sigmoid [T, H, D]
    float32)."""
    s, dt, f32 = cfg.ssm, cfg.dtype, jnp.float32
    heads = (*x.shape[:-1], s.n_heads, s.d_head)
    low = ((x @ p["f_a"].astype(dt)) @ p["f_b"].astype(dt)).astype(f32)
    g = -jnp.exp(p["A_log"].astype(f32))[:, None] * jax.nn.softplus(
        low + p["dt_bias"].astype(f32)).reshape(heads)
    beta = 2.0 * jax.nn.sigmoid((x @ p["w_beta"].astype(dt)).astype(f32))
    gate = (((x @ p["g_a"].astype(dt)) @ p["g_b"].astype(dt)).astype(f32)
            + p["g_bias"].astype(f32)).reshape(heads)
    return x @ p["in_qkv"].astype(dt), g, beta, gate


def _l2_normed(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _kda_launches(T, cfg, interpret):
    """Whether the mixer's elementwise work over T positions runs in
    `ops.ssm`'s launches here (`interpret`: wherever the shapes tile: tests)."""
    s = cfg.ssm
    return (ops.ssm.kda_mixer_in_kernel(T, s.d_head, s.d_conv)
            or interpret and ops.ssm.kda_mixer_tiles(T, s.d_head, s.d_conv))


def kda_conv(qkv, tail, w, cfg, *, interpret=False):
    """The mixer's convolutions over a sequence: qkv [T, 3 H D] as projected,
    after `tail` -> the float32 sums [T, 3 H D] before their SiLU. One Pallas
    launch where `ops.ssm.kda_mixer_in_kernel` says so (`kda_conv` in a device
    trace: the projection is read as it was written, in the activations'
    dtype), `ops.causal_conv` of a float32 copy elsewhere."""
    if _kda_launches(qkv.shape[0], cfg, interpret):
        return ops.ssm.kda_conv(qkv, tail, w, interpret=interpret)
    return ops.causal_conv(qkv.astype(jnp.float32), tail, w)


def kda_split(qkv, cfg, *, interpret=False):
    """Convolved qkv [T, 3 H D] after its SiLU -> (q, k, v [T, H, D] float32):
    q and k of unit length a head (eps 1e-6 under the root), q times D ** -0.5.
    A sequence's [T, 3 H D] float32 goes through one Pallas launch where
    `ops.ssm.kda_mixer_in_kernel` says so (`kda_split` in a device trace; the
    decode step's few rows never do)."""
    s = cfg.ssm
    heads = lambda x: x.reshape(*x.shape[:-1], s.n_heads, s.d_head)     # noqa: E731

    def xla(qkv):
        qkv = jax.nn.silu(qkv).astype(jnp.float32)
        q, k, v = (heads(qkv[..., i * s.d_inner:(i + 1) * s.d_inner]) for i in range(3))
        return _l2_normed(q) * s.d_head ** -0.5, _l2_normed(k), v

    if qkv.ndim == 2 and qkv.dtype == jnp.float32 and _kda_launches(qkv.shape[0], cfg, interpret):
        return ops.ssm.with_gradient_of(xla, lambda qkv: tuple(heads(x) for x in (
            ops.ssm.kda_split_launch(qkv, interpret=interpret))), qkv)
    return xla(qkv)


def kda_gated_norm(o, gate, w, cfg, *, interpret=False):
    """o [T, H, D] float32 (S_t^T q_t) through the RMS norm over each head's
    values (weight w [D]), times sigmoid(gate) -> [T, H D] in the activations'
    dtype: what out_proj multiplies. A sequence's goes through one Pallas
    launch where `ops.ssm.kda_mixer_in_kernel` says so (`kda_gate_norm` in a
    device trace), so that the product has no float32 producer fused in."""
    flat = lambda x: x.reshape(*x.shape[:-2], cfg.ssm.d_inner)          # noqa: E731

    def xla(o, gate, w):
        return flat(ops.rms_norm(o, w, eps=cfg.norm_eps) * jax.nn.sigmoid(gate)).astype(cfg.dtype)

    if (o.ndim == 3 and o.dtype == gate.dtype == jnp.float32
            and _kda_launches(o.shape[0], cfg, interpret)):
        return ops.ssm.with_gradient_of(xla, lambda o, gate, w: ops.ssm.kda_gate_norm_launch(
            flat(o), flat(gate), w, eps=cfg.norm_eps, dtype=cfg.dtype, interpret=interpret),
            o, gate, w)
    return xla(o, gate, w)


def kda_out(o, gate, p, cfg):
    """o [T, H, D] float32 (S_t^T q_t) through the RMS norm over each head's
    values, times sigmoid(gate), then out_proj -> [T, E]."""
    return kda_gated_norm(o, gate, p["norm"], cfg) @ p["out_proj"].astype(cfg.dtype)


def kda_mixer(x, p, cfg, length=None, state=None, tail=None):
    """The KDA mixer over one sequence, as `mamba_mixer`: normed x [T, E] ->
    (its output before the residual [T, E], the state after position length -
    1 [H, D, D] float32, the convolutions' tail there [d_conv - 1, 3 H D]).
    Past `length` g and beta are 0, so padding leaves the state as it was."""
    s, T = cfg.ssm, x.shape[0]
    qkv, g, beta, gate = kda_project(x, p, cfg)
    if tail is None:
        tail = jnp.zeros((s.d_conv - 1, s.conv_dim), qkv.dtype)
    if length is not None:
        real = jnp.arange(T) < length
        g, beta = jnp.where(real[:, None, None], g, 0.0), jnp.where(real[:, None], beta, 0.0)
    # the convolutions' sums and their SiLU stay float32 (the tails are kept
    # as projected, in the activations' dtype)
    q, k, v = kda_split(kda_conv(qkv, tail, p["conv_w"], cfg), cfg)
    o, state = ops.kda_chunk_scan(q, k, v, g, beta, chunk=s.chunk, state=state)
    return (kda_out(o, gate, p, cfg), state,
            ops.conv_tail(qkv, tail, T if length is None else length))


def recurrent_mixer(cfg):
    """The mixer of the stack's recurrent layers over one sequence:
    `kda_mixer` or `mamba_mixer`, one signature."""
    return kda_mixer if cfg.kda else mamba_mixer


def _to_lanes(x, cfg):
    """x [..., w] with zeros appended up to the cached row's whole lanes."""
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, cfg.latent_lanes - x.shape[-1])])


def _mla_project(x, p, cfg, cos, sin, positions=None):
    """Normed x [B, T, E] -> (q [B, T, H, nope + rope], its rope part
    rotated; the row the cache holds [B, T, latent_lanes]: c = RMSNorm(x
    W_dkv's first kv_lora_rank columns) | k_rope = RoPE(the rest), one head
    shared by all query heads | zeros up to whole lanes)."""
    dt = cfg.dtype
    dn, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    q = jnp.einsum("bte,ehd->bthd", x, p["wq"].astype(dt))
    q = jnp.concatenate(
        [q[..., :dn], ops.apply_rope(q[..., dn:], cos, sin, positions=positions)], axis=-1)
    ckr = x @ p["w_dkv"].astype(dt)
    c = ops.rms_norm(ckr[..., :r], p["kv_norm"], eps=cfg.kv_norm_eps)
    k_rope = ops.apply_rope(ckr[..., None, r:], cos, sin, positions=positions)[..., 0, :]
    return q, _to_lanes(jnp.concatenate([c, k_rope], axis=-1), cfg)


def _mla_expand(latent, p, cfg):
    """Cached rows [B, S, latent_lanes] -> per-head k [B, S, H, nope + rope]
    and v [B, S, H, v]: the form prefill attends in."""
    dn, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    kv = jnp.einsum("bsr,rhd->bshd", latent[..., :r], p["w_ukv"].astype(cfg.dtype))
    k_rope = jnp.broadcast_to(latent[..., None, r:r + cfg.qk_rope_head_dim],
                              kv.shape[:-1] + (cfg.qk_rope_head_dim,))
    return jnp.concatenate([kv[..., :dn], k_rope], axis=-1), kv[..., dn:]


def _mla_absorb_q(q, p, cfg):
    """q [B, H, nope + rope] -> [B, H, latent_lanes] = (q_nope W_uk^T | q_rope
    | 0): its dot with a cached row is the score of the expanded form."""
    dn = cfg.qk_nope_head_dim
    q_abs = jnp.einsum("bhd,rhd->bhr", q[..., :dn],
                       p["w_ukv"][..., :dn].astype(cfg.dtype))
    return _to_lanes(jnp.concatenate([q_abs, q[..., dn:]], axis=-1), cfg)


def _mla_absorb_out(o_lat, p, cfg):
    """Attention output over cached rows [B, H, latent_lanes] (a weighted sum
    of rows: its first kv_lora_rank columns are sum p c) -> [B, H, v]."""
    return jnp.einsum("bhr,rhd->bhd", o_lat[..., :cfg.kv_lora_rank],
                      p["w_ukv"][..., cfg.qk_nope_head_dim:].astype(cfg.dtype))


def qk_normed(q, k, p, cfg):
    """q [..., H, Dh], k [..., Hkv, Dh] through the layer's RMS norms over the
    head dimension (cfg.qk_norm; before the rope), else as they are."""
    if not cfg.qk_norm:
        return q, k
    return (ops.rms_norm(q, p["q_norm"], eps=cfg.norm_eps),
            ops.rms_norm(k, p["k_norm"], eps=cfg.norm_eps))


def attn_gated(out, x, p, cfg):
    """The heads' output `out` [B, T, H, Dh] times sigmoid(x W_g), x [B, T, E]
    the sublayer's normed input (cfg.attn_gate; before the output
    projection), else as it is. The sigmoid in float32."""
    if not cfg.attn_gate:
        return out
    with jax.named_scope("ray_tpu:attn_gate"):
        g = jnp.einsum("bte,ehd->bthd", x, p["wg"].astype(cfg.dtype))
        return out * jax.nn.sigmoid(g.astype(jnp.float32)).astype(out.dtype)


def _attn_block(x, p, cfg, cos, sin, sp_axis, attn_impl, window=None):
    dt = cfg.dtype
    if cfg.mla:
        if sp_axis is not None:
            raise ValueError("latent attention has no sequence-parallel form")
        q, latent = _mla_project(x, p, cfg, cos, sin)
        k, v = _mla_expand(latent, p, cfg)
        # v heads are narrower than q/k heads: not the flash kernel's shape
        out = ops.attention(q, k, v, causal=True, scale=cfg.softmax_scale,
                            impl="reference")
        return jnp.einsum("bthd,hde->bte", out, p["wo"].astype(dt))
    q = jnp.einsum("bte,ehd->bthd", x, p["wq"].astype(dt))
    k = jnp.einsum("bte,ehd->bthd", x, p["wk"].astype(dt))
    v = jnp.einsum("bte,ehd->bthd", x, p["wv"].astype(dt))
    if cfg.bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    q, k = qk_normed(q, k, p, cfg)
    if cos is not None:  # this kind of layer has the rope (`rope_by_kind`)
        if sp_axis is not None:
            # sequence-sharded: offset positions by this shard's start
            idx = jax.lax.axis_index(sp_axis)
            T = x.shape[1]
            positions = idx * T + jnp.arange(T)
            q = ops.apply_rope(q, cos, sin, positions=positions)
            k = ops.apply_rope(k, cos, sin, positions=positions)
        else:
            q = ops.apply_rope(q, cos, sin)
            k = ops.apply_rope(k, cos, sin)
    out = ops.attention(q, k, v, causal=True, scale=cfg.softmax_scale, sp_axis=sp_axis,
                        impl=attn_impl, window=window)
    out = jnp.einsum("bthd,hde->bte", attn_gated(out, x, p, cfg), p["wo"].astype(dt))
    if cfg.bias:
        out = out + p["bo"].astype(dt)
    return out


def _dense_mlp(x, p, cfg):
    dt = cfg.dtype
    if cfg.act == "swiglu":
        h = ops.swiglu(x @ p["wi_gate"].astype(dt), x @ p["wi_up"].astype(dt))
        return h @ p["wo"].astype(dt)
    h = x @ p["wi"].astype(dt)
    if cfg.bias:
        h = h + p["bi"].astype(dt)
    h = ops.gelu(h)
    out = h @ p["wo"].astype(dt)
    if cfg.bias:
        out = out + p["bo"].astype(dt)
    return out


def _moe_mlp(x, p, cfg):
    """x [B, T, E] -> (the experts' output [B, T, E], the auxiliary loss); a
    layer that holds a share of the experts (cfg.moe.share) returns a third
    value, `ops.share_counts` of the call: int32 [2]."""
    dt, moe = cfg.dtype, cfg.moe
    B, T, E = x.shape
    xf = x.reshape(B * T, E)
    if moe.score_func == "sigmoid":
        # the published gate computes in float32 (2048 x 64 a token is free)
        router_logits = jnp.dot(xf.astype(jnp.float32), p["router"].astype(jnp.float32),
                                precision=jax.lax.Precision.HIGHEST)
    else:
        router_logits = (xf @ p["router"].astype(dt)).astype(jnp.float32)
    experts = {k: p[k] for k in ("gate", "up", "down")}
    if moe.dropless:
        if moe.score_func == "sigmoid":
            idx, w, aux = ops.sigmoid_topk(router_logits, p["router_bias"], k=moe.top_k,
                                           scale=moe.routed_scaling_factor)
        else:
            idx, w, aux = ops.softmax_topk(router_logits, k=moe.top_k)
        # of a share the held experts' slots alone, their weights as routed
        mine = ops.held_slots(idx, moe.first_expert, moe.held)[0] if moe.share else idx
        routing = None if ops.sorted_pays(B * T, moe.slots_a_held_expert(B * T)) \
            else ops.onehot_dispatch(mine, w, moe.held, B * T)
    else:
        routing = ops.topk_routing(router_logits, num_experts=moe.num_experts,
                                   k=moe.top_k, capacity_factor=moe.capacity_factor)
        aux = routing.aux_loss
    # a share's products and its shared expert are named in the device trace
    scope = jax.named_scope if moe.share else (lambda name: contextlib.nullcontext())
    if routing is None:
        share = dict(first=moe.first_expert, of=moe.num_experts) if moe.share else {}
        with scope("ray_tpu:experts_held"):
            y = ops.moe_sorted(xf, idx, w, **experts, layer=p.get("layer"), **share)
    else:
        if "layer" in p:  # the stack's experts, whole: this layer's
            experts = jax.tree.map(lambda t: jax.lax.dynamic_index_in_dim(
                t, p["layer"], 0, keepdims=False), experts)

        def expert_fn(pe, xe):
            h = ops.swiglu(xe @ pe["gate"].astype(dt), xe @ pe["up"].astype(dt))
            return h @ pe["down"].astype(dt)

        y = ops.moe_apply(xf, routing, expert_fn, experts)
    if moe.n_shared_experts:  # one MLP, every token, ungated
        with scope("ray_tpu:expert_shared"):
            y = y + _dense_mlp(xf, p["shared"], cfg)
    if moe.share:
        return y.reshape(B, T, E), aux, ops.share_counts(idx, moe.first_expert, moe.held)
    return y.reshape(B, T, E), aux


# What a layer's checkpoint (cfg.remat) keeps for the backward pass: its carry
# and, where its attention ran the flash kernels, their output and log-sum-exp
# (ops/attention.py `_flash` names them), so the backward pass runs the layer
# again but not the forward kernel. Every other layer names nothing and keeps
# its carry alone. One object: a jaxpr's text carries the policy's identity
_LAYER_KEEPS = jax.checkpoint_policies.save_only_these_names(*ops.FLASH_KEPT)


def forward(params, tokens, cfg: TransformerConfig, *, sp_axis: str | None = None,
            attn_impl: str | None = None, return_hidden: bool = False,
            return_exit: bool = False):
    """tokens [B, T] int32 → logits [B, T, V] (cfg.dtype). Returns
    (logits, aux_loss); with return_hidden=True, returns the pre-head hidden
    states [B, T, E] instead of logits; with return_exit=True (a model with
    the exit gate) a third value, `exit_distribution` [n_passes, B, T]."""
    x = embed_tokens(params, tokens, cfg)
    dt = cfg.dtype
    if cfg.ssm is not None and sp_axis is not None:
        raise ValueError("state-space layers have no sequence-parallel form")
    if cfg.pos == "learned":
        T = tokens.shape[1]
        if sp_axis is not None:
            idx = jax.lax.axis_index(sp_axis)
            pos = jax.lax.dynamic_slice_in_dim(params["pos_embed"], idx * T, T)
        else:
            pos = params["pos_embed"][:T]
        x = x + pos.astype(dt)
    if cfg.window is not None and sp_axis is not None:
        raise ValueError("window layers have no sequence-parallel form")
    rope = rope_by_kind(cfg)

    aux_total = jnp.zeros((), jnp.float32)

    def block(carry, layer_p, window=False, ssm=False):
        h, aux, gates = carry
        normed = _norm(h, layer_p["norm1"], cfg)
        if ssm:
            mixer = recurrent_mixer(cfg)
            mixed = jax.vmap(lambda row: mixer(row, layer_p["mixer"], cfg)[0])(normed)
        else:
            mixed = _attn_block(normed, layer_p["attn"], cfg, *rope[window], sp_axis,
                                attn_impl, cfg.window if window else None)
        h = _residual(h, mixed, layer_p, "post_attn_norm", cfg)
        normed = _norm(h, layer_p["norm2"], cfg)
        if "router" in layer_p["mlp"]:
            delta, layer_aux, *_ = _moe_mlp(normed, layer_p["mlp"], cfg)
            aux = aux + layer_aux
        else:
            delta = _dense_mlp(normed, layer_p["mlp"], cfg)
        return (_residual(h, delta, layer_p, "post_mlp_norm", cfg), aux, gates), None

    if cfg.remat:
        inner = block

        def block(carry, layer_p, **kind):
            return jax.checkpoint(functools.partial(inner, **kind),
                                  policy=_LAYER_KEEPS)(carry, layer_p)
    gates = (jnp.zeros((cfg.n_passes,) + tokens.shape, jnp.float32)
             if cfg.exit_gate else None)

    def close(carry, t):
        h, aux, gates = carry
        h, gates = close_pass(h, gates, t, params, cfg)
        return h, aux, gates

    (x, aux_total, gates), _ = scan_layers(block, (x, aux_total, gates), params, cfg,
                                           close=close)
    leave = (exit_distribution(gates),) if return_exit else ()
    if return_hidden:
        return (x, aux_total) + leave
    return (lm_logits(x, params, cfg), aux_total) + leave


def loss_fn(params, tokens, cfg: TransformerConfig, *, sp_axis: str | None = None,
            attn_impl: str | None = None, fused_ce: bool | None = None,
            logits_spec=None, ce_chunk: int | None = None):
    """Next-token LM loss on tokens [B, T]; positions with label -100 ignored.

    fused_ce (default: on for vocab >= 8192) streams the lm_head matmul into
    a chunked cross-entropy so [B,T,V] logits are never materialized.
    logits_spec optionally shards the per-chunk head-matmul output over the
    mesh (vocab dim on tp — see ops.fused_head_cross_entropy)."""
    if fused_ce is None:
        fused_ce = cfg.vocab_size >= 8192
    fused_ce = fused_ce and not cfg.tie_embeddings  # fused path needs lm_head
    if logits_spec is not None and not fused_ce:
        raise ValueError(
            "logits_spec requires the fused-CE path (untied embeddings and "
            "fused_ce enabled); the unfused path would silently materialize "
            "replicated [B,T,V] logits")
    labels = tokens[:, 1:]
    if fused_ce:
        hidden, aux = forward(params, tokens[:, :-1], cfg, sp_axis=sp_axis,
                              attn_impl=attn_impl, return_hidden=True)
        B, T, E = hidden.shape
        loss, _ = ops.fused_head_cross_entropy(
            hidden.reshape(B * T, E), params["lm_head"], labels.reshape(B * T),
            logits_spec=logits_spec, chunk=ce_chunk or 2048)
    else:
        logits, aux = forward(params, tokens[:, :-1], cfg, sp_axis=sp_axis, attn_impl=attn_impl)
        loss, _ = ops.softmax_cross_entropy(logits, labels)
    if cfg.moe:
        loss = loss + cfg.moe.aux_coef * aux / cfg.n_layers
    return loss
