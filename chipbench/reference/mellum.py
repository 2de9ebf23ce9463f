"""Mellum 2 (JetBrains/Mellum2-12B-A2.5B-Instruct `config.json`, model_type
`mellum`), the whole forward pass over the whole context: plain `jax.numpy`,
float32, `jax.default_matmul_precision("highest")`, the window as a dense
mask, no kernels, no cache, no batching.

Sizes as published: d = 2304, 32 query heads on 4 KV heads of 128 (groups of
8), no attention bias, RMSNorm eps 1e-6, untied embedding and head,
vocabulary 98,304, 64 experts of width 896, 8 a token, window 1024, periods
of 4 layers. `intermediate_size` (7168) is unused: every entry of
`mlp_layer_types` is `sparse`.

Block: h = x + Attn_l(RMSNorm(x)); y = h + MoE(RMSNorm(h)); a final RMSNorm;
logits = y W_head [d, V].

Attn_l: q = x W_q [H, 128], k = x W_k, v = x W_v [Hkv, 128], rope on q and k
by the layer's kind, scores q k^T / sqrt(128), softmax in float32, output
W_o. Query i sees key j iff j <= i and, on a `sliding_attention` layer,
i - j < window (the window counts the query's own position, as
`transformers` applies `sliding_window`). Layer l is a `full_attention` layer
iff l % 4 == 3.

Rope, halves rotated, head width D = 128, theta 500,000. Window layers:
plain, inv_freq_j = theta^(-2j/D). Full layers, YaRN as `transformers`
computes it (`_compute_yarn_parameters`, truncate on): dim(r) = D ln(M / (2
pi r)) / (2 ln theta) with M = 8192 original positions; low = floor(dim(32))
= 18, high = ceil(dim(1)) = 35; ramp_j = clip((j - low) / (high - low), 0,
1); inv_freq'_j = inv_freq_j (1 - ramp_j) + (inv_freq_j / 16) ramp_j; cos and
sin both times the attention factor 1.2772588722239782 (= 0.1 ln 16 + 1), so
a full layer's scores carry its square.

MoE, h = RMSNorm(x): p = softmax(h W_r) over the 64 experts in float32, the
8 largest, w = p_sel / sum(p_sel) (`norm_topk_prob`), y = sum_e w_e W_down,e
(silu(W_gate,e h) * W_up,e h). No shared expert, no bias, no scaling factor,
nothing dropped. Experts one by one, each over the tokens routed to it
(padded to a multiple of 128, the padding weighted 0), so that the float32
copy of what is multiplied fits beside bfloat16 weights that fill most of a
chip.

The serve check's search asks for many forwards over ONE context that differ
in the routing of one token in one layer. The residual stream after l layers
depends on the routing of those l layers alone, so `forward` keeps it by
that routing (`_KEPT`, the newest few dozen) and starts from the deepest
layer whose routing so far it has seen: the same arithmetic, not done twice
(as reference/kimi_vl.py does).

Departures from the published description, each stated in the configuration
file: the multi-token-prediction head the model card speaks of is not built
(the config has no key for it, and serving does not run it); no q/k norms
(the config has no key for them); the epsilon and every size come from
`sizes`.

Parameter tree (the program's): embed [V, d], lm_head [d, V], final_norm/w,
layers/* with a leading layer dimension — norm{1,2}/w, attn/{wq [d,H,Dh],
wk, wv [d,Hkv,Dh], wo [H,Dh,d]}, mlp/{router [d,E], gate, up [E,d,F],
down [E,F,d]}.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _rms_norm(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * w.astype(F32)


def yarn_bounds(head_dim: int, theta: float, yarn: dict) -> tuple:
    """(low, high) of the ramp: frequency indices, truncated."""
    def dim(turns):
        return (head_dim * math.log(yarn["original_max_position"] / (2 * math.pi * turns))
                / (2 * math.log(theta)))

    return math.floor(dim(yarn["beta_fast"])), math.ceil(dim(yarn["beta_slow"]))


def _rope(x, theta, yarn):
    """x [T, H, D] at positions 0..T-1, half-rotation; `yarn` a tuple of
    (factor, original_max_position, beta_fast, beta_slow, attention_factor)
    or None for the plain rope."""
    T, _, D = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=F32) / D)
    scale = 1.0
    if yarn is not None:
        factor, original, fast, slow, scale = yarn
        low, high = yarn_bounds(D, theta, {
            "original_max_position": original, "beta_fast": fast, "beta_slow": slow})
        ramp = jnp.clip((jnp.arange(D // 2, dtype=F32) - low) / (high - low), 0.0, 1.0)
        inv = inv * (1.0 - ramp) + inv / factor * ramp
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None]
    c, s = jnp.cos(ang)[:, None] * scale, jnp.sin(ang)[:, None] * scale
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def _at(stacked, i):
    return jax.lax.dynamic_index_in_dim(stacked, i, 0, keepdims=False)


@functools.partial(jax.jit, static_argnames=("theta", "eps", "window", "yarn"))
def _attention(layers, i, x, *, theta, eps, window, yarn):
    """`window` None: a full layer (rope by `yarn`); else a window layer."""
    a = {k: _at(v, i).astype(F32) for k, v in layers["attn"].items()}
    T = x.shape[0]
    h = _rms_norm(x, _at(layers["norm1"]["w"], i), eps)
    q = _rope(jnp.einsum("te,ehd->thd", h, a["wq"]), theta, yarn)
    k = _rope(jnp.einsum("te,ehd->thd", h, a["wk"]), theta, yarn)
    v = jnp.einsum("te,ehd->thd", h, a["wv"])
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(q.shape[-1]))
    gap = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]          # i - j
    seen = gap >= 0 if window is None else (gap >= 0) & (gap < window)
    s = jnp.where(seen[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    return x + jnp.einsum("thd,hde->te", o, a["wo"])


@functools.partial(jax.jit, static_argnames=("top_k", "eps"))
def _route(layers, i, x, depth, *, top_k, eps):
    """(normed input, gates [T, E]: renormalised top-k weights, 0 elsewhere,
    margin [T, 2]: router-logit gap between the last expert taken by plain
    top-k and the first, and the second, one left out). `depth` [T] int: 0
    is plain top-k; at 1 (2) the first (second) expert left out is taken
    instead of the last one taken: the other side of a tie."""
    h = _rms_norm(x, _at(layers["norm2"]["w"], i), eps)
    logits = h @ _at(layers["mlp"]["router"], i).astype(F32)
    probs = jax.nn.softmax(logits, axis=-1)
    top, idx = jax.lax.top_k(probs, top_k + 2)
    last = (top_k - 1 + depth)[:, None]
    top = jnp.concatenate([top[:, :top_k - 1],
                           jnp.take_along_axis(top, last, axis=1)], axis=1)
    idx = jnp.concatenate([idx[:, :top_k - 1],
                           jnp.take_along_axis(idx, last, axis=1)], axis=1)
    top = top / top.sum(-1, keepdims=True)
    gates = jnp.zeros_like(probs).at[jnp.arange(x.shape[0])[:, None], idx].set(top)
    ranked = jnp.sort(logits, axis=-1)
    margin = ranked[:, -top_k, None] - ranked[:, -top_k - 2:-top_k][:, ::-1]
    return h, gates, margin


@functools.partial(jax.jit, static_argnames=("cap",))
def _experts(mlp, i, h, gates, *, cap):
    """sum_e gates[:, e] SwiGLU_e(h), expert after expert, each over the
    tokens routed to it: at most `cap`, the rest of its `cap` rows are token
    0 weighted 0."""
    def one(e, y):
        def w(name):
            return _at(_at(mlp[name], i), e).astype(F32)

        gate_e = jax.lax.dynamic_index_in_dim(gates, e, 1, keepdims=False)
        taken = gate_e > 0
        rows = jnp.nonzero(taken, size=cap, fill_value=0)[0]
        weight = jnp.where(jnp.arange(cap) < taken.sum(), gate_e[rows], 0.0)
        he = h[rows]
        ye = (jax.nn.silu(he @ w("gate")) * (he @ w("up"))) @ w("down")
        return y.at[rows].add(ye * weight[:, None])

    return jax.lax.fori_loop(0, gates.shape[1], one, jnp.zeros_like(h))


_KEPT = {"params": None, "tokens": None, "after": {}}


def forward(params, tokens, sizes: dict, depth=None):
    """tokens [T] int32 -> (logits [T, V] float32, margin [L, T, 2]: see
    `_route` — a token whose margin is within rounding of zero may
    legitimately be routed otherwise by a router fed rounded activations).
    `depth` [L, T] int routes the marked tokens of the marked layers the
    other way (None: top-k)."""
    with jax.default_matmul_precision("highest"):
        L, T = sizes["n_layers"], tokens.shape[0]
        depth = jnp.zeros((L, T), jnp.int32) if depth is None else jnp.asarray(depth, jnp.int32)
        eps, theta, period = float(sizes["norm_eps"]), float(sizes["rope_theta"]), sizes["window_period"]
        y = sizes.get("yarn")
        yarn = y and (float(y["factor"]), int(y["original_max_position"]),
                      float(y["beta_fast"]), float(y["beta_slow"]),
                      float(y["attention_factor"]))
        routing, context = np.asarray(depth, np.int8), np.asarray(tokens).tobytes()
        if _KEPT["params"] is not params or _KEPT["tokens"] != context:
            _KEPT.update(params=params, tokens=context, after={})
        after = _KEPT["after"]    # the routing of the first l layers -> (x, margins) after them
        first = max((l for l in range(L + 1) if (l, routing[:l].tobytes()) in after), default=0)
        x, margins = after.get((first, routing[:first].tobytes()),
                               (params["embed"][tokens].astype(F32), ()))
        margins, layers = list(margins), params["layers"]
        for i in range(first, L):
            full = i % period == period - 1
            x = _attention(layers, i, x, theta=theta, eps=eps,
                           window=None if full else int(sizes["window"]),
                           yarn=yarn if full else None)
            h, gates, margin = _route(layers, i, x, depth[i], top_k=sizes["top_k"], eps=eps)
            margins.append(margin)
            most = int(np.asarray((gates > 0).sum(0)).max())
            x = x + _experts(layers["mlp"], i, h, gates, cap=-(-most // 128) * 128)
            after[i + 1, routing[:i + 1].tobytes()] = (x, tuple(margins))
            while len(after) > 48:
                del after[next(iter(after))]
        x = _rms_norm(x, params["final_norm"]["w"], eps)
        return x @ params["lm_head"].astype(F32), jnp.stack(margins)
