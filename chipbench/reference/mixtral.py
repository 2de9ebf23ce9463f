"""Mixtral (Jiang et al. 2024; mistralai/Mixtral-8x7B-v0.1 `config.json`).

RMSNorm pre-norm blocks, grouped-query causal attention with rotary
positions (half-rotation, theta `rope_theta`), no biases, and a sparse
mixture of SwiGLU experts: router logits -> softmax over all experts ->
top-k -> the k gates renormalised to sum to one -> every token is served by
every one of its k experts. No capacity, nothing dropped. Untied head.

Layer by layer and expert by expert, so that the float32 copy of what is
being multiplied (one expert: 3 x d x d_ff) fits beside bf16 weights that
fill most of a chip. Each token is put through every expert densely and the
unrouted results are masked out: the sample is a few hundred tokens.

Departure from the published config, stated in the configuration file: the
epsilon of RMSNorm is the one given in `sizes["norm_eps"]`.

Parameter tree (the program's): embed [V, d], lm_head [d, V], final_norm/w,
layers/* with a leading layer dimension — norm{1,2}/w, attn/{wq [d,H,Dh],
wk, wv [d,Hkv,Dh], wo [H,Dh,d]}, mlp/{router [d,E], gate, up [E,d,F],
down [E,F,d]}.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms_norm(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * w.astype(F32)


def _rope(x, theta):
    """x [T, H, D] at positions 0..T-1, half-rotation."""
    T, _, D = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=F32) / D)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None]
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def _at(stacked, i):
    return jax.lax.dynamic_index_in_dim(stacked, i, 0, keepdims=False)


@functools.partial(jax.jit, static_argnames=("theta", "eps"))
def _attention(layers, i, x, *, theta, eps):
    a = {k: _at(v, i).astype(F32) for k, v in layers["attn"].items()}
    T = x.shape[0]
    h = _rms_norm(x, _at(layers["norm1"]["w"], i), eps)
    q = _rope(jnp.einsum("te,ehd->thd", h, a["wq"]), theta)
    k = _rope(jnp.einsum("te,ehd->thd", h, a["wk"]), theta)
    v = jnp.einsum("te,ehd->thd", h, a["wv"])
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(q.shape[-1]))
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
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


@jax.jit
def _expert(mlp, i, e, h, gate_e):
    def w(name):
        return _at(_at(mlp[name], i), e).astype(F32)

    y = (jax.nn.silu(h @ w("gate")) * (h @ w("up"))) @ w("down")
    return y * gate_e[:, None]


def forward(params, tokens, sizes: dict, depth=None):
    """tokens [T] int32 -> (logits [T, V] float32, margin [L, T, 2]: see
    `_route` — a token whose margin is within rounding of zero may
    legitimately be routed otherwise by a lower-precision router). `depth`
    [L, T] int routes the marked tokens the other way (None: top-k)."""
    with jax.default_matmul_precision("highest"):
        if depth is None:
            depth = jnp.zeros((sizes["n_layers"], tokens.shape[0]), jnp.int32)
        depth = jnp.asarray(depth, jnp.int32)
        layers = params["layers"]
        x = params["embed"].astype(F32)[tokens]
        margins = []
        for i in range(sizes["n_layers"]):
            x = _attention(layers, i, x, theta=float(sizes["rope_theta"]),
                           eps=float(sizes["norm_eps"]))
            h, gates, margin = _route(layers, i, x, depth[i], top_k=sizes["top_k"],
                                      eps=float(sizes["norm_eps"]))
            margins.append(margin)
            for e in range(sizes["num_experts"]):
                x = x + _expert(layers["mlp"], i, e, h, gates[:, e])
        x = _rms_norm(x, params["final_norm"]["w"], float(sizes["norm_eps"]))
        return x @ params["lm_head"].astype(F32), jnp.stack(margins)
