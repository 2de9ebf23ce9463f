"""Ouro (ByteDance/Ouro-2.6B `config.json`, model_type `ouro`; the Ouro
1.4B / 2.6B LoopLM family): a dense decoder whose L layers are applied T =
`total_ut_steps` times over the SAME weights.

    x_0 = E[tokens]                                   (embedding not scaled)
    for t = 1 .. T:
        h = x_{t-1}
        for l = 1 .. L:
            a = Attn_l(RMS(h; n1_l))                  causal, full, no bias, rope by halves
            h = h + RMS(a; n2_l)                      a includes the output projection
            m = W_down_l(silu(W_gate_l u) * (W_up_l u)),  u = RMS(h; n3_l)
            h = h + RMS(m; n4_l)
        x_t = RMS(h; n_final)                         closes EVERY pass, feeds the next
        lambda_t = sigmoid(w_exit . x_t + b_exit)
    logits = x_T W_head
    p_t = lambda_t prod_{j<t} (1 - lambda_j)  (t < T),   p_T = prod_{j<T} (1 - lambda_j)

Where the norms sit, the norm between passes and the gate's bias are not in
the config: they follow the family's modelling code as the configuration
file's `assumed` states. Float32, `default_matmul_precision("highest")`, a
Python loop over passes and layers, one layer's weights converted at a time,
no cache, no scan, nothing of the program's.

Parameter tree (the program's): embed [V, d], lm_head [d, V], final_norm/w,
exit_gate/{w [d], b []}, layers/* with a leading layer dimension —
norm1/w (n1), post_attn_norm/w (n2), norm2/w (n3), post_mlp_norm/w (n4),
attn/{wq, wk, wv [d, H, Dh], wo [H, Dh, d]}, mlp/{wi_gate, wi_up [d, F],
wo [F, d]}.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms_norm(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * w.astype(F32)


def _rope(x, theta):
    """x [T, H, D] at positions 0..T-1, rotated by halves on all D lanes."""
    T, _, D = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=F32) / D)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None]
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


@functools.partial(jax.jit, static_argnames=("theta", "eps"))
def _layer(layers, i, h, *, theta, eps):
    """One application of layer `i` to h [T, d]."""
    p = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, i, 0, keepdims=False).astype(F32), layers)
    T = h.shape[0]
    u = _rms_norm(h, p["norm1"]["w"], eps)
    q = _rope(jnp.einsum("te,ehd->thd", u, p["attn"]["wq"]), theta)
    k = _rope(jnp.einsum("te,ehd->thd", u, p["attn"]["wk"]), theta)
    v = jnp.einsum("te,ehd->thd", u, p["attn"]["wv"])
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(q.shape[-1]))
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    a = jnp.einsum("thd,hde->te", o, p["attn"]["wo"])
    h = h + _rms_norm(a, p["post_attn_norm"]["w"], eps)
    u = _rms_norm(h, p["norm2"]["w"], eps)
    m = (jax.nn.silu(u @ p["mlp"]["wi_gate"]) * (u @ p["mlp"]["wi_up"])) @ p["mlp"]["wo"]
    return h + _rms_norm(m, p["post_mlp_norm"]["w"], eps)


def passes(params, tokens, sizes: dict):
    """tokens [T] int32 -> (x [n_passes, T, d]: every pass's closed output,
    lambda [n_passes, T]: the exit gate on each)."""
    eps, theta = float(sizes["norm_eps"]), float(sizes["rope_theta"])
    x = params["embed"].astype(F32)[tokens]
    xs, gates = [], []
    for _ in range(sizes["n_passes"]):
        for i in range(sizes["n_layers"]):
            x = _layer(params["layers"], i, x, theta=theta, eps=eps)
        x = _rms_norm(x, params["final_norm"]["w"], eps)
        xs.append(x)
        gates.append(jax.nn.sigmoid(x @ params["exit_gate"]["w"].astype(F32)
                                    + params["exit_gate"]["b"].astype(F32)))
    return jnp.stack(xs), jnp.stack(gates)


def exit_probabilities(params, tokens, sizes: dict):
    """p [n_passes, T]: the probability that a position leaves after pass t."""
    with jax.default_matmul_precision("highest"):
        lam = passes(params, tokens, sizes)[1]
    stay = [jnp.ones_like(lam[0])]
    for t in range(lam.shape[0] - 1):
        stay.append(stay[-1] * (1.0 - lam[t]))
    return jnp.stack([lam[t] * stay[t] for t in range(lam.shape[0] - 1)] + [stay[-1]])


def forward(params, tokens, sizes: dict, depth=None):
    """tokens [T] int32 -> (logits [T, V] float32, margin [L, T, 2]). A dense
    model has no router: `depth` is not read, and the margins are a large
    finite number that no `router_tie` reaches (the shape and the finiteness
    are what `check.serve_check` and its result line ask for)."""
    with jax.default_matmul_precision("highest"):
        x = passes(params, tokens, sizes)[0][-1]
        logits = x @ params["lm_head"].astype(F32)
    return logits, jnp.full((sizes["n_layers"], tokens.shape[0], 2), 1e9, F32)


def loss(params, tokens, sizes: dict, remat: bool = False):
    """Mean next-token cross-entropy of tokens [T + 1] (`remat` is accepted
    for the signature `check.train_check` calls; this loop keeps everything)."""
    logits = forward(params, tokens[:-1], sizes)[0]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, tokens[1:, None], axis=-1).mean()
