"""GPT-2 (Radford et al. 2019; openai-community/gpt2-* `config.json`).

Learned positions, pre-LayerNorm blocks (eps `layer_norm_epsilon`), biased
projections, causal softmax attention scaled by 1/sqrt(d_head), the tanh
GELU (`gelu_new`), output head tied to the token embedding, mean next-token
cross-entropy. Departure, memory only: with `remat=True` each layer is
wrapped in `jax.checkpoint` so that the gradient of a 36–48 layer stack in
float32 fits beside the weights; the mathematics is unchanged.

Parameter tree (the program's): embed [V, d], pos_embed [S, d], layers/* with
a leading layer dimension — norm{1,2}/{w,b}, attn/{wq,wk,wv [d,H,Dh], wo
[H,Dh,d], bq,bk,bv [H,Dh], bo [d]}, mlp/{wi [d,F], bi, wo [F,d], bo} —
final_norm/{w,b}.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _layer_norm(x, p, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["w"].astype(F32) + p["b"].astype(F32)


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x ** 3)))


def _layer(x, p, eps):
    """x [T, d] float32 -> [T, d]."""
    T = x.shape[0]
    a = p["attn"]
    h = _layer_norm(x, p["norm1"], eps)
    q = jnp.einsum("te,ehd->thd", h, a["wq"].astype(F32)) + a["bq"].astype(F32)
    k = jnp.einsum("te,ehd->thd", h, a["wk"].astype(F32)) + a["bk"].astype(F32)
    v = jnp.einsum("te,ehd->thd", h, a["wv"].astype(F32)) + a["bv"].astype(F32)
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(q.shape[-1]))
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    x = x + jnp.einsum("thd,hde->te", o, a["wo"].astype(F32)) + a["bo"].astype(F32)
    m = p["mlp"]
    h = _layer_norm(x, p["norm2"], eps)
    h = _gelu_new(h @ m["wi"].astype(F32) + m["bi"].astype(F32))
    return x + h @ m["wo"].astype(F32) + m["bo"].astype(F32)


def forward(params, tokens, sizes: dict, *, remat: bool = False):
    """tokens [T] int32 -> logits [T, V] float32."""
    eps = sizes["norm_eps"]
    with jax.default_matmul_precision("highest"):
        T = tokens.shape[0]
        x = params["embed"].astype(F32)[tokens] + params["pos_embed"].astype(F32)[:T]
        layer = jax.checkpoint(_layer, static_argnums=(2,)) if remat else _layer
        x, _ = jax.lax.scan(lambda h, p: (layer(h, p, eps), None), x, params["layers"])
        x = _layer_norm(x, params["final_norm"], eps)
        return x @ params["embed"].astype(F32).T


def loss(params, tokens, sizes: dict, *, remat: bool = False):
    """Mean next-token cross-entropy of tokens [T + 1]."""
    logits = forward(params, tokens[:-1], sizes, remat=remat)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, tokens[1:, None], axis=-1).mean()
