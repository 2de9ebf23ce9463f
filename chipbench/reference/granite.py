"""Granite 4.0-H (ibm-granite/granite-4.0-h-micro `config.json`, model_type
`granitemoehybrid`): a dense decoder whose layers have a Mamba-2 mixer where
a transformer has attention, except the layers `attn_layers`.

    h = 12 E[tokens]                                   embedding_multiplier
    for l = 0 .. L-1:
        h = h + 0.22 Mixer_l(RMS(h; n1_l))             residual_multiplier
        h = h + 0.22 W_out(silu(W_gate u) * (W_up u)),  u = RMS(h; n2_l)
    logits = RMS(h; n_final) E^T / 8                   tied head, logits_scaling

    attention layer: 32 heads on 8 KV heads of 64, no bias, NO positions,
        causal softmax of q . k * attention_multiplier (1/64, not 1/sqrt(64))
    Mamba-2 layer (H heads of P, one group of N states, convolution width K):
        [z | xBC | dt] = W_in u
        xBC_t = silu(sum_j w[j] * xBC_{t-K+1+j} + b)   depthwise, causal
        xBC -> x [H, P] | B [N] | C [N];  dt = softplus(dt + dt_bias);  A = -exp(A_log)
        h_t = exp(dt_t A) h_{t-1} + dt_t (x_t outer B_t)        a head, [P, N]
        y_t = h_t C_t + D x_t
        out = W_out RMS(y * silu(z); w_g)              the gate FIRST, then the norm

Float32, `default_matmul_precision("highest")`, a Python loop over the
layers, one layer's weights converted at a time, the recurrence TOKEN BY TOKEN
(`lax.scan` over t: no chunks), the convolution as K shifted products, the
attention a masked softmax, the logits in blocks of the vocabulary; no cache,
no kernel, nothing of the program's.

Parameter tree (the program's): embed [V, d], final_norm/w, layers/* the
attention layers in depth order with a leading dimension (norm1/w, norm2/w,
attn/{wq, wk, wv [d, H, Dh], wo [H, Dh, d]}, mlp/{wi_gate, wi_up [d, F], wo
[F, d]}), ssm_layers/* the others (norm1/w, norm2/w, mlp/*, mixer/{in_z [d,
H P], in_xbc [d, H P + 2 N], in_dt [d, H]: the published in_proj's three
column blocks; conv_w [K, H P + 2 N], conv_b, dt_bias [H], A_log [H], D [H],
norm [H P], out_proj [H P, d]}).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
VOCAB_BLOCKS = 8


def _rms_norm(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * w.astype(F32)


def _mlp(p, h, eps, res):
    u = _rms_norm(h, p["norm2"]["w"], eps)
    m = (jax.nn.silu(u @ p["mlp"]["wi_gate"]) * (u @ p["mlp"]["wi_up"])) @ p["mlp"]["wo"]
    return h + res * m


def _take(layers, i):
    return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, i, 0, keepdims=False).astype(F32), layers)


@functools.partial(jax.jit, static_argnames=("eps", "res", "scale"))
def _attn_layer(layers, i, h, *, eps, res, scale):
    p = _take(layers, i)
    T = h.shape[0]
    u = _rms_norm(h, p["norm1"]["w"], eps)
    q = jnp.einsum("te,ehd->thd", u, p["attn"]["wq"])     # no rope: "nope"
    k = jnp.einsum("te,ehd->thd", u, p["attn"]["wk"])
    v = jnp.einsum("te,ehd->thd", u, p["attn"]["wv"])
    G = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, G, axis=1), jnp.repeat(v, G, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * scale
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    h = h + res * jnp.einsum("thd,hde->te", o, p["attn"]["wo"])
    return _mlp(p, h, eps, res)


def recurrence(x, dt, A, B, C, D):
    """x [T, H, P], dt [T, H], A [H], B and C [T, N], D [H] -> (y [T, H, P],
    the state after the last token [H, P, N]): h_t = exp(dt_t A) h_{t-1} +
    dt_t (x_t outer B_t), y_t = h_t C_t + D x_t, one token at a time."""
    def step(h, at):
        x_t, dt_t, B_t, C_t = at
        h = (jnp.exp(dt_t * A)[:, None, None] * h
             + (dt_t[:, None] * x_t)[:, :, None] * B_t[None, None, :])
        return h, (h * C_t[None, None, :]).sum(-1) + D[:, None] * x_t

    h0 = jnp.zeros(x.shape[1:] + B.shape[-1:], F32)
    h, y = jax.lax.scan(step, h0, (x, dt, B, C))
    return y, h


def mixer(p, u, *, n_heads: int, d_state: int, eps: float):
    """The Mamba-2 mixer on normed u [T, d] -> [T, d]."""
    T = u.shape[0]
    d_inner = p["norm"].shape[0]
    K, conv_dim = p["conv_w"].shape
    # the published in_proj, [z | xBC | dt] = W_in u, stored as its column blocks
    z, xBC = u @ p["in_z"], u @ p["in_xbc"]
    dt = jax.nn.softplus(u @ p["in_dt"] + p["dt_bias"])   # no time_step_limit
    # the published weight is [conv_dim, 1, K] (a Conv1d's); the program stores
    # its transpose [K, conv_dim]: w[j] multiplies the input K - 1 - j back
    padded = jnp.concatenate([jnp.zeros((K - 1, conv_dim), F32), xBC])
    xBC = jax.nn.silu(sum(padded[j:j + T] * p["conv_w"][j][None] for j in range(K))
                      + p["conv_b"][None])
    x = xBC[:, :d_inner].reshape(T, n_heads, d_inner // n_heads)
    B, C = xBC[:, d_inner:d_inner + d_state], xBC[:, d_inner + d_state:]
    y, _ = recurrence(x, dt, -jnp.exp(p["A_log"]), B, C, p["D"])
    y = y.reshape(T, d_inner) * jax.nn.silu(z)            # norm_before_gate false
    return _rms_norm(y, p["norm"], eps) @ p["out_proj"]   # one norm group


@functools.partial(jax.jit, static_argnames=("eps", "res", "n_heads", "d_state"))
def _ssm_layer(layers, i, h, *, eps, res, n_heads, d_state):
    p = _take(layers, i)
    h = h + res * mixer(p["mixer"], _rms_norm(h, p["norm1"]["w"], eps),
                        n_heads=n_heads, d_state=d_state, eps=eps)
    return _mlp(p, h, eps, res)


def hidden(params, tokens, sizes: dict):
    """tokens [T] int32 -> the final norm's output [T, d]."""
    eps, res = float(sizes["norm_eps"]), float(sizes["residual_multiplier"])
    h = params["embed"][tokens].astype(F32) * float(sizes["embedding_multiplier"])
    n_attn = n_ssm = 0
    for l in range(sizes["n_layers"]):
        if l in sizes["attn_layers"]:
            h = _attn_layer(params["layers"], n_attn, h, eps=eps, res=res,
                            scale=float(sizes["attention_multiplier"]))
            n_attn += 1
        else:
            h = _ssm_layer(params["ssm_layers"], n_ssm, h, eps=eps, res=res,
                           n_heads=sizes["ssm_heads"], d_state=sizes["ssm_d_state"])
            n_ssm += 1
    return _rms_norm(h, params["final_norm"]["w"], eps)


def forward(params, tokens, sizes: dict, depth=None):
    """tokens [T] int32 -> (logits [T, V] float32, margin [L, T, 2]). A dense
    model has no router: `depth` is not read, and the margins are a large
    finite number that no `router_tie` reaches (reference/ouro.py)."""
    with jax.default_matmul_precision("highest"):
        x = hidden(params, tokens, sizes)
        E = params["embed"]
        step = -(-E.shape[0] // VOCAB_BLOCKS)
        logits = jnp.concatenate(  # the embedding converted a block at a time
            [x @ E[v:v + step].astype(F32).T for v in range(0, E.shape[0], step)],
            axis=-1) / float(sizes["logits_scaling"])
    return logits, jnp.full((sizes["n_layers"], tokens.shape[0], 2), 1e9, F32)


def loss(params, tokens, sizes: dict, remat: bool = False):
    """Mean next-token cross-entropy of tokens [T + 1] (`remat` is accepted
    for the signature `check.train_check` calls; this loop keeps everything)."""
    logits = forward(params, tokens[:-1], sizes)[0]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, tokens[1:, None], axis=-1).mean()
