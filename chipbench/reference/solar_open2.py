"""Upstage Solar Open 2 (upstage/Solar-Open2-250B `config.json`, model_type
`solar_open2`), d the hidden size, every product float32 at precision HIGHEST,
over the whole sequence: no cache, no kernels, no chunks, no batching.

h = E[tok]. Every layer, two RMS norms (eps `norm_eps`): h <- h + Mix(RMS(h;
w1)); h <- h + MLP(RMS(h; w2)). logits = RMS(h; w_f) W_head. No biases on the
projections, untied head, no positions anywhere.

Layer l of `gqa_layers` (0, 4, 8, ...: place 0 of every period of four),
x the normed input: q = x W_q [H, D], k = x W_k [Hkv, D], v = x W_v [Hkv, D],
g = x W_g [H, D]; s = q . k / sqrt(D), causal softmax, o = sum p v, H / Hkv
query heads a KV head, one KV head's group at a time; Mix = (o * sigmoid(g))
W_o.

Every other layer is gated delta-rule linear attention (Kimi Delta Attention,
arXiv 2510.26692), H heads, keys and values of D:

    q = silu(conv_q(x W_q)), k = silu(conv_k(x W_k)), v = silu(conv_v(x W_v))
        conv: depthwise, causal, width K, no bias: sum_j w[j] * in_{t-K+1+j}
    q = q / sqrt(|q|^2 + 1e-6) * D^-1/2,  k = k / sqrt(|k|^2 + 1e-6)   a head
    g = -exp(A_log[h]) * softplus((x W_fa) W_fb + dt_bias)    [H, D] < 0
    beta = 2 * sigmoid(x W_b)                                 [H] in (0, 2)
    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                                S [D (key), D (value)] a head
    Mix = (RMS_D(o; w_n) * sigmoid((x W_ga) W_gb + b_g)) W_o

TOKEN BY TOKEN (`lax.scan` over t), the recurrence as written: the decayed
state, less beta k (k^T of it), plus beta k v^T.

MLP of every layer: s = sigmoid(x W_r), one score for each of ALL the model's
experts; the k experts are the top-k of s + b (b a bias an expert; no groups);
a weight is s_e / (the k chosen scores' sum + 1e-20) * `routed_scaling_factor`;
y = sum_e w_e SwiGLU_e(x) + SwiGLU_shared(x), the shared expert on every
token, ungated. Nothing is dropped. Experts one by one.

THE SHARE. `sizes["experts_held"]` lists, by index among the router's experts,
the experts whose weights `params` has (row j of gate / up / down is expert
`experts_held[j]`); absent from `sizes`, every expert is held and this is the
uncut model. The routing is over all experts and a held expert's weight is
what the full routing gives it; what the absent experts would add is left
out, and that partial result goes on to the next layer (reference/trinity.py).

`forward(params, tokens, sizes, depth)` as check.tie_search calls it; `_route`
takes the other side of a tie by depth, and `_KEPT` keeps the residual stream
by the routing of the layers before (reference/kimi_vl.py says why).

Departures from the published description, stated in the configuration file:
the config has no key for the router's score function, the select bias, the
shared expert's width, the gates' inner width or the L2 norms' epsilon, and
the file's `assumed` says what was taken for each; b comes with the weights
(a published initial value of 0 is assumed); the three projections and the
three convolutions of a KDA layer are stored as the column blocks q | k | v
of one matrix each, which changes no number.

Parameter tree (the program's): embed [V, d], lm_head [d, V], final_norm/w;
layers/* the attention layers in depth order with a leading dimension
(norm1/w, norm2/w, attn/{wq, wg [d,H,D], wk, wv [d,Hkv,D], wo [H,D,d]}, mlp/*),
ssm_layers/* the others (norm1/w, norm2/w, mlp/*, mixer/{in_qkv [d, 3 H D],
conv_w [K, 3 H D], f_a [d, R], f_b [R, H D], dt_bias [H D], A_log [H], w_beta
[d, H], g_a [d, R], g_b [R, H D], g_bias [H D], norm [D], out_proj [H D, d]});
mlp/{router [d,E], router_bias [E], gate, up [held,d,F], down [held,F,d],
shared/{wi_gate, wi_up, wo}}.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.kimi_vl import _at, _experts, _rms_norm, _route, _swiglu

F32 = jnp.float32


@functools.partial(jax.jit, static_argnames=("eps",))
def _attention(layers, i, x, *, eps):
    """x + the gated attention sublayer (no positions, causal)."""
    a = {k: _at(v, i).astype(F32) for k, v in layers["attn"].items()}
    T = x.shape[0]
    h = _rms_norm(x, _at(layers["norm1"]["w"], i), eps)
    q = jnp.einsum("te,ehd->thd", h, a["wq"])
    k = jnp.einsum("te,ehd->thd", h, a["wk"])
    v = jnp.einsum("te,ehd->thd", h, a["wv"])
    seen = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    Hkv, D = k.shape[1], k.shape[2]

    def group(one):
        qg, kg, vg = one                                           # [T, G, D], [T, D] x 2
        s = jnp.einsum("qgd,kd->gqk", qg, kg) / jnp.sqrt(F32(D))
        s = jnp.where(seen[None], s, -jnp.inf)
        return jnp.einsum("gqk,kd->qgd", jax.nn.softmax(s, axis=-1), vg)

    o = jax.lax.map(group, (jnp.moveaxis(q.reshape(T, Hkv, -1, D), 1, 0),
                            jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)))
    o = jnp.moveaxis(o, 0, 1).reshape(q.shape)
    o = o * jax.nn.sigmoid(jnp.einsum("te,ehd->thd", h, a["wg"]))
    return x + jnp.einsum("thd,hde->te", o, a["wo"])


def delta_rule(q, k, v, g, beta, state=None):
    """q, k, g [T, H, D], v [T, H, Dv], beta [T, H] -> (o [T, H, Dv], the state
    after the last token [H, D, Dv]): S_t = (I - beta_t k_t k_t^T)
    Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T, o_t = S_t^T q_t, one token at a
    time."""
    def step(S, at):
        q_t, k_t, v_t, g_t, b_t = at
        S = jnp.exp(g_t)[:, :, None] * S                           # Diag(exp(g)) S
        kS = jnp.einsum("hk,hkv->hv", k_t, S)                      # k^T of it
        S = (S - b_t[:, None, None] * k_t[:, :, None] * kS[:, None, :]
             + b_t[:, None, None] * k_t[:, :, None] * v_t[:, None, :])
        return S, jnp.einsum("hk,hkv->hv", q_t, S)

    S0 = jnp.zeros(k.shape[1:] + v.shape[-1:], F32) if state is None else state
    S, o = jax.lax.scan(step, S0, (q, k, v, g, beta))
    return o, S


@functools.partial(jax.jit, static_argnames=("heads", "eps"))
def _kda(layers, i, x, *, heads, eps):
    """x + the KDA sublayer."""
    p = {k: _at(v, i).astype(F32) for k, v in layers["mixer"].items()}
    T = x.shape[0]
    h = _rms_norm(x, _at(layers["norm1"]["w"], i), eps)
    K, width = p["conv_w"].shape                                   # q | k | v
    D = width // 3 // heads
    # the published weights are three Conv1d's [H D, 1, K]; the program stores
    # the transposes side by side: w[j] multiplies the input K - 1 - j back
    padded = jnp.concatenate([jnp.zeros((K - 1, width), F32), h @ p["in_qkv"]])
    qkv = jax.nn.silu(sum(padded[j:j + T] * p["conv_w"][j][None] for j in range(K)))
    q, k, v = (qkv[:, j * heads * D:(j + 1) * heads * D].reshape(T, heads, D)
               for j in range(3))
    q = q / jnp.sqrt((q * q).sum(-1, keepdims=True) + 1e-6) / jnp.sqrt(F32(D))
    k = k / jnp.sqrt((k * k).sum(-1, keepdims=True) + 1e-6)
    g = -jnp.exp(p["A_log"])[None, :, None] * jax.nn.softplus(
        (h @ p["f_a"]) @ p["f_b"] + p["dt_bias"]).reshape(T, heads, D)
    beta = 2.0 * jax.nn.sigmoid(h @ p["w_beta"])
    o, _ = delta_rule(q, k, v, g, beta)
    gate = jax.nn.sigmoid((h @ p["g_a"]) @ p["g_b"] + p["g_bias"]).reshape(T, heads, D)
    y = _rms_norm(o, p["norm"], eps) * gate
    return x + y.reshape(T, heads * D) @ p["out_proj"]


_KEPT = {"params": None, "tokens": None, "after": {}}


def forward(params, tokens, sizes: dict, depth=None):
    """tokens [T] int32 -> (logits [T, V] float32, margin [L, T, 2]: see
    kimi_vl `_route`). `depth` [L, T] int routes the marked tokens of the
    marked layers the nearest (1) or second nearest (2) other way (None:
    top-k)."""
    with jax.default_matmul_precision("highest"):
        L, T = sizes["n_layers"], tokens.shape[0]
        depth = jnp.zeros((L, T), jnp.int32) if depth is None else jnp.asarray(depth, jnp.int32)
        eps = float(sizes["norm_eps"])
        held = sizes.get("experts_held")
        held = None if held is None else jnp.asarray(held, jnp.int32)
        routing, context = np.asarray(depth, np.int8), np.asarray(tokens).tobytes()
        if _KEPT["params"] is not params or _KEPT["tokens"] != context:
            _KEPT.update(params=params, tokens=context, after={})
        after = _KEPT["after"]    # the routing of the first l layers -> (x, margins) after them
        first = max((l for l in range(L + 1) if (l, routing[:l].tobytes()) in after), default=0)
        x, margins = after.get((first, routing[:first].tobytes()), (None, ()))
        if x is None:
            x = params["embed"][tokens].astype(F32)
        margins = list(margins)
        gqa = [l for l in range(L) if l in sizes["gqa_layers"]]
        for layer in range(first, L):
            if layer in gqa:
                layers, i = params["layers"], gqa.index(layer)
                x = _attention(layers, i, x, eps=eps)
            else:
                layers, i = params["ssm_layers"], layer - sum(l < layer for l in gqa)
                x = _kda(layers, i, x, heads=sizes["kda_heads"], eps=eps)
            h, gates, margin = _route(layers, i, x, depth[layer], top_k=sizes["top_k"],
                                      scale=float(sizes["routed_scaling_factor"]), eps=eps)
            margins.append(margin)
            if held is not None:  # the share: the held experts' columns
                gates = gates[:, held]
            most = int(np.asarray((gates > 0).sum(0)).max())
            x = x + _swiglu(layers["mlp"]["shared"], i, h) + _experts(
                layers["mlp"], i, h, gates, cap=max(-(-most // 128) * 128, 128))
            after[layer + 1, routing[:layer + 1].tobytes()] = (x, tuple(margins))
            while len(after) > 16:  # [T, d] float32 each, beside weights that fill the chip
                del after[next(iter(after))]
        x = _rms_norm(x, params["final_norm"]["w"], eps)
        return x @ params["lm_head"].astype(F32), jnp.stack(margins)
