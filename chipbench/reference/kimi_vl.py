"""Kimi-VL-A3B-Instruct's language model (moonshotai/Kimi-VL-A3B-Instruct
`config.json`, `text_config`; the layer is DeepSeek-V3's, Liu et al. 2024).

Pre-norm residual blocks, x <- x + Attn(RMSNorm(x)); x <- x + FFN(RMSNorm(x));
a final RMSNorm; logits = x W_head. No biases, untied head.

Attention (multi-head latent attention), h = RMSNorm(x):
q = h W_q [H, nope + rope], the rope part rotated; [c' | k_r'] = h W_dkv
[rank + rope]; c = RMSNorm(c') with its own weight and epsilon; k_rope =
RoPE(k_r'), ONE head shared by all query heads; [k_nope | v] = c W_ukv
[H, nope + v]. s_ij = (q_nope_i . k_nope_j + q_rope_i . k_rope_j) /
sqrt(nope + rope), causal softmax, o = sum p v, out = o W_o. In expanded form
over the whole sequence: no cache, no absorbed form.

FFN: the first `n_dense_layers` layers a SwiGLU MLP; the others, h =
RMSNorm(x): scores = sigmoid(h W_r); the k experts are the top-k of scores +
b (b a per-expert bias; one group: no group restriction); a weight is the
expert's score WITHOUT b, over (the k scores' sum + 1e-20), times
`routed_scaling_factor`; y = sum_i w_i SwiGLU_i(h) + SwiGLU_shared(h), the
shared MLP for every token, ungated. Nothing is dropped. Experts one by one,
each over the tokens routed to it (padded to a multiple of 128, the padding
weighted 0), so that the float32 copy of what is multiplied fits beside
bfloat16 weights that fill most of a chip.

The serve check's search asks for hundreds of forwards over ONE context that
differ in the routing of one token in one layer. The residual stream after l
layers depends on the routing of those l layers alone, so `forward` keeps it
by that routing (`_KEPT`, the newest few dozen) and starts from the deepest
layer whose routing so far it has seen: the same arithmetic, not done twice.

Departures from the published code, stated in the configuration file:
rotary positions rotate halves where the published code rotates interleaved
pairs (a fixed permutation of the rope columns of W_q and W_dkv, void under
seeded weights); the epsilons are `sizes["norm_eps"]` and
`sizes["kv_norm_eps"]`; b comes with the weights (published initial value 0).

Parameter tree (the program's): embed [V, d], lm_head [d, V], final_norm/w;
dense_layers/* and layers/* with a leading layer dimension — norm{1,2}/w,
attn/{wq [d,H,nope+rope], w_dkv [d,rank+rope], kv_norm [rank], w_ukv
[rank,H,nope+v], wo [H,v,d]}; dense_layers/mlp/{wi_gate, wi_up [d,F], wo
[F,d]}; layers/mlp/{router [d,E], router_bias [E], gate, up [E,d,F], down
[E,F,d], shared/{wi_gate, wi_up, wo}}.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

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


@functools.partial(jax.jit, static_argnames=("nope", "rank", "theta", "eps", "kv_eps"))
def _attention(layers, i, x, *, nope, rank, theta, eps, kv_eps):
    a = {k: _at(v, i).astype(F32) for k, v in layers["attn"].items()}
    T = x.shape[0]
    h = _rms_norm(x, _at(layers["norm1"]["w"], i), eps)
    q = jnp.einsum("te,ehd->thd", h, a["wq"])
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], theta)
    ckr = h @ a["w_dkv"]
    c = _rms_norm(ckr[:, :rank], a["kv_norm"], kv_eps)
    k_rope = _rope(ckr[:, None, rank:], theta)[:, 0]             # [T, rope]
    kv = jnp.einsum("tr,rhd->thd", c, a["w_ukv"])
    k_nope, v = kv[..., :nope], kv[..., nope:]
    s = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
         + jnp.einsum("qhd,kd->hqk", q_rope, k_rope)) / jnp.sqrt(F32(q.shape[-1]))
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    return x + jnp.einsum("thd,hde->te", o, a["wo"])


@jax.jit
def _swiglu(mlp, i, h):
    w = {k: _at(mlp[k], i).astype(F32) for k in ("wi_gate", "wi_up", "wo")}
    return (jax.nn.silu(h @ w["wi_gate"]) * (h @ w["wi_up"])) @ w["wo"]


@functools.partial(jax.jit, static_argnames=("top_k", "scale", "eps"))
def _route(layers, i, x, depth, *, top_k, scale, eps):
    """(normed input, gates [T, E]: the k weights, 0 elsewhere, margin
    [T, 2]). `depth` [T] int: 0 is plain top-k of score + b. 1 and 2 are the
    two routings NEAREST to it, by the gap in score + b that rounding has to
    bridge, and `margin` holds those gaps: at 1 the first expert left out is
    taken instead of the last one taken (the k-th and the k+1-th change
    places); at 2 the nearer of: the second one left out instead of the last
    one taken (k-th out, k+2-th in), or the first one left out instead of
    the last but one taken (k-1-th out, k+1-th in). With 6 of 64 sigmoid
    scores the two lie equally near, and a router fed bfloat16 activations
    takes either (PERF.md section 6, PR 28)."""
    h = _rms_norm(x, _at(layers["norm2"]["w"], i), eps)
    scores = jax.nn.sigmoid(h @ _at(layers["mlp"]["router"], i).astype(F32))
    biased = scores + _at(layers["mlp"]["router_bias"], i).astype(F32)
    best, idx = jax.lax.top_k(biased, top_k + 2)
    k = top_k
    last_for_second = best[:, k - 1] - best[:, k + 1] <= best[:, k - 2] - best[:, k]
    margin = jnp.stack([best[:, k - 1] - best[:, k],
                        jnp.minimum(best[:, k - 1] - best[:, k + 1],
                                    best[:, k - 2] - best[:, k])], axis=1)
    keep = jnp.arange(k + 2)[None, :] < k                        # plain top-k
    swap = keep.at[:, k - 1].set(False).at[:, k].set(True)       # k-th out, k+1-th in
    far = jnp.where(last_for_second[:, None],
                    keep.at[:, k - 1].set(False).at[:, k + 1].set(True),
                    keep.at[:, k - 2].set(False).at[:, k].set(True))
    taken = jnp.where(depth[:, None] == 0, keep, jnp.where(depth[:, None] == 1, swap, far))
    w = jnp.where(taken, jnp.take_along_axis(scores, idx, axis=1), 0.0)   # without b
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * scale
    gates = jnp.zeros_like(scores).at[jnp.arange(x.shape[0])[:, None], idx].set(w)
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
    legitimately be routed otherwise by a router fed rounded activations; a
    dense layer's rows are +inf). `depth` [L, T] int routes the marked
    tokens of the marked layers the nearest (1) or second nearest (2) other
    way (None: top-k)."""
    with jax.default_matmul_precision("highest"):
        L, n_dense, T = sizes["n_layers"], sizes["n_dense_layers"], tokens.shape[0]
        depth = jnp.zeros((L, T), jnp.int32) if depth is None else jnp.asarray(depth, jnp.int32)
        eps = float(sizes["norm_eps"])
        routing, context = np.asarray(depth, np.int8), np.asarray(tokens).tobytes()
        if _KEPT["params"] is not params or _KEPT["tokens"] != context:
            _KEPT.update(params=params, tokens=context, after={})
        after = _KEPT["after"]    # the routing of the first l layers -> (x, margins) after them
        first = max((l for l in range(L + 1) if (l, routing[:l].tobytes()) in after), default=0)
        x, margins = after.get((first, routing[:first].tobytes()),
                               (params["embed"][tokens].astype(F32), ()))
        margins = list(margins)
        for layer in range(first, L):
            dense = layer < n_dense
            layers = params["dense_layers" if dense else "layers"]
            i = layer if dense else layer - n_dense
            x = _attention(layers, i, x, nope=sizes["qk_nope_head_dim"],
                           rank=sizes["kv_lora_rank"], theta=float(sizes["rope_theta"]),
                           eps=eps, kv_eps=float(sizes["kv_norm_eps"]))
            if dense:
                h = _rms_norm(x, _at(layers["norm2"]["w"], i), eps)
                x = x + _swiglu(layers["mlp"], i, h)
                margins.append(jnp.full((T, 2), jnp.inf, F32))
                after[layer + 1, routing[:layer + 1].tobytes()] = (x, tuple(margins))
                continue
            h, gates, margin = _route(layers, i, x, depth[layer], top_k=sizes["top_k"],
                                      scale=float(sizes["routed_scaling_factor"]), eps=eps)
            margins.append(margin)
            x = x + _swiglu(layers["mlp"]["shared"], i, h)
            most = int(np.asarray((gates > 0).sum(0)).max())
            x = x + _experts(layers["mlp"], i, h, gates, cap=-(-most // 128) * 128)
            after[layer + 1, routing[:layer + 1].tobytes()] = (x, tuple(margins))
            while len(after) > 48:
                del after[next(iter(after))]
        x = _rms_norm(x, params["final_norm"]["w"], eps)
        return x @ params["lm_head"].astype(F32), jnp.stack(margins)
