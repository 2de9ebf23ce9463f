"""Arcee Trinity Large (arcee-ai/Trinity-Large-Preview `config.json`,
model_type `afmoe`), d the hidden size, every product float32 at precision
HIGHEST, over the whole sequence: no cache, no kernels, no batching.

h = E[tok] * sqrt(d). Every layer, four RMS norms (eps `norm_eps`):
h <- h + RMS(Attn(RMS(h; w1)); w_pa); h <- h + RMS(MLP(RMS(h; w2)); w_pm).
logits = RMS(h; w_f) W_head. No biases, untied head.

Attention, x the normed input: q = x W_q [H, D], k = x W_k [Hkv, D], v = x W_v
[Hkv, D], g = x W_g [H, D]; q = RMS(q; w_qn), k = RMS(k; w_kn) over the D of
each head (one weight of D, shared by the heads). On a window layer q and k
are rotated (theta `rope_theta`, by halves) and query i sees key j iff 0 <= i
- j < `window`; on a full layer there is NO rotation and the mask is causal.
s = q . k / sqrt(D), softmax, o = sum p v, H / Hkv query heads a KV head;
out = (o * sigmoid(g)) W_o. One KV head's group of query heads at a time, so
that the scores of a 4,600-token sample fit beside the weights.

Kinds: the first `n_dense_layers` layers are window layers with a SwiGLU MLP
of the dense width; of the layers after them the last of each `window_period`
is a full layer (the cut's order: the configuration file says which published
layers they stand for).

MLP of the other layers: s = sigmoid(x W_r), one score for each of ALL the
model's experts; the k experts are the top-k of s + b (b a bias an expert;
one group: no group restriction); a weight is s_e / (the k chosen scores' sum
+ 1e-20) * `routed_scaling_factor`; y = sum_e w_e SwiGLU_e(x) + SwiGLU_shared(x),
the shared expert on every token, ungated. Nothing is dropped.

THE SHARE. `sizes["experts_held"]` lists, by index among the router's experts,
the experts whose weights `params` has (row j of gate / up / down is expert
`experts_held[j]`); absent from `sizes`, every expert is held and this is the
uncut model. The routing is over all experts and a held expert's weight is
what the full routing gives it; what the absent experts would add is left
out, and that partial result goes on to the next layer: what one chip of a
deployment that divides each layer's experts computes before the exchange.

`forward(params, tokens, sizes, depth)` as check.tie_search calls it; `_route`
takes the other side of a tie by depth, and `_KEPT` keeps the residual stream
by the routing of the layers before (reference/kimi_vl.py says why).

Departures from the published code, stated in the configuration file: b comes
with the weights (published initial value 0); every norm gain is what `params`
holds (the "depth-scaled" initial value is void under seeded weights).

Parameter tree (the program's): embed [V, d], lm_head [d, V], final_norm/w;
dense_layers/* and layers/* with a leading layer dimension — norm{1,2}/w,
post_{attn,mlp}_norm/w, attn/{wq, wg [d,H,D], wk, wv [d,Hkv,D], wo [H,D,d],
q_norm, k_norm [D]}; dense_layers/mlp/{wi_gate, wi_up [d,F], wo [F,d]};
layers/mlp/{router [d,E], router_bias [E], gate, up [held,d,F], down
[held,F,d], shared/{wi_gate, wi_up, wo}}.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.kimi_vl import _at, _rms_norm, _rope, _route, _swiglu

F32 = jnp.float32


@functools.partial(jax.jit, static_argnames=("theta", "eps", "window"))
def _attention(layers, i, x, *, theta, eps, window):
    """The attention sublayer's output BEFORE its post-norm. `window` None: a
    full layer (no rotation); else a window layer (rope, the window's mask)."""
    a = {k: _at(v, i).astype(F32) for k, v in layers["attn"].items()}
    T = x.shape[0]
    h = _rms_norm(x, _at(layers["norm1"]["w"], i), eps)
    q = _rms_norm(jnp.einsum("te,ehd->thd", h, a["wq"]), a["q_norm"], eps)
    k = _rms_norm(jnp.einsum("te,ehd->thd", h, a["wk"]), a["k_norm"], eps)
    v = jnp.einsum("te,ehd->thd", h, a["wv"])
    if window is not None:
        q, k = _rope(q, theta), _rope(k, theta)
    gap = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]          # i - j
    seen = gap >= 0 if window is None else (gap >= 0) & (gap < window)
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
    return jnp.einsum("thd,hde->te", o, a["wo"])


@functools.partial(jax.jit, static_argnames=("name", "eps"))
def _post(layers, name, i, x, delta, *, eps):
    """x + RMS(delta; the layer's norm `name`)."""
    return x + _rms_norm(delta, _at(layers[name]["w"], i), eps)


@functools.partial(jax.jit, static_argnames=("cap",))
def _experts(mlp, i, h, gates, *, cap):
    """sum_j gates[:, j] SwiGLU_j(h) over the experts `mlp` HOLDS (gates [T,
    held]: the held experts' columns), expert after expert, each over the
    tokens routed to it: at most `cap`, the rest of its `cap` rows are token 0
    weighted 0."""
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


def is_full_layer(sizes: dict, layer: int) -> bool:
    j, period = layer - sizes["n_dense_layers"], sizes["window_period"]
    return j >= 0 and j % period == period - 1


_KEPT = {"params": None, "tokens": None, "after": {}}


def forward(params, tokens, sizes: dict, depth=None):
    """tokens [T] int32 -> (logits [T, V] float32, margin [L, T, 2]: see
    kimi_vl `_route`; a dense layer's rows are +inf). `depth` [L, T] int
    routes the marked tokens of the marked layers the nearest (1) or second
    nearest (2) other way (None: top-k)."""
    with jax.default_matmul_precision("highest"):
        L, n_dense, T = sizes["n_layers"], sizes["n_dense_layers"], tokens.shape[0]
        depth = jnp.zeros((L, T), jnp.int32) if depth is None else jnp.asarray(depth, jnp.int32)
        eps, theta = float(sizes["norm_eps"]), float(sizes["rope_theta"])
        held = sizes.get("experts_held")
        held = None if held is None else jnp.asarray(held, jnp.int32)
        routing, context = np.asarray(depth, np.int8), np.asarray(tokens).tobytes()
        if _KEPT["params"] is not params or _KEPT["tokens"] != context:
            _KEPT.update(params=params, tokens=context, after={})
        after = _KEPT["after"]    # the routing of the first l layers -> (x, margins) after them
        first = max((l for l in range(L + 1) if (l, routing[:l].tobytes()) in after), default=0)
        x, margins = after.get((first, routing[:first].tobytes()), (None, ()))
        if x is None:
            x = params["embed"][tokens].astype(F32) * F32(sizes["embedding_multiplier"])
        margins = list(margins)
        for layer in range(first, L):
            dense = layer < n_dense
            layers = params["dense_layers" if dense else "layers"]
            i = layer if dense else layer - n_dense
            window = None if is_full_layer(sizes, layer) else int(sizes["window"])
            x = _post(layers, "post_attn_norm", i, x,
                      _attention(layers, i, x, theta=theta, eps=eps, window=window), eps=eps)
            if dense:
                h = _rms_norm(x, _at(layers["norm2"]["w"], i), eps)
                x = _post(layers, "post_mlp_norm", i, x, _swiglu(layers["mlp"], i, h), eps=eps)
                margins.append(jnp.full((T, 2), jnp.inf, F32))
            else:
                h, gates, margin = _route(layers, i, x, depth[layer], top_k=sizes["top_k"],
                                          scale=float(sizes["routed_scaling_factor"]), eps=eps)
                margins.append(margin)
                if held is not None:  # the share: the held experts' columns
                    gates = gates[:, held]
                most = int(np.asarray((gates > 0).sum(0)).max())
                y = _swiglu(layers["mlp"]["shared"], i, h) + _experts(
                    layers["mlp"], i, h, gates, cap=max(-(-most // 128) * 128, 128))
                x = _post(layers, "post_mlp_norm", i, x, y, eps=eps)
            after[layer + 1, routing[:layer + 1].tobytes()] = (x, tuple(margins))
            while len(after) > 16:  # [T, d] float32 each, beside weights that fill the chip
                del after[next(iter(after))]
        x = _rms_norm(x, params["final_norm"]["w"], eps)
        return x @ params["lm_head"].astype(F32), jnp.stack(margins)
