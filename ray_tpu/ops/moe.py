"""Mixture-of-experts routing and dispatch, token-choice top-k.

Two routing functions give each token its k experts and their weights:
`softmax_topk` (Mixtral: softmax over all experts, top-k, renormalised) and
`sigmoid_topk` (DeepSeek-V3 `noaux_tc`: sigmoid scores, selection on score +
a per-expert bias, weights from the scores alone, renormalised and scaled).

Two dispatches carry them out:

- `moe_sorted`: dropless. The N*k routed slots are sorted by expert and go
  through grouped matmuls (`ops.grouped_matmul`: a Pallas kernel on the TPU,
  `jax.lax.ragged_dot` elsewhere), so expert FLOPs grow with k*N whatever the
  expert count.
- `onehot_dispatch` + `moe_apply`: capacity (GShard-style), dense einsums
  over one-hot `[N, E, C]` dispatch tensors — static shapes, tokens over
  capacity dropped, the `expert` dimension shards cleanly over the `ep` mesh
  axis, expert FLOPs grow with E*C. (The reference has no in-repo EP —
  SURVEY.md §2.6 — it passes knobs to vLLM; this is the TPU-native
  implementation.)

A layer that may drop nothing picks between them by the tokens of the call,
`sorted_pays(N)`: with C = N the one-hot form drops nothing either.

A layer may hold a SHARE of the experts (`first`: experts [first, first + E)
of the router's, E the experts its weights have): the routing is over all of
them, a slot whose expert lies outside the share is not computed and adds
nothing, and the weights of the slots that are held are what the full routing
gave them. What the other shares would add is theirs to add; nothing here
stands in for them or for the exchange with them.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.grouped_matmul import grouped_matmul


class RoutingInfo(NamedTuple):
    dispatch: jax.Array       # [N, E, C] one-hot dispatch mask
    combine: jax.Array        # [N, E, C] combine weights (softmax-scaled)
    aux_loss: jax.Array       # load-balancing loss (scalar)


# Tokens in a call from which a dropless layer sorts. Below it the one-hot
# form with C = N is bound by reading the experts' weights once, which XLA's
# batched matmul does at 94 % of the HBM's rate; above it the one-hot form
# multiplies E*N slots where k*N are routed (a 1024-token prefill chunk:
# `mixtral-8x7b.doc-saturated` `served_tok_s` 7,455 one-hot, 8,252 sorted).
# A v5e turns compute-bound at about 240 rows a weight matrix; the buckets
# on either side are 256 and 512. The threshold was set in PR 28 against the
# TPU compiler's own grouped product, which read the weights at 25-40 % of
# that rate (a decode step: `mixtral-8x7b.chat-steady` `tpot_p50_ms` 26.3
# one-hot, 31.9 sorted). The kernel of PR 29 (`ops.grouped_matmul`) reads
# them at 82 % of it in a 1024-token chunk of `kimi-vl-a3b` (0.55 ms a call
# against 0.45) and runs Mixtral's chunk, which is compute-bound, at 50 % of
# the MXU (2.45 ms against 1.22); the threshold has not been found again
# against it. PERF.md section 6, PRs 28 and 29.
SORTED_MIN_TOKENS = 512


def sorted_pays(n_tokens: int, slots_a_held_expert: float | None = None) -> bool:
    """Whether a dropless layer of `n_tokens` sorts. A layer that holds a share
    of the experts also sorts where its held experts get under one routed slot
    each in the mean (`slots_a_held_expert` = N * k / the router's experts):
    then some surely have no row, the sorted form reads only those that have
    one, and the one-hot form reads them all."""
    if slots_a_held_expert is not None and slots_a_held_expert < 1.0:
        return True
    return n_tokens >= SORTED_MIN_TOKENS


def held_slots(expert_idx, first: int, held: int):
    """expert_idx [N, k] over the router's experts -> (index among the `held`
    experts [first, first + held) where the slot's expert is one of them, else
    `held`; [N, k] bool: whether it is)."""
    local = expert_idx - first
    mine = (local >= 0) & (local < held)
    return jnp.where(mine, local, held), mine


def share_counts(expert_idx, first: int, held: int):
    """int32 [2]: (routed slots whose expert is held here, held experts with at
    least one such slot) of one call: what `stats()["experts"]` sums."""
    local, mine = held_slots(expert_idx, first, held)
    rows = jnp.any(local.reshape(-1)[:, None] == jnp.arange(held)[None, :], axis=0)
    return jnp.stack([mine.sum(dtype=jnp.int32), rows.sum(dtype=jnp.int32)])


def topk_routing(router_logits, *, num_experts: int, k: int,
                 capacity_factor: float = 1.25) -> RoutingInfo:
    """router_logits: [N, E] (N = flattened tokens). Top-k token-choice routing
    with per-expert capacity C = ceil(k * N / E * capacity_factor); tokens over
    capacity are dropped (their combine weights are zero)."""
    N, E = router_logits.shape
    assert E == num_experts
    capacity = int(max(k * N / E * capacity_factor, 1.0) + 0.9999)
    expert_idx, gate_vals, probs = _softmax_topk(router_logits, k)
    routing = onehot_dispatch(expert_idx, gate_vals, E, capacity)
    # Switch-style load-balance aux loss
    frac_tokens = jnp.mean(jax.nn.one_hot(expert_idx[:, 0], E, dtype=jnp.float32), axis=0)
    return routing._replace(aux_loss=E * jnp.sum(frac_tokens * jnp.mean(probs, axis=0)))


def onehot_dispatch(expert_idx, gate_vals, num_experts: int, capacity: int) -> RoutingInfo:
    """expert_idx, gate_vals [N, k] -> the `[N, E, C]` dispatch and combine
    tensors of `moe_apply`; a token over an expert's `capacity` is dropped
    (`capacity` N drops nothing). aux_loss 0: the caller's to set."""
    (N, k), E = expert_idx.shape, num_experts

    # position of each (token, choice) in its expert's queue
    onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.int32)             # [N, k, E]
    flat = onehot.reshape(N * k, E)
    # order: token-major, choice-major — earlier tokens win capacity
    pos_in_expert = jnp.cumsum(flat, axis=0) - flat                     # [N*k, E]
    pos = (pos_in_expert * flat).sum(-1).reshape(N, k)                  # [N, k]
    within_cap = pos < capacity

    slot_onehot = jax.nn.one_hot(pos, capacity, dtype=jnp.float32)      # [N, k, C]
    keep = within_cap.astype(jnp.float32)                               # [N, k]
    # accumulate per choice: peak memory stays at the [N, E, C] output size
    # instead of materializing a [N, k, E, C] intermediate
    dispatch = jnp.zeros((N, E, capacity), jnp.float32)
    combine = jnp.zeros((N, E, capacity), jnp.float32)
    for c in range(k):
        d = (onehot[:, c].astype(jnp.float32)[:, :, None]
             * slot_onehot[:, c][:, None, :]
             * keep[:, c][:, None, None])                               # [N, E, C]
        dispatch = dispatch + d
        combine = combine + d * gate_vals[:, c][:, None, None]

    return RoutingInfo(dispatch=dispatch, combine=combine,
                       aux_loss=jnp.zeros((), jnp.float32))


def _softmax_topk(router_logits, k: int):
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)  # [N, E]
    gate_vals, expert_idx = jax.lax.top_k(probs, k)                     # [N, k]
    # renormalize the selected gates (Mixtral convention)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)
    return expert_idx, gate_vals, probs


def softmax_topk(router_logits, *, k: int):
    """router_logits [N, E] -> (experts [N, k] int32, weights [N, k] float32,
    Switch-style load-balance loss): softmax over all experts, top-k, the k
    gates renormalised to sum to one."""
    E = router_logits.shape[-1]
    expert_idx, gate_vals, probs = _softmax_topk(router_logits, k)
    frac_tokens = jnp.mean(jax.nn.one_hot(expert_idx[:, 0], E, dtype=jnp.float32), axis=0)
    aux = E * jnp.sum(frac_tokens * jnp.mean(probs, axis=0))
    return expert_idx, gate_vals, aux


def sigmoid_topk(router_logits, select_bias, *, k: int, scale: float = 1.0):
    """router_logits [N, E] float32, select_bias [E] -> (experts [N, k],
    weights [N, k] float32, 0.0). The k experts are the top-k of
    sigmoid(logits) + bias; a weight is the expert's score WITHOUT the bias,
    over (the k scores' sum + 1e-20), times `scale`. No auxiliary loss: the
    bias is what balances the load."""
    scores = jax.nn.sigmoid(router_logits.astype(jnp.float32))
    _, expert_idx = jax.lax.top_k(scores + select_bias.astype(jnp.float32), k)
    w = jnp.take_along_axis(scores, expert_idx, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * scale
    return expert_idx, w, jnp.zeros((), jnp.float32)


def moe_sorted(x, expert_idx, weights, gate, up, down, *, layer=None, first=None, of=None):
    """Dropless SwiGLU experts. x [N, D]; expert_idx, weights [N, k]; gate,
    up [E, D, F], down [E, F, D]. Every one of the N*k routed slots is
    computed: slots sorted by expert, three grouped matmuls over the group
    sizes, unsorted, weighted and summed per token in float32.

    With `layer` (an index, traced in a layer scan) the weights are those of
    ALL layers, [L, E, ...], multiplied as L*E groups of which only this
    layer's have rows: the grouped product is a custom call, and a layer's
    slice of the stack handed to one is copied out whole first, every layer
    of every step (the compiler's account, PERF.md section 4); the kernel's
    steps are laid over the groups with rows, so the others cost nothing.

    With `first` the weights are those of a SHARE of the experts, [first,
    first + E) of the ones `expert_idx` counts: the slots of absent experts are
    sorted behind the held groups, take no step of the grouped products
    (`rows_past="skip"`) and are masked to zero here; `of`, the experts the
    slots were routed over, tells the products how few rows a group has."""
    N, k = expert_idx.shape
    E = gate.shape[-3]
    flat = expert_idx.reshape(N * k)
    share = (E,)    # the grouped products' other arguments
    if first is not None:
        flat, share = held_slots(flat, first, E)[0], (max(of or E, E), "skip")
    order = jnp.argsort(flat, stable=True)                     # slot ids by expert
    sizes = jnp.sum(flat[:, None] == jnp.arange(E, dtype=flat.dtype)[None, :],
                    axis=0, dtype=jnp.int32)
    if layer is not None:
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((gate.shape[0] * E,), jnp.int32), sizes, (layer * E,))
        gate, up, down = (w.reshape(-1, *w.shape[2:]) for w in (gate, up, down))
    xs = x[order // k]                                         # [N*k, D]
    dt = x.dtype
    h = jax.nn.silu(grouped_matmul(xs, gate.astype(dt), sizes, *share)) \
        * grouped_matmul(xs, up.astype(dt), sizes, *share)
    ys = grouped_matmul(h, down.astype(dt), sizes, *share)     # [N*k, D]
    if first is not None:
        ys = jnp.where((jnp.arange(N * k) < sizes.sum())[:, None], ys, 0)
    back = jnp.zeros_like(order).at[order].set(jnp.arange(N * k, dtype=order.dtype))
    y = ys[back].reshape(N, k, -1).astype(jnp.float32)
    return jnp.sum(y * weights[..., None].astype(jnp.float32), axis=1).astype(dt)


def moe_apply(x, routing: RoutingInfo, expert_fn, expert_params):
    """x: [N, D]; expert_fn(params_e, xe) applied per expert via vmap.

    expert_params leaves have leading dim E (shardable over 'ep')."""
    xe = jnp.einsum("nd,nec->ecd", x, routing.dispatch.astype(x.dtype))  # [E, C, D]
    ye = jax.vmap(expert_fn)(expert_params, xe)                          # [E, C, D]
    return jnp.einsum("ecd,nec->nd", ye, routing.combine.astype(x.dtype))
