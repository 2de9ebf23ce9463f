"""Paged KV-cache decoding: block-table attention with static shapes.

How the KV cache is laid out, and which code attends over it, is decided
here and nowhere else. HBM is carved into a shared **page pool** (two, for a
stack with window layers: below); each slot owns just the pages its sequence
needs, tracked in a block table, so the same HBM serves many more concurrent
sequences at typical length distributions than a `max_len` reservation per
slot would.

All shapes stay static (XLA-first, like everything here): a pool is
[planes, num_pages, page, Hkv, Dh] over the cache planes of its kind: one a
layer, and in a looped stack (cfg.n_passes = T passes over L layers) one a
layer APPLICATION, T * L of them, plane t * L + l holding what layer l wrote
in pass t. Per-step writes are scatters at
(page_id, offset) and attention is one ragged launch over the rows' block
tables. Page allocation/free is host-side bookkeeping in the engine
(a free list), mirroring how vLLM's scheduler owns its block tables.

The decode step carries the pools through the layer scan (viewed flat,
[L*num_pages, page, Hkv, Dh], layer l's pages at l*num_pages + id) and
scatters each layer's rows into the carry in place, because pools handed to
the scan as inputs and stacked as its outputs are sliced, copied and
rewritten whole every step.

Whole pages (a prefill's, a chunk's, transferred ones) enter a donated pool
one `dynamic_update_slice` a page (`_set_pages`): as ONE scatter along the
page axis, a pool whose (Hkv, Dh) tile is (4, 128) was re-laid whole, in and
out, around the scatter (four KV heads: 18 ms a call for 25 MB written).

A model with latent attention (cfg.kv_lora_rank, MLA) has ONE pool, `kp`
[L, num_pages, page, latent_lanes], and no `vp`: a token's row is (c |
k_rope | padding to whole lanes), nothing per head. Prefill attends in
expanded form (per-head K and V from the rows), the decode step in absorbed
form over the rows as they lie in the pool: the same mathematics.

A model with window layers (cfg.window) has TWO kinds of cache state a row.
Its full layers keep `kp`/`vp` [L_full, num_pages, page, Hkv, Dh] and the
`block` table, pages that grow with the row: page j of a row holds cache
positions [j*P, (j+1)*P). Its window layers get pools of their own, `wkp`/
`wvp` [L_win, window_pages, page, Hkv, Dh], and a row a **ring** of at most
`window_ring` pages (`wblock`, of which the row holds `wring`), granted once
at admission: logical page q = t // P lies in ring slot q % wring, so a page
the window has left behind is written over by the page `wring` further on
and no table changes on the device while the row lives. The ring has
window / P + 1 slots for the pages a decode step sweeps and a prefill
chunk's worth of room, so that a chunk (padded to its bucket) never writes
over a page the chunk's own queries still see. Page 0 of both pools is
scratch. One state, one decode step, one chunked prefill for both kinds.

(reference capability: vLLM paged attention behind
llm/_internal/serve/engines/vllm/vllm_engine.py:114; design here is
TPU-native — static gathers and a Pallas kernel, no custom CUDA.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_tpu.models.decoding import _attn_qkv, _mla_prefill_attn, _mlp_block
from ray_tpu.models.transformer import (TransformerConfig, _mla_absorb_out, _mla_absorb_q,
                                        _mla_project, _norm, _residual, close_pass,
                                        exit_distribution, kind_index, rope_by_kind,
                                        scan_layers)
from ray_tpu import ops


def window_ring(cfg: TransformerConfig, page_size: int,
                prefill_chunk: int | None = None) -> int:
    """Pages of a long row's ring in the window pool: the window / P + 1 a
    decode step sweeps, and a prefill chunk of room (module docstring)."""
    if cfg.window % page_size:
        raise ValueError(f"window {cfg.window} must be a multiple of "
                         f"page_size {page_size}: a decode step sweeps whole pages")
    return cfg.window // page_size + 1 + (prefill_chunk or 0) // page_size


def init_paged_state(cfg: TransformerConfig, max_slots: int, max_len: int,
                     num_pages: int, page_size: int, window_pages: int | None = None,
                     ring: int | None = None) -> dict:
    """Page pool + block tables. `num_pages * page_size` is the total token
    capacity shared by all slots (oversubscribable vs max_slots*max_len).
    With window layers: `ring` slots a row (default `window_ring` without a
    chunk, never more than a row's pages) in a pool of `window_pages`
    (default: a ring for every slot + scratch, or num_pages if that is
    fewer: no row holds more window pages than full ones)."""
    # cache planes: the layers, times the passes of a looped stack (which has
    # neither latent rows nor window layers)
    L, Hkv, Dh = cfg.n_planes, cfg.kv_heads, cfg.head_dim
    max_pages_per_seq = (max_len + page_size - 1) // page_size
    gate = {}
    if cfg.exit_gate:  # the last decode step's exit CDF a row, by pass
        gate["exit_cdf"] = jnp.zeros((max_slots, cfg.n_passes), jnp.float32)
    if cfg.mla:
        pools = {"kp": jnp.zeros((L, num_pages, page_size, cfg.latent_lanes), cfg.dtype)}
    elif cfg.window:
        ring = min(ring or window_ring(cfg, page_size), max_pages_per_seq)
        window_pages = window_pages or min(num_pages, max_slots * ring + 1)
        Lf = cfg.n_full_layers
        pools = {"kp": jnp.zeros((Lf, num_pages, page_size, Hkv, Dh), cfg.dtype),
                 "vp": jnp.zeros((Lf, num_pages, page_size, Hkv, Dh), cfg.dtype),
                 "wkp": jnp.zeros((L - Lf, window_pages, page_size, Hkv, Dh), cfg.dtype),
                 "wvp": jnp.zeros((L - Lf, window_pages, page_size, Hkv, Dh), cfg.dtype),
                 # ring slot -> page id of the window pool; `wring` slots held
                 "wblock": jnp.zeros((max_slots, ring), jnp.int32),
                 "wring": jnp.ones((max_slots,), jnp.int32)}
    else:
        pools = {"kp": jnp.zeros((L, num_pages, page_size, Hkv, Dh), cfg.dtype),
                 "vp": jnp.zeros((L, num_pages, page_size, Hkv, Dh), cfg.dtype)}
    return {
        **pools, **gate,
        # page ids per slot; unused entries point at page 0 (masked anyway)
        "block": jnp.zeros((max_slots, max_pages_per_seq), jnp.int32),
        "length": jnp.zeros((max_slots,), jnp.int32),
        "last_token": jnp.zeros((max_slots,), jnp.int32),
        "active": jnp.zeros((max_slots,), jnp.bool_),
    }


@functools.partial(jax.jit, donate_argnames=("state",), static_argnames=("cfg",))
def insert_sequence_paged(state, slot, kv, length, first_token, pages,
                          cfg: TransformerConfig, window_pages=None):
    """Write a prefilled [L, T, Hkv, Dh] KV into the first T/page_size of
    this slot's `pages` (int32 [max_pages_per_seq], padded with 0 — the
    engine grants ALL pages the sequence will ever need up front, so no
    mid-flight allocation) and activate the row.

    With window layers the full layers' part goes to `pages`, the window
    layers' to the row's ring `window_pages` (int32 [ring] ids of the window
    pool, padded with 0; default: the leading entries of `pages`, which are
    valid ids there as long as the window pool is no smaller than the
    row): of the T/page_size pages only the last ones, those a decode step
    can still see, and never more than the ring holds."""
    if cfg.window:
        state, kv = _insert_ring(state, slot, kv, length, pages, window_pages)
    state = _write_pages(state, kv, pages)
    return _activate(state, slot, pages, length, first_token)


def _activate(state, slot, block_row, length, first_token) -> dict:
    state["block"] = jax.lax.dynamic_update_slice_in_dim(
        state["block"], block_row[None], slot, axis=0)
    state["length"] = state["length"].at[slot].set(length)
    state["last_token"] = state["last_token"].at[slot].set(first_token)
    state["active"] = state["active"].at[slot].set(True)
    return state


def _split_kinds(kv, state) -> tuple:
    """A prefill's {k, v: [L, T, ...]} -> (the full layers' {k, v}, the
    window layers' {wk, wv}): each period's last layer is its full layer."""
    full, window = {}, {}
    for name, rows in kv.items():
        L, Lf = rows.shape[0], state["kp"].shape[0]
        folded = rows.reshape(Lf, L // Lf, *rows.shape[1:])
        full[name] = folded[:, -1]
        window["w" + name] = folded[:, :-1].reshape(L - Lf, *rows.shape[1:])
    return full, window


def _set_ring(state, slot, ring_ids) -> dict:
    """A copy of `state` in which row `slot` holds the ring `ring_ids` [ring]:
    its nonzero ids (page 0 is scratch, never granted) lead."""
    state = dict(state)
    state["wblock"] = jax.lax.dynamic_update_slice_in_dim(
        state["wblock"], ring_ids[None], slot, axis=0)
    state["wring"] = state["wring"].at[slot].set(_ring_held(ring_ids))
    return state


def _ring_pages(ids, held, logical):
    """Where rings hold logical pages (t // P) of the window layers: ring
    slot q % held, scratch page 0 for a q before the row's start. `ids`
    [..., ring] page ids of the window pool, `held` [...] slots held of
    them, `logical` [..., n] -> page ids [..., n]. The one place that knows
    the ring's arithmetic: inserts, chunk writes, the chunk's prefix gather
    and the decode step all come here."""
    held = jnp.asarray(held)[..., None]
    return jnp.where(logical >= 0,
                     jnp.take_along_axis(ids, logical % held, axis=-1), 0)


def _ring_held(ring_ids):
    """Slots a row holds of its ring `ring_ids` [ring]: its nonzero ids
    (page 0 is scratch, never granted), which lead; at least 1."""
    return jnp.maximum(jnp.count_nonzero(ring_ids), 1).astype(jnp.int32)


def _insert_ring(state, slot, kv, length, pages, window_pages) -> tuple:
    """(`state` with row `slot`'s ring set and the window layers' part of a
    whole prefilled row in it, the full layers' part of `kv`). The ring is
    `window_pages`, or the leading entries of `pages`."""
    kv, window_kv = _split_kinds(kv, state)
    ring = state["wblock"].shape[1]
    state = _set_ring(state, slot, pages[:ring] if window_pages is None
                      else window_pages)
    return _write_ring(state, window_kv, slot, length), kv


def _write_ring(state, window_kv, slot, length) -> dict:
    """A copy of `state` with the window layers' part of a bucketed prefill
    ({wk, wv: [L_win, T, Hkv, Dh]}, positions 0..T-1, `length` of them real)
    in row `slot`'s ring: the min(T/P, ring) logical pages that end with the
    page of position length - 1. Logical pages before the row's start go to
    scratch page 0; the ring is never lapped: consecutive pages, no more
    than it has slots (a row that holds fewer slots than the ring's size
    holds a slot for every page it will ever reach)."""
    P, ring = state["wkp"].shape[2], state["wblock"].shape[1]
    ids, held = state["wblock"][slot], state["wring"][slot]
    state = dict(state)
    for name, rows in window_kv.items():
        pool = state[name + "p"]
        n = rows.shape[1] // P  # static: T is a bucket
        m = min(n, ring)
        q = (length - 1) // P - (m - 1) + jnp.arange(m)
        src = rows.reshape(rows.shape[0], n, P, *rows.shape[2:])[:, jnp.clip(q, 0, n - 1)]
        state[name + "p"] = _set_pages(pool, _ring_pages(ids, held, q), src)
    return state


def _write_pages(state, kv, pages) -> dict:
    """A copy of `state` with a bucketed kv ({k, v: [L, T, Hkv, Dh]}, or the
    latent {k: [L, T, lanes]}) written into the first T/page_size of `pages`."""
    P = state["kp"].shape[2]
    state = dict(state)
    for name, rows in kv.items():
        pool = state[name + "p"]
        n = rows.shape[1] // P  # static: T is a bucket
        state[name + "p"] = _set_pages(
            pool, pages[:n], rows.reshape(rows.shape[0], n, P, *rows.shape[2:]))
    return state


def _set_pages(pool, ids, src):
    """`pool` [planes, pages, P, ...] with page `ids[i]` of every plane set to
    `src[:, i]` (`ids` [n], `src` [planes, n, P, ...]): the one way whole
    pages enter a pool outside the decode step. One `dynamic_update_slice` a
    page, in the order of `ids` (ids that repeat are scratch page 0's: the
    last one stays), which updates the donated pool where it lies
    (module docstring)."""
    src = src.astype(pool.dtype)

    def set_page(i, pool):
        page = jax.lax.dynamic_slice_in_dim(src, i, 1, axis=1)
        return jax.lax.dynamic_update_slice_in_dim(pool, page, ids[i], axis=1)

    return jax.lax.fori_loop(0, ids.shape[0], set_page, pool)


@functools.partial(jax.jit, donate_argnames=("state",),
                   static_argnames=("cfg", "pages_bound", "kernel"))
def decode_step_paged_ragged(params, state, cfg: TransformerConfig,
                             pages_bound: int, kernel: bool = False,
                             lora_bank=None, slot_lora=None):
    """Advance every active row one token: one scatter of the step's rows a
    layer, then ONE ragged attention launch over the batch's block tables
    (ops/ragged_paged_attention.py): no [B, max_pages*page] gather, and the
    sweep stops at `pages_bound` — the engine's host-side bound on the
    batch's LIVE page count (power of two, so compile count stays
    O(log(max_pages))). `kernel=True` runs the Pallas TPU kernel; False
    runs the bit-consistent pure-JAX reference (the CPU path).

    The attention core sees qh [B, Hkv, G, Dh] against the FLAT pools
    [L*num_pages, P, Hkv, Dh] the scan carries, in which this layer's page
    `i` lies at `base + i`. With `state` donated the pools alias input to
    output and a step writes B rows a layer. With latent attention qh is
    [B, 1, H, lanes] (the absorbed query), kp the one pool
    [L*num_pages, P, lanes], vp None, and the result's first kv_lora_rank
    columns are sum p c. With `lora_bank` (decoding.init_lora_bank) +
    `slot_lora` [B], each row adds its own adapter's q/v deltas in the same
    step (index 0 = the null adapter = the base model).

    With window layers the scan carries both kinds of pool and each layer
    of a period takes its own (the kind is static in the unrolled period: no
    branch between carried pools, which would copy them): a window layer
    scatters at ring slot (pos // P) % wring and its launch sweeps the
    window // P + 1 logical pages that end at pos, whatever `pages_bound`.

    A looped stack scatters and attends plane by plane (plane t * L + l in
    pass t), the final norm closing every pass; with the exit gate the
    returned state's `exit_cdf` [B, n_passes] is each row's probability of
    having left the loop by the end of each pass (float32; the sampler does
    not read it: the engine counts from it)."""
    from ray_tpu.ops.ragged_paged_attention import ragged_decode_attention

    # the ragged sweep only walks the batch's live prefix of each table;
    # positions past a row's `pos` inside that prefix are masked in-kernel
    tbl = state["block"][:, :pages_bound]
    dt = cfg.dtype
    B = state["block"].shape[0]
    L, num_pages, P = state["kp"].shape[:3]
    flat = (L * num_pages,) + state["kp"].shape[2:]
    vp0 = None if cfg.mla else state["vp"].reshape(flat)
    tokens = state["last_token"][:, None]
    pos = state["length"]                                      # [B]
    page_ids = jnp.take_along_axis(state["block"],
                                   (pos // P)[:, None], axis=1)[:, 0]  # [B]
    # inactive rows scatter into page 0 — RESERVED as scratch (the engine's
    # allocator never hands out page 0), so they can't corrupt live pages
    page_ids = jnp.where(state["active"], page_ids, 0)
    offsets = pos % P                                          # [B]
    x = params["embed"].astype(dt)[tokens]
    if cfg.pos == "learned":
        x = x + params["pos_embed"].astype(dt)[pos][:, None]
    rope = rope_by_kind(cfg)
    G = cfg.n_heads // cfg.kv_heads
    lscale = None if lora_bank is None else lora_bank["scale"][slot_lora]
    bases = jnp.arange(L, dtype=jnp.int32) * num_pages
    wpools = ()
    if cfg.window:
        Lw, window_pages = state["wkp"].shape[:2]
        wflat = (Lw * window_pages,) + state["wkp"].shape[2:]
        wpools = (state["wkp"].reshape(wflat), state["wvp"].reshape(wflat))
        # a layer's first page in the pool of its kind, in depth order
        period = cfg.window_period
        bases = jnp.asarray([
            i * (num_pages if l % period == period - 1 else window_pages)
            for l, i in enumerate(kind_index(cfg))], jnp.int32)
        # the window sweep's logical pages, and where each lies in the ring
        steps = cfg.window // P + 1
        logical = (pos // P)[:, None] - (steps - 1) + jnp.arange(steps)[None]
        wtbl = _ring_pages(state["wblock"], state["wring"], logical)
        wpage_ids = jnp.where(state["active"], wtbl[:, -1], 0)

    def attend(qh, kp, vp, base, window=False):
        return ragged_decode_attention(
            qh, kp, vp, base + (wtbl if window else tbl), pos,
            scale=cfg.qk_dim ** -0.5, impl="kernel" if kernel else "reference",
            window=cfg.window if window else None)

    def block(carry, layer_in, window=False):
        h, gates, kp, vp, *others = carry        # pools [L*num_pages, P, Hkv, Dh]
        if window:                               # this layer's kind of pool
            (kp, vp), others = others, [kp, vp]
        layer_p, base, *lora_l = layer_in        # base: this layer's first page
        cos, sin = rope[window]
        rows = wpage_ids if window else page_ids
        normed = _norm(h, layer_p["norm1"], cfg)
        if cfg.mla:
            ap = layer_p["attn"]
            q, row = _mla_project(normed, ap, cfg, cos, sin, pos[:, None])
            kp = kp.at[base + page_ids, offsets].set(row[:, 0].astype(kp.dtype))
            o_lat = attend(_mla_absorb_q(q[:, 0], ap, cfg)[:, None], kp, None, base)
            out = _mla_absorb_out(o_lat[:, 0].astype(dt), ap, cfg)
            h = h + jnp.einsum("bhd,hde->be", out, ap["wo"].astype(dt))[:, None]
            h = h + _mlp_block(_norm(h, layer_p["norm2"], cfg), layer_p, cfg)
            return (h, gates, kp, vp), None
        q, k, v = _attn_qkv(normed, layer_p["attn"], cfg, lora_l, slot_lora,
                            lscale)                            # [B, 1, H, Dh]
        if cfg.pos == "rope":
            q = ops.apply_rope(q, cos, sin, positions=pos[:, None])
            k = ops.apply_rope(k, cos, sin, positions=pos[:, None])
        # scatter this step's K/V at (page, offset) per row
        kp = kp.at[base + rows, offsets].set(k[:, 0].astype(kp.dtype))
        vp = vp.at[base + rows, offsets].set(v[:, 0].astype(vp.dtype))
        qh = q[:, 0].reshape(B, cfg.kv_heads, G, cfg.head_dim)
        out = attend(qh, kp, vp, base, window)
        out = out.reshape(B, 1, cfg.n_heads, cfg.head_dim).astype(dt)
        out = jnp.einsum("bthd,hde->bte", out, layer_p["attn"]["wo"].astype(dt))
        if cfg.bias:
            out = out + layer_p["attn"]["bo"].astype(dt)
        h = _residual(h, out, layer_p, "post_attn_norm", cfg)
        h = _residual(h, _mlp_block(_norm(h, layer_p["norm2"], cfg), layer_p, cfg),
                      layer_p, "post_mlp_norm", cfg)
        if window:
            return (h, gates, *others, kp, vp), None
        return (h, gates, kp, vp, *others), None

    def close(carry, t):
        h, gates, *pools = carry
        return (*close_pass(h, gates, t, params, cfg), *pools)

    gates = jnp.zeros((cfg.n_passes, B, 1), jnp.float32) if cfg.exit_gate else None
    (x, gates, kp, vp, *wpools), _ = scan_layers(
        block, (x, gates, state["kp"].reshape(flat), vp0, *wpools), params, cfg, bases,
        *(() if lora_bank is None else
          (lora_bank[k] for k in ("A_q", "B_q", "A_v", "B_v"))), close=close)
    if cfg.tie_embeddings:
        logits = x[:, 0] @ params["embed"].astype(dt).T
    else:
        logits = x[:, 0] @ params["lm_head"].astype(dt)
    state = dict(state)
    state["kp"] = kp.reshape(state["kp"].shape)
    if vp is not None:
        state["vp"] = vp.reshape(state["vp"].shape)
    for name, pool in zip(("wkp", "wvp"), wpools):
        state[name] = pool.reshape(state[name].shape)
    if gates is not None:
        state["exit_cdf"] = jnp.cumsum(exit_distribution(gates[..., 0]), axis=0).T
    state["length"] = jnp.where(state["active"], state["length"] + 1, state["length"])
    return state, logits.astype(jnp.float32)


@functools.partial(jax.jit, donate_argnames=("state",))
def release_slot_paged(state, slot):
    state = dict(state)
    state["active"] = state["active"].at[slot].set(False)
    state["length"] = state["length"].at[slot].set(0)
    return state


# --------------------------------------------------- prefix-cache support
# (reference capability: vLLM automatic prefix caching / hash-block reuse;
# TPU design: cached blocks stay IN the page pool and are gathered into a
# dense bucketed array for the continuation prefill — static shapes, no
# custom kernels.)


@jax.jit
def gather_prefix_pages(kp, vp, page_ids):
    """Collect cached prefix KV out of the page pool: page_ids [n] →
    k, v [L, n*P, Hkv, Dh] (n static via the id vector's shape; unused
    tail ids point at scratch page 0 and are masked by prefix_len). A latent
    pool has no `vp` (None): k is the rows [L, n*P, lanes], v None."""
    L, _, P = kp.shape[:3]
    n = page_ids.shape[0]
    k = kp[:, page_ids].reshape(L, n * P, *kp.shape[3:])
    v = None if vp is None else vp[:, page_ids].reshape(L, n * P, *vp.shape[3:])
    return k, v


# float32 scores of every head at once, [H, Ts, Tp + Ts], up to this many
# bytes; over it the continuation attends one KV head's group after another
_SCORES_AT_ONCE = 2 << 30


@functools.partial(jax.jit, static_argnames=("cfg",))
def prefill_with_prefix(params, tokens, prefix_k, prefix_v, prefix_len,
                        length, cfg: TransformerConfig,
                        window_k=None, window_v=None):
    """Continuation prefill: run ONLY the suffix tokens [1, Ts] (padded
    bucket; true count `length`) attending over a cached prefix KV
    [L, Tp, Hkv, Dh] (valid first `prefix_len` positions — cached K is
    already roped at its absolute positions) plus the causal suffix.

    Returns (logits at the last suffix token [V],
             suffix kv {k, v: [L, Ts, Hkv, Dh]}); a looped stack takes its
    prefix and returns its suffix by plane, [n_passes * L, ...].
    Compilation count is bounded by #prefix_buckets × #suffix_buckets.
    With latent attention `prefix_k` is the cached rows [L, Tp, lanes],
    `prefix_v` None: every layer expands them to per-head K and V.
    With window layers `prefix_k`/`prefix_v` are the full layers' [L_full,
    Tp, Hkv, Dh] and `window_k`/`window_v` [L_win, Tw, Hkv, Dh] hold the Tw
    positions that END at the prefix's end (position prefix_len - Tw + j at
    j; before the row's start: masked): a window layer attends over those
    within the window, and the suffix kv comes back for all L layers.
    """
    dt = cfg.dtype
    B, Ts = tokens.shape
    Tp = prefix_k.shape[1]
    x = params["embed"].astype(dt)[tokens]
    pos_suffix = prefix_len + jnp.arange(Ts)                     # [Ts]
    if cfg.pos == "learned":
        x = x + params["pos_embed"].astype(dt)[pos_suffix][None]
    rope = rope_by_kind(cfg)

    # [Ts, Tp + Ts]: every suffix query sees the real prefix positions and
    # its causal suffix slice
    prefix_mask = jnp.broadcast_to(
        jnp.arange(Tp)[None, :] < prefix_len, (Ts, Tp))
    causal = jnp.arange(Ts)[:, None] >= jnp.arange(Ts)[None, :]
    masks = {False: jnp.concatenate([prefix_mask, causal], axis=1)}
    per_layer = (prefix_k, prefix_v)
    if cfg.window:
        # [Ts, Tw + Ts]: key j of the gathered span stands at prefix_len - Tw
        # + j, suffix key j at prefix_len + j; query i at prefix_len + i
        Tw = window_k.shape[1]
        kpos = jnp.concatenate([jnp.arange(Tw) - Tw, jnp.arange(Ts)])[None, :]
        qpos = jnp.arange(Ts)[:, None]
        masks[True] = ((kpos <= qpos) & (qpos - kpos < cfg.window)
                       & (kpos >= -prefix_len))
        per_layer = (jnp.asarray(kind_index(cfg), jnp.int32),)

    def block(h, layer_in, window=False):
        if cfg.window:
            layer_p, i = layer_in
            pk, pv = (jax.lax.dynamic_index_in_dim(t, i, 0, keepdims=False)
                      for t in ((window_k, window_v) if window
                                else (prefix_k, prefix_v)))
        else:
            layer_p, pk, pv = layer_in                # [Tp, Hkv, Dh] each
        mask, (cos, sin) = masks[window], rope[window]
        normed = _norm(h, layer_p["norm1"], cfg)
        if cfg.mla:
            out, rows = _mla_prefill_attn(normed, layer_p["attn"], cfg, cos, sin,
                                          pos_suffix, prefix=pk, mask=mask)
            h = h + out
            h = h + _mlp_block(_norm(h, layer_p["norm2"], cfg), layer_p, cfg)
            return h, (rows,)
        q, k, v = _attn_qkv(normed, layer_p["attn"], cfg)  # [1, Ts, H, Dh]
        if cfg.pos == "rope":
            q = ops.apply_rope(q, cos, sin, positions=pos_suffix)
            k = ops.apply_rope(k, cos, sin, positions=pos_suffix)
        k_all = jnp.concatenate([pk[None].astype(dt), k], axis=1)
        v_all = jnp.concatenate([pv[None].astype(dt), v], axis=1)
        G = cfg.n_heads // cfg.kv_heads
        qh = q.reshape(B, Ts, cfg.kv_heads, G, cfg.head_dim)

        def attend(qh, k_all, v_all):
            scores = jnp.einsum("btkgd,bskd->btkgs", qh,
                                k_all.astype(dt)) / (cfg.head_dim ** 0.5)
            scores = jnp.where(mask[None, :, None, None, :],
                               scores.astype(jnp.float32), -1e30)
            w = jax.nn.softmax(scores, axis=-1).astype(dt)
            return jnp.einsum("btkgs,bskd->btkgd", w, v_all.astype(dt))

        if 4 * B * cfg.n_heads * Ts * k_all.shape[1] <= _SCORES_AT_ONCE:
            out = attend(qh, k_all, v_all)
        else:  # one KV head's group of query heads at a time
            out = jax.lax.map(
                lambda one: attend(*(t[:, :, None] for t in one))[:, :, 0],
                tuple(jnp.moveaxis(t, 2, 0) for t in (qh, k_all, v_all)))
            out = jnp.moveaxis(out, 0, 2)
        out = out.reshape(B, Ts, cfg.n_heads, cfg.head_dim)
        out = jnp.einsum("bthd,hde->bte", out, layer_p["attn"]["wo"].astype(dt))
        if cfg.bias:
            out = out + layer_p["attn"]["bo"].astype(dt)
        h = _residual(h, out, layer_p, "post_attn_norm", cfg)
        h = _residual(h, _mlp_block(_norm(h, layer_p["norm2"], cfg), layer_p, cfg),
                      layer_p, "post_mlp_norm", cfg)
        return h, (k[0], v[0])

    x, kv = scan_layers(block, x, params, cfg, *per_layer,
                        close=lambda h, t: close_pass(h, None, t, params, cfg)[0])
    last = x[0, length - 1]
    if cfg.tie_embeddings:
        logits = last @ params["embed"].astype(dt).T
    else:
        logits = last @ params["lm_head"].astype(dt)
    return logits.astype(jnp.float32), dict(zip("kv", kv))


@functools.partial(jax.jit, donate_argnames=("state",))
def write_kv_pages(state, kv, pages, ring_ids=None, start=None):
    """Write a bucketed [L, T, Hkv, Dh] KV into `pages` (T/page_size ids)
    WITHOUT touching the row bookkeeping — the chunked-prefill building
    block: chunks accumulate into the pool page by page, and the row only
    activates once the whole prompt is resident (activate_slot). With window
    layers their part goes into the row's ring `ring_ids` [ring] (the ids
    `activate_slot` will take), at the slots of the T/page_size logical
    pages from position `start` (a multiple of the page size) on."""
    if ring_ids is not None:
        kv, window_kv = _split_kinds(kv, state)
        P = state["wkp"].shape[2]
        logical = start // P + jnp.arange(window_kv["wk"].shape[1] // P)
        state = _write_pages(state, window_kv,
                             _ring_pages(ring_ids, _ring_held(ring_ids), logical))
    return _write_pages(state, kv, pages)


@functools.partial(jax.jit, static_argnames=("cfg",))
def gather_window_pages(state, ring_ids, start, cfg: TransformerConfig):
    """The window layers' prefix of a chunk that starts at position `start`
    (a multiple of the page size): k, v [L_win, window, Hkv, Dh], the
    window's worth of logical pages that end there, out of the row's ring
    `ring_ids` [ring] (before the row's start: scratch page 0, which
    `prefill_with_prefix` masks)."""
    P = state["wkp"].shape[2]
    span = cfg.window // P
    logical = start // P - span + jnp.arange(span)
    return gather_prefix_pages(
        state["wkp"], state["wvp"],
        _ring_pages(ring_ids, _ring_held(ring_ids), logical))


@functools.partial(jax.jit, donate_argnames=("state",))
def activate_slot(state, slot, block_row, length, first_token, window_pages=None):
    """Turn a fully-prefilled slot live for decode (the bookkeeping half
    of insert_sequence_paged, after write_kv_pages staged the KV); with
    window layers `window_pages` [ring] is the row's ring."""
    if window_pages is not None:
        state = _set_ring(state, slot, window_pages)
    return _activate(dict(state), slot, block_row, length, first_token)


@functools.partial(jax.jit, donate_argnames=("state",), static_argnames=("cfg",))
def insert_sequence_paged_prefix(state, slot, kv, suffix_pages, block_row,
                                 length, first_token, cfg: TransformerConfig,
                                 window_pages=None):
    """Like insert_sequence_paged, but only the SUFFIX KV is written (the
    prefix already lives in shared cache pages): `suffix_pages` [ns] are
    the pages receiving the suffix bucket, `block_row`
    [max_pages_per_seq] is the full table (shared prefix ids + private
    ids + 0-padding). With window layers nothing is shared (their pages lie
    in a ring: no prefix cache over them), the suffix is the whole row, and
    its window layers' part goes to the ring `window_pages` as
    insert_sequence_paged puts it there."""
    if cfg.window:
        state, kv = _insert_ring(state, slot, kv, length, block_row, window_pages)
    state = _write_pages(state, kv, suffix_pages)
    return _activate(state, slot, block_row, length, first_token)
