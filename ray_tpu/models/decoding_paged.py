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

A model with state-space layers (cfg.ssm) has two kinds of state a row as
well, and only one of them is pages. Its attention layers keep `kp`/`vp`
[L_attn, num_pages, page, Hkv, Dh] and the `block` table, pages that grow
with the row. Its state-space layers keep a FIXED state a slot, whatever the
row's length: `ssm` [L_ssm, max_slots, H, P, N] (float32: the recurrent
state) and `conv` [L_ssm, max_slots, d_conv - 1, conv_dim] (the
convolution's last inputs). Slot s of both belongs to the row in slot s for
its whole life: an insert writes the prefilled row's state over whatever the
last occupant left, the decode step updates every live row's state where it
lies (an inactive row is stepped with dt = 0, which changes nothing), and a
chunked prefill carries the state from chunk to chunk OUTSIDE the slots
(`prefill_with_prefix(row_state=)`), so that it enters its slot only when
the row goes live (`activate_slot(row_state=)`). Recurrent layers of the
second kind (`KDAConfig`: the gated delta rule) keep the same two leaves
under the same names, shaped by the config's `state_shape` and `conv_dim`:
`ssm` [L_kda, max_slots, H, D, D] (float32: a matrix a head) and `conv`
[L_kda, max_slots, d_conv - 1, 3 H D] (the tails of q, k and v).

(reference capability: vLLM paged attention behind
llm/_internal/serve/engines/vllm/vllm_engine.py:114; design here is
TPU-native — static gathers and a Pallas kernel, no custom CUDA.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_tpu.models.decoding import (_attn_qkv, _close_block, _mla_prefill_attn, _mlp_block,
                                     counts_experts, kv_tree)
# `_residual` is this module's by name for chipbench/loop_faults.py, which
# plants a wrong one here and in decoding.py (whose `_close_block` calls it)
from ray_tpu.models.transformer import (TransformerConfig, _mla_absorb_out, _mla_absorb_q,
                                        _mla_project, _norm, _residual, attn_gated,  # noqa: F401
                                        close_pass, embed_tokens, exit_distribution,
                                        is_full_layer, kda_out, kda_project, kda_split,
                                        kind_index, lm_logits, mixer_out, mixer_project,
                                        mixer_split, recurrent_mixer, rope_by_kind,
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
    # cache planes: the attention layers, times the passes of a looped stack
    # (which has neither latent rows nor window layers)
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
    else:  # a token's row (Hkv, Dh), or packed 128 lanes wide (cfg.kv_packed)
        pools = {"kp": jnp.zeros((L, num_pages, page_size, *cfg.kv_row), cfg.dtype),
                 "vp": jnp.zeros((L, num_pages, page_size, *cfg.kv_row), cfg.dtype)}
    if cfg.ssm:  # a fixed state a slot beside the pages (module docstring)
        s = cfg.ssm
        pools.update(
            ssm=jnp.zeros((cfg.n_ssm_layers, max_slots, *s.state_shape), jnp.float32),
            conv=jnp.zeros((cfg.n_ssm_layers, max_slots, s.d_conv - 1, s.conv_dim),
                           cfg.dtype))
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
    can still see, and never more than the ring holds.
    With state-space layers kv's `ssm` and `conv` go into slot `slot` of the
    state of that name: the last occupant's is written over whole."""
    kv = _pages_part(kv)
    if cfg.window:
        state, kv = _insert_ring(state, slot, kv, length, pages, window_pages,
                                 cfg.n_dense_layers)
    kv, row_state = _split_row_state(kv)
    state = _set_row_state(_write_pages(state, kv, pages), slot, row_state)
    return _activate(state, slot, pages, length, first_token)


def _pages_part(kv) -> dict:
    """A prefill's kv without what is no layer's K, V or state: the counts of
    a model whose expert layers hold a share (`decoding.kv_tree`)."""
    return {name: t for name, t in kv.items() if name != "expert_counts"}


def _split_row_state(kv) -> tuple:
    """A prefill's kv -> (its part that goes to pages, its recurrent part
    {ssm, conv}: None for a model without state-space layers)."""
    row = {name: kv[name] for name in ("ssm", "conv") if name in kv}
    return {name: t for name, t in kv.items() if name not in row}, row or None


def _set_row_state(state, slot, row_state) -> dict:
    """`state` with slot `slot` of the recurrent state set to `row_state`
    ({ssm [L_ssm, H, P, N], conv [L_ssm, d_conv - 1, conv_dim]}; None: as it
    is): a `dynamic_update_slice` a kind, into the donated state where it lies."""
    if row_state is None:
        return state
    state = dict(state)
    for name, rows in row_state.items():
        state[name] = jax.lax.dynamic_update_slice_in_dim(
            state[name], rows[:, None].astype(state[name].dtype), slot, axis=1)
    return state


def _activate(state, slot, block_row, length, first_token) -> dict:
    state["block"] = jax.lax.dynamic_update_slice_in_dim(
        state["block"], block_row[None], slot, axis=0)
    state["length"] = state["length"].at[slot].set(length)
    state["last_token"] = state["last_token"].at[slot].set(first_token)
    state["active"] = state["active"].at[slot].set(True)
    return state


def _split_kinds(kv, state, dense: int = 0) -> tuple:
    """A prefill's {k, v: [L, T, ...]} -> (the full layers' {k, v}, the
    window layers' {wk, wv}): the `dense` leading dense layers are window
    layers, and of the periods after them each one's last layer is its full
    layer (`transformer.is_full_layer`)."""
    full, window = {}, {}
    for name, rows in kv.items():
        L, Lf = rows.shape[0] - dense, state["kp"].shape[0]
        folded = rows[dense:].reshape(Lf, L // Lf, *rows.shape[1:])
        full[name] = folded[:, -1]
        window["w" + name] = folded[:, :-1].reshape(L - Lf, *rows.shape[1:])
        if dense:
            window["w" + name] = jnp.concatenate([rows[:dense], window["w" + name]])
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


def _insert_ring(state, slot, kv, length, pages, window_pages, dense: int = 0) -> tuple:
    """(`state` with row `slot`'s ring set and the window layers' part of a
    whole prefilled row in it, the full layers' part of `kv`). The ring is
    `window_pages`, or the leading entries of `pages`."""
    kv, window_kv = _split_kinds(kv, state, dense)
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


def _pack_queries(qh, head_dim: int):
    """Queries [B, Hkv, G, Dh] for a pool that packs r = 128 / Dh KV heads a
    row of 128 lanes: [B, Hkv / r, r * G, 128], a head's queries in the lanes
    its keys lie in and zeros in the others', so that the launch is the
    per-head one over Hkv / r heads of 128 (the zeros add nothing to a
    score)."""
    r = 128 // head_dim
    B, Hkv, G, Dh = qh.shape
    own = jnp.eye(r, dtype=qh.dtype)[None, None, :, None, :, None]
    wide = qh.reshape(B, Hkv // r, r, G, 1, Dh) * own
    return wide.reshape(B, Hkv // r, r * G, r * Dh)


def _unpack_outputs(out, head_dim: int):
    """The launch's [B, Hkv / r, r * G, 128] -> [B, Hkv, G, Dh]: of each
    query's 128 lanes the ones its own head's values lie in."""
    r = 128 // head_dim
    B, rows, rG, _ = out.shape
    own = jnp.eye(r, dtype=out.dtype)[None, None, :, None, :, None]
    wide = out.reshape(B, rows, r, rG // r, r, head_dim)
    return (wide * own).sum(axis=4).reshape(B, rows * r, rG // r, head_dim)


def _set_pages(pool, ids, src):
    """`pool` [planes, pages, P, ...] with page `ids[i]` of every plane set to
    `src[:, i]` (`ids` [n], `src` [planes, n, P, ...]): the one way whole
    pages enter a pool outside the decode step. One `dynamic_update_slice` a
    page, in the order of `ids` (ids that repeat are scratch page 0's: the
    last one stays), which updates the donated pool where it lies
    (module docstring). A pool that packs its KV heads (cfg.kv_packed) takes
    the per-head `src` [.., P, Hkv, Dh] as the rows it stores."""
    if src.shape[3:] != pool.shape[3:]:
        src = src.reshape(*src.shape[:3], *pool.shape[3:])
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
    (ops/ragged_paged_attention.py): no [B, max_pages*page] gather. For the
    reference (and the latent kernel) the table is cut to `pages_bound`
    columns — the engine's host-side bound on the batch's LIVE page count
    (power of two, so compile count stays O(log(max_pages))) — which bounds
    their walk; the per-head kernel takes the table whole and walks each
    active row's own pages, blocks of them at a time, and no page of a row
    that is not active. `kernel=True` runs the Pallas TPU kernel; False
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
    not read it: the engine counts from it).

    With state-space layers the scan also carries `ssm` and `conv` whole and
    a state-space layer updates its own plane of both where it lies
    (`ops.ssm_state_update`, or `ops.kda_state_update` for the gated delta
    rule's layers: the Pallas kernel with `kernel`, `jax.numpy` without); an
    inactive row keeps its state and its tail, and the kernel does not touch
    it.

    For a model whose expert layers hold a share of the experts
    (`decoding.counts_experts`) the returned state's `expert_counts` int32 [2]
    is this step's `ops.share_counts`, summed over the layers."""
    from ray_tpu.ops.ragged_paged_attention import ragged_decode_attention, table_width

    # the launches whose sweep the table's width bounds get the batch's live
    # prefix of it (the reference, the latent kernel's grid); the per-head
    # kernel that walks each row's own pages takes it whole (`table_width`)
    nb = pages_bound if cfg.mla else table_width(
        state["block"].shape[1], pages_bound, state["kp"].shape[-1], kernel)
    tbl = state["block"][:, :nb]
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
    x = embed_tokens(params, tokens, cfg)
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
        bases = jnp.asarray([
            i * (num_pages if is_full_layer(cfg, l) else window_pages)
            for l, i in enumerate(kind_index(cfg))], jnp.int32)
        # the window sweep's logical pages, and where each lies in the ring
        steps = cfg.window // P + 1
        logical = (pos // P)[:, None] - (steps - 1) + jnp.arange(steps)[None]
        wtbl = _ring_pages(state["wblock"], state["wring"], logical)
        wpage_ids = jnp.where(state["active"], wtbl[:, -1], 0)

    # a row that is not active walks no page (the launch's `pos` < 0)
    walked = jnp.where(state["active"], pos, -1)

    def attend(qh, kp, vp, base, window=False):
        if cfg.kv_packed:
            qh = _pack_queries(qh, cfg.head_dim)
        out = ragged_decode_attention(
            qh, kp, vp, base + (wtbl if window else tbl), walked,
            scale=cfg.softmax_scale, impl="kernel" if kernel else "reference",
            window=cfg.window if window else None)
        return _unpack_outputs(out, cfg.head_dim) if cfg.kv_packed else out

    # the update kernel's walk over the live rows, the same for every layer
    schedule = ops.live_rows(state["active"]) if cfg.ssm and kernel else None

    def stored(t):
        """This step's K or V [B, 1, Hkv, Dh] as the pool stores a token's row."""
        return t[:, 0].reshape(B, *cfg.kv_row) if cfg.kv_packed else t[:, 0]

    def kda_block(carry, layer_in):
        h, gates, kp, vp, rec, conv = carry      # the recurrent state, whole
        layer_p, i = layer_in                    # i: this layer's plane of it
        p = layer_p["mixer"]
        qkv, g, beta, gate = kda_project(_norm(h, layer_p["norm1"], cfg)[:, 0], p, cfg)
        tail = jax.lax.dynamic_index_in_dim(conv, i, 0, keepdims=False)  # [B, K-1, C]
        q, k, v = kda_split(jax.vmap(
            lambda x, t: ops.causal_conv(x[None], t, p["conv_w"])[0])(
                qkv.astype(jnp.float32), tail), cfg)
        rec, o = ops.kda_state_update(
            rec, i, q, k, v, g, beta, live=state["active"], schedule=schedule,
            impl="kernel" if kernel else "reference")
        moved = jnp.concatenate([tail[:, 1:], qkv[:, None].astype(tail.dtype)], axis=1)
        conv = jax.lax.dynamic_update_index_in_dim(
            conv, jnp.where(state["active"][:, None, None], moved, tail), i, 0)
        h, counts = _close_block(h, kda_out(o, gate, p, cfg)[:, None], layer_p, cfg)
        return (h, gates, kp, vp, rec, conv), (counts or None)

    def ssm_block(carry, layer_in):
        if cfg.kda:
            return kda_block(carry, layer_in)
        h, gates, kp, vp, rec, conv = carry      # the recurrent state, whole
        layer_p, i = layer_in                    # i: this layer's plane of it
        p = layer_p["mixer"]
        z, xBC, dts = mixer_project(_norm(h, layer_p["norm1"], cfg)[:, 0], p, cfg)
        tail = jax.lax.dynamic_index_in_dim(conv, i, 0, keepdims=False)  # [B, K-1, C]
        xs, Bs, Cs = mixer_split(jax.vmap(
            lambda x, t: ops.causal_conv(x[None], t, p["conv_w"], p["conv_b"])[0])(
                xBC, tail), cfg)
        rec, y = ops.ssm_state_update(
            rec, i, xs, dts, -jnp.exp(p["A_log"].astype(jnp.float32)), Bs, Cs,
            live=state["active"], schedule=schedule,
            impl="kernel" if kernel else "reference")
        moved = jnp.concatenate([tail[:, 1:], xBC[:, None].astype(tail.dtype)], axis=1)
        conv = jax.lax.dynamic_update_index_in_dim(
            conv, jnp.where(state["active"][:, None, None], moved, tail), i, 0)
        h, _ = _close_block(h, mixer_out(y, xs, z, p, cfg)[:, None], layer_p, cfg)
        return (h, gates, kp, vp, rec, conv), None

    def block(carry, layer_in, window=False, ssm=False):
        if ssm:
            return ssm_block(carry, layer_in)
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
        if cos is not None:  # this kind of layer has the rope
            q = ops.apply_rope(q, cos, sin, positions=pos[:, None])
            k = ops.apply_rope(k, cos, sin, positions=pos[:, None])
        # scatter this step's K/V at (page, offset) per row
        kp = kp.at[base + rows, offsets].set(stored(k).astype(kp.dtype))
        vp = vp.at[base + rows, offsets].set(stored(v).astype(vp.dtype))
        qh = q[:, 0].reshape(B, cfg.kv_heads, G, cfg.head_dim)
        out = attend(qh, kp, vp, base, window)
        out = out.reshape(B, 1, cfg.n_heads, cfg.head_dim).astype(dt)
        out = jnp.einsum("bthd,hde->bte", attn_gated(out, normed, layer_p["attn"], cfg),
                         layer_p["attn"]["wo"].astype(dt))
        if cfg.bias:
            out = out + layer_p["attn"]["bo"].astype(dt)
        h, counts = _close_block(h, out, layer_p, cfg)
        if window:
            return (h, gates, *others, kp, vp), (counts or None)
        return (h, gates, kp, vp, *others), (counts or None)

    def close(carry, t):
        h, gates, *pools = carry
        return (*close_pass(h, gates, t, params, cfg), *pools)

    gates = jnp.zeros((cfg.n_passes, B, 1), jnp.float32) if cfg.exit_gate else None
    # what the scan carries beside kp and vp: the window pools, or the
    # recurrent state
    others = ("wkp", "wvp") if cfg.window else ("ssm", "conv") if cfg.ssm else ()
    if cfg.ssm:
        wpools = (state["ssm"], state["conv"])
    (x, gates, kp, vp, *wpools), counts = scan_layers(
        block, (x, gates, state["kp"].reshape(flat), vp0, *wpools), params, cfg, bases,
        *(() if lora_bank is None else
          (lora_bank[k] for k in ("A_q", "B_q", "A_v", "B_v"))), close=close,
        ssm_per_layer=((jnp.arange(cfg.n_ssm_layers, dtype=jnp.int32),)
                       if cfg.ssm else ()))
    logits = lm_logits(x[:, 0], params, cfg)
    state = dict(state)
    state["kp"] = kp.reshape(state["kp"].shape)
    if vp is not None:
        state["vp"] = vp.reshape(state["vp"].shape)
    for name, pool in zip(others, wpools):
        state[name] = pool.reshape(state[name].shape)
    if gates is not None:
        state["exit_cdf"] = jnp.cumsum(exit_distribution(gates[..., 0]), axis=0).T
    if counts_experts(cfg):  # this step's, for the engine to take with its tokens
        state["expert_counts"] = (
            counts[0][0].sum(axis=0) + counts[1][0].sum(axis=0) if cfg.ssm  # by kind
            else counts[0].sum(axis=0))
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
# dense bucketed array for the continuation prefill — static shapes; the
# attention over it is one flash launch a layer where `continuation_blocks`
# says so, since PR 47.)


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


def continuation_blocks(cfg: TransformerConfig, chunk: int, span: int, kernel: bool):
    """The blocks of the flash launch that a chunk of `chunk` tokens attends
    by over a gathered span of `span` (ops/flash_attention.py
    `flash_prefix_attention`), or None where the continuation keeps the XLA
    form: without `kernel` (the caller sees no unsharded TPU), with latent
    attention, with a packed pool's heads of 64, or at shapes the launch does
    not take (`prefix_blocks`: a tail's bucket under 1,024). From
    what the program's shapes say, for the program and for the engine's count
    alike."""
    if not kernel or cfg.mla or cfg.kv_packed:
        return None
    return ops.prefix_blocks(chunk, span, cfg.head_dim)


@functools.partial(jax.jit, static_argnames=("cfg", "kernel"))
def prefill_with_prefix(params, tokens, prefix_k, prefix_v, prefix_len,
                        length, cfg: TransformerConfig,
                        window_k=None, window_v=None, row_state=None,
                        kernel: bool = False):
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
    With state-space layers `prefix_k`/`prefix_v` are the attention layers'
    [L_attn, Tp, Hkv, Dh] and `row_state` {ssm, conv} is the recurrent state
    and the convolution's tail at the prefix's end (what the chunk before
    returned): the suffix runs on from them, and kv's `ssm` / `conv` are
    those at the suffix's last real position.
    `kernel`: the attention is one flash launch a layer where
    `continuation_blocks` says so; elsewhere, and without it, two einsums
    around a float32 softmax over scores that are written out (`scored`).
    """
    dt = cfg.dtype
    B, Ts = tokens.shape
    Tp = prefix_k.shape[1]
    x = embed_tokens(params, tokens, cfg)
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

    def block(h, layer_in, window=False, ssm=False):
        if ssm:
            layer_p, rec, tail = layer_in
            mixer = recurrent_mixer(cfg)
            out, rec, tail = mixer(_norm(h, layer_p["norm1"], cfg)[0],
                                   layer_p["mixer"], cfg, length, rec, tail)
            h, counts = _close_block(h, out[None], layer_p, cfg)
            return h, (rec, tail, *counts)
        if cfg.window:
            layer_p, i = layer_in
            pk, pv = (jax.lax.dynamic_index_in_dim(t, i, 0, keepdims=False)
                      for t in ((window_k, window_v) if window
                                else (prefix_k, prefix_v)))
        else:
            layer_p, pk, pv = layer_in                # [Tp, Hkv, Dh] each
        if cfg.kv_packed:                             # as gathered out of the pool
            pk, pv = (t.reshape(Tp, cfg.kv_heads, cfg.head_dim) for t in (pk, pv))
        mask, (cos, sin) = masks[window], rope[window]
        normed = _norm(h, layer_p["norm1"], cfg)
        if cfg.mla:
            out, rows = _mla_prefill_attn(normed, layer_p["attn"], cfg, cos, sin,
                                          pos_suffix, prefix=pk, mask=mask)
            h = h + out
            h = h + _mlp_block(_norm(h, layer_p["norm2"], cfg), layer_p, cfg)
            return h, (rows,)
        q, k, v = _attn_qkv(normed, layer_p["attn"], cfg)  # [1, Ts, H, Dh]
        if cos is not None:  # this kind of layer has the rope
            q = ops.apply_rope(q, cos, sin, positions=pos_suffix)
            k = ops.apply_rope(k, cos, sin, positions=pos_suffix)
        blocks = continuation_blocks(cfg, Ts, pk.shape[0], kernel and B == 1)
        if blocks is not None:  # one flash launch: no score leaves VMEM
            out = ops.flash_prefix_attention(
                q[0], pk.astype(dt), pv.astype(dt), k[0], v[0], prefix_len,
                scale=cfg.softmax_scale, window=cfg.window if window else None,
                blocks=blocks)
        else:
            out = scored(q, jnp.concatenate([pk[None].astype(dt), k], axis=1),
                         jnp.concatenate([pv[None].astype(dt), v], axis=1), mask)
        out = out.reshape(B, Ts, cfg.n_heads, cfg.head_dim)
        out = jnp.einsum("bthd,hde->bte", attn_gated(out, normed, layer_p["attn"], cfg),
                         layer_p["attn"]["wo"].astype(dt))
        if cfg.bias:
            out = out + layer_p["attn"]["bo"].astype(dt)
        h, counts = _close_block(h, out, layer_p, cfg)
        return h, (k[0], v[0], *counts)

    def scored(q, k_all, v_all, mask):
        """The XLA form: float32 scores of the chunk's queries against the
        span and the chunk, written out, all heads at once up to
        `_SCORES_AT_ONCE`."""
        G = cfg.n_heads // cfg.kv_heads
        qh = q.reshape(B, Ts, cfg.kv_heads, G, cfg.head_dim)

        def attend(qh, k_all, v_all):
            # a division, as this program has always had: the configurations
            # that set no multiplier keep the program they had
            scores = jnp.einsum("btkgd,bskd->btkgs", qh,
                                k_all.astype(dt)) / (1.0 / cfg.softmax_scale)
            scores = jnp.where(mask[None, :, None, None, :],
                               scores.astype(jnp.float32), -1e30)
            w = jax.nn.softmax(scores, axis=-1).astype(dt)
            return jnp.einsum("btkgs,bskd->btkgd", w, v_all.astype(dt))

        if 4 * B * cfg.n_heads * Ts * k_all.shape[1] <= _SCORES_AT_ONCE:
            return attend(qh, k_all, v_all)
        # one KV head's group of query heads at a time
        out = jax.lax.map(
            lambda one: attend(*(t[:, :, None] for t in one))[:, :, 0],
            tuple(jnp.moveaxis(t, 2, 0) for t in (qh, k_all, v_all)))
        return jnp.moveaxis(out, 0, 2)

    x, kv = scan_layers(block, x, params, cfg, *per_layer,
                        close=lambda h, t: close_pass(h, None, t, params, cfg)[0],
                        ssm_per_layer=() if row_state is None else (
                            row_state["ssm"], row_state["conv"]))
    logits = lm_logits(x[0, length - 1], params, cfg)
    return logits.astype(jnp.float32), kv_tree(kv, cfg)


@functools.partial(jax.jit, donate_argnames=("state",), static_argnames=("dense_layers",))
def write_kv_pages(state, kv, pages, ring_ids=None, start=None, dense_layers: int = 0):
    """Write a bucketed [L, T, Hkv, Dh] KV into `pages` (T/page_size ids)
    WITHOUT touching the row bookkeeping — the chunked-prefill building
    block: chunks accumulate into the pool page by page, and the row only
    activates once the whole prompt is resident (activate_slot). With window
    layers their part goes into the row's ring `ring_ids` [ring] (the ids
    `activate_slot` will take), at the slots of the T/page_size logical
    pages from position `start` (a multiple of the page size) on. A recurrent
    part of `kv` (`ssm`, `conv`) is no page's and is left out: it rides from
    chunk to chunk outside the state and enters its slot at `activate_slot`.
    `dense_layers`: the model's leading dense layers, which are window layers
    (`_split_kinds`)."""
    kv = _split_row_state(_pages_part(kv))[0]
    if ring_ids is not None:
        kv, window_kv = _split_kinds(kv, state, dense_layers)
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
def activate_slot(state, slot, block_row, length, first_token, window_pages=None,
                  row_state=None):
    """Turn a fully-prefilled slot live for decode (the bookkeeping half
    of insert_sequence_paged, after write_kv_pages staged the KV); with
    window layers `window_pages` [ring] is the row's ring; with state-space
    layers `row_state` {ssm, conv} is what the last chunk returned, written
    into the slot here."""
    if window_pages is not None:
        state = _set_ring(state, slot, window_pages)
    state = _set_row_state(state, slot, row_state)
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
    kv = _pages_part(kv)
    if cfg.window:
        state, kv = _insert_ring(state, slot, kv, length, block_row, window_pages,
                                 cfg.n_dense_layers)
    kv, row_state = _split_row_state(kv)
    state = _set_row_state(_write_pages(state, kv, suffix_pages), slot, row_state)
    return _activate(state, slot, block_row, length, first_token)
