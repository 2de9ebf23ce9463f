"""Paged KV-cache decoding: block-table attention with static shapes.

How the KV cache is laid out, and which code attends over it, is decided
here and nowhere else. HBM is carved into a shared **page pool**; each slot
owns just the pages its sequence needs, tracked in a block table, so the
same HBM serves many more concurrent sequences at typical length
distributions than a `max_len` reservation per slot would.

All shapes stay static (XLA-first, like everything here): the pool is
[L, num_pages, page, Hkv, Dh]; per-step writes are scatters at
(page_id, offset) and attention is one ragged launch over the rows' block
tables. Page allocation/free is host-side bookkeeping in the engine
(a free list), mirroring how vLLM's scheduler owns its block tables.

The decode step carries the pools through the layer scan (viewed flat,
[L*num_pages, page, Hkv, Dh], layer l's pages at l*num_pages + id) and
scatters each layer's rows into the carry in place, because pools handed to
the scan as inputs and stacked as its outputs are sliced, copied and
rewritten whole every step.

A model with latent attention (cfg.kv_lora_rank, MLA) has ONE pool, `kp`
[L, num_pages, page, latent_lanes], and no `vp`: a token's row is (c |
k_rope | padding to whole lanes), nothing per head. Prefill attends in
expanded form (per-head K and V from the rows), the decode step in absorbed
form over the rows as they lie in the pool: the same mathematics.

(reference capability: vLLM paged attention behind
llm/_internal/serve/engines/vllm/vllm_engine.py:114; design here is
TPU-native — static gathers and a Pallas kernel, no custom CUDA.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_tpu.models.decoding import _attn_qkv, _mla_prefill_attn, _mlp_block, _rope
from ray_tpu.models.transformer import (TransformerConfig, _mla_absorb_out, _mla_absorb_q,
                                        _mla_project, _norm, scan_layers)
from ray_tpu import ops


def init_paged_state(cfg: TransformerConfig, max_slots: int, max_len: int,
                     num_pages: int, page_size: int) -> dict:
    """Page pool + block tables. `num_pages * page_size` is the total token
    capacity shared by all slots (oversubscribable vs max_slots*max_len)."""
    L, Hkv, Dh = cfg.n_layers, cfg.kv_heads, cfg.head_dim
    max_pages_per_seq = (max_len + page_size - 1) // page_size
    if cfg.mla:
        pools = {"kp": jnp.zeros((L, num_pages, page_size, cfg.latent_lanes), cfg.dtype)}
    else:
        pools = {"kp": jnp.zeros((L, num_pages, page_size, Hkv, Dh), cfg.dtype),
                 "vp": jnp.zeros((L, num_pages, page_size, Hkv, Dh), cfg.dtype)}
    return {
        **pools,
        # page ids per slot; unused entries point at page 0 (masked anyway)
        "block": jnp.zeros((max_slots, max_pages_per_seq), jnp.int32),
        "length": jnp.zeros((max_slots,), jnp.int32),
        "last_token": jnp.zeros((max_slots,), jnp.int32),
        "active": jnp.zeros((max_slots,), jnp.bool_),
    }


@functools.partial(jax.jit, donate_argnames=("state",), static_argnames=("cfg",))
def insert_sequence_paged(state, slot, kv, length, first_token, pages,
                          cfg: TransformerConfig):
    """Write a prefilled [L, T, Hkv, Dh] KV into the first T/page_size of
    this slot's `pages` (int32 [max_pages_per_seq], padded with 0 — the
    engine grants ALL pages the sequence will ever need up front, so no
    mid-flight allocation) and activate the row."""
    state = _write_pages(state, kv, pages)
    state["block"] = jax.lax.dynamic_update_slice_in_dim(
        state["block"], pages[None], slot, axis=0)
    state["length"] = state["length"].at[slot].set(length)
    state["last_token"] = state["last_token"].at[slot].set(first_token)
    state["active"] = state["active"].at[slot].set(True)
    return state


def _write_pages(state, kv, pages) -> dict:
    """A copy of `state` with a bucketed kv ({k, v: [L, T, Hkv, Dh]}, or the
    latent {k: [L, T, lanes]}) written into the first T/page_size of `pages`."""
    P = state["kp"].shape[2]
    state = dict(state)
    for name, rows in kv.items():
        pool = state[name + "p"]
        n = rows.shape[1] // P  # static: T is a bucket
        state[name + "p"] = pool.at[:, pages[:n]].set(
            rows.reshape(rows.shape[0], n, P, *rows.shape[2:]).astype(pool.dtype))
    return state


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
    step (index 0 = the null adapter = the base model)."""
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
    cos, sin = _rope(cfg)
    G = cfg.n_heads // cfg.kv_heads
    lscale = None if lora_bank is None else lora_bank["scale"][slot_lora]

    def attend(qh, kp, vp, base):
        return ragged_decode_attention(
            qh, kp, vp, base + tbl, pos, scale=cfg.qk_dim ** -0.5,
            impl="kernel" if kernel else "reference")

    def block(carry, layer_in):
        h, kp, vp = carry                        # pools [L*num_pages, P, Hkv, Dh]
        layer_p, base, *lora_l = layer_in        # base: this layer's first page
        normed = _norm(h, layer_p["norm1"], cfg)
        if cfg.mla:
            ap = layer_p["attn"]
            q, row = _mla_project(normed, ap, cfg, cos, sin, pos[:, None])
            kp = kp.at[base + page_ids, offsets].set(row[:, 0].astype(kp.dtype))
            o_lat = attend(_mla_absorb_q(q[:, 0], ap, cfg)[:, None], kp, None, base)
            out = _mla_absorb_out(o_lat[:, 0].astype(dt), ap, cfg)
            h = h + jnp.einsum("bhd,hde->be", out, ap["wo"].astype(dt))[:, None]
            h = h + _mlp_block(_norm(h, layer_p["norm2"], cfg), layer_p, cfg)
            return (h, kp, vp), None
        q, k, v = _attn_qkv(normed, layer_p["attn"], cfg, lora_l, slot_lora,
                            lscale)                            # [B, 1, H, Dh]
        if cfg.pos == "rope":
            q = ops.apply_rope(q, cos, sin, positions=pos[:, None])
            k = ops.apply_rope(k, cos, sin, positions=pos[:, None])
        # scatter this step's K/V at (page, offset) per row
        kp = kp.at[base + page_ids, offsets].set(k[:, 0].astype(kp.dtype))
        vp = vp.at[base + page_ids, offsets].set(v[:, 0].astype(vp.dtype))
        qh = q[:, 0].reshape(B, cfg.kv_heads, G, cfg.head_dim)
        out = attend(qh, kp, vp, base)
        out = out.reshape(B, 1, cfg.n_heads, cfg.head_dim).astype(dt)
        out = jnp.einsum("bthd,hde->bte", out, layer_p["attn"]["wo"].astype(dt))
        if cfg.bias:
            out = out + layer_p["attn"]["bo"].astype(dt)
        h = h + out
        h = h + _mlp_block(_norm(h, layer_p["norm2"], cfg), layer_p, cfg)
        return (h, kp, vp), None

    (x, kp, vp), _ = scan_layers(
        block, (x, state["kp"].reshape(flat), vp0), params, cfg,
        jnp.arange(L, dtype=jnp.int32) * num_pages,
        *(() if lora_bank is None else
          (lora_bank[k] for k in ("A_q", "B_q", "A_v", "B_v"))))
    x = _norm(x, params["final_norm"], cfg)
    if cfg.tie_embeddings:
        logits = x[:, 0] @ params["embed"].astype(dt).T
    else:
        logits = x[:, 0] @ params["lm_head"].astype(dt)
    state = dict(state)
    state["kp"] = kp.reshape(state["kp"].shape)
    if vp is not None:
        state["vp"] = vp.reshape(state["vp"].shape)
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


@functools.partial(jax.jit, static_argnames=("cfg",))
def prefill_with_prefix(params, tokens, prefix_k, prefix_v, prefix_len,
                        length, cfg: TransformerConfig):
    """Continuation prefill: run ONLY the suffix tokens [1, Ts] (padded
    bucket; true count `length`) attending over a cached prefix KV
    [L, Tp, Hkv, Dh] (valid first `prefix_len` positions — cached K is
    already roped at its absolute positions) plus the causal suffix.

    Returns (logits at the last suffix token [V],
             suffix kv {k, v: [L, Ts, Hkv, Dh]}).
    Compilation count is bounded by #prefix_buckets × #suffix_buckets.
    With latent attention `prefix_k` is the cached rows [L, Tp, lanes],
    `prefix_v` None: every layer expands them to per-head K and V.
    """
    dt = cfg.dtype
    B, Ts = tokens.shape
    Tp = prefix_k.shape[1]
    x = params["embed"].astype(dt)[tokens]
    pos_suffix = prefix_len + jnp.arange(Ts)                     # [Ts]
    if cfg.pos == "learned":
        x = x + params["pos_embed"].astype(dt)[pos_suffix][None]
    cos, sin = _rope(cfg)

    # [Ts, Tp + Ts]: every suffix query sees the real prefix positions and
    # its causal suffix slice
    prefix_mask = jnp.broadcast_to(
        jnp.arange(Tp)[None, :] < prefix_len, (Ts, Tp))
    causal = jnp.arange(Ts)[:, None] >= jnp.arange(Ts)[None, :]
    mask = jnp.concatenate([prefix_mask, causal], axis=1)

    def block(h, layer_in):
        layer_p, pk, pv = layer_in                    # [Tp, Hkv, Dh] each
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
        scores = jnp.einsum("btkgd,bskd->btkgs", qh,
                            k_all.astype(dt)) / (cfg.head_dim ** 0.5)
        scores = jnp.where(mask[None, :, None, None, :],
                           scores.astype(jnp.float32), -1e30)
        w = jax.nn.softmax(scores, axis=-1).astype(dt)
        out = jnp.einsum("btkgs,bskd->btkgd", w, v_all.astype(dt))
        out = out.reshape(B, Ts, cfg.n_heads, cfg.head_dim)
        out = jnp.einsum("bthd,hde->bte", out, layer_p["attn"]["wo"].astype(dt))
        if cfg.bias:
            out = out + layer_p["attn"]["bo"].astype(dt)
        h = h + out
        h = h + _mlp_block(_norm(h, layer_p["norm2"], cfg), layer_p, cfg)
        return h, (k[0], v[0])

    x, kv = scan_layers(block, x, params, cfg, prefix_k, prefix_v)
    x = _norm(x, params["final_norm"], cfg)
    last = x[0, length - 1]
    if cfg.tie_embeddings:
        logits = last @ params["embed"].astype(dt).T
    else:
        logits = last @ params["lm_head"].astype(dt)
    return logits.astype(jnp.float32), dict(zip("kv", kv))


@functools.partial(jax.jit, donate_argnames=("state",))
def write_kv_pages(state, kv, pages):
    """Write a bucketed [L, T, Hkv, Dh] KV into `pages` (T/page_size ids)
    WITHOUT touching the row bookkeeping — the chunked-prefill building
    block: chunks accumulate into the pool page by page, and the row only
    activates once the whole prompt is resident (activate_slot)."""
    return _write_pages(state, kv, pages)


@functools.partial(jax.jit, donate_argnames=("state",))
def activate_slot(state, slot, block_row, length, first_token):
    """Turn a fully-prefilled slot live for decode (the bookkeeping half
    of insert_sequence_paged, after write_kv_pages staged the KV)."""
    state = dict(state)
    state["block"] = jax.lax.dynamic_update_slice_in_dim(
        state["block"], block_row[None], slot, axis=0)
    state["length"] = state["length"].at[slot].set(length)
    state["last_token"] = state["last_token"].at[slot].set(first_token)
    state["active"] = state["active"].at[slot].set(True)
    return state


@functools.partial(jax.jit, donate_argnames=("state",), static_argnames=("cfg",))
def insert_sequence_paged_prefix(state, slot, kv, suffix_pages, block_row,
                                 length, first_token, cfg: TransformerConfig):
    """Like insert_sequence_paged, but only the SUFFIX KV is written (the
    prefix already lives in shared cache pages): `suffix_pages` [ns] are
    the pages receiving the suffix bucket, `block_row`
    [max_pages_per_seq] is the full table (shared prefix ids + private
    ids + 0-padding)."""
    state = _write_pages(state, kv, suffix_pages)
    state["block"] = jax.lax.dynamic_update_slice_in_dim(
        state["block"], block_row[None], slot, axis=0)
    state["length"] = state["length"].at[slot].set(length)
    state["last_token"] = state["last_token"].at[slot].set(first_token)
    state["active"] = state["active"].at[slot].set(True)
    return state
