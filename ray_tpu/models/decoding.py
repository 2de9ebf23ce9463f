"""KV-cache decoding for the shared transformer core: the TPU inference path.

Design (JetStream-style, XLA-first — everything static-shape):
- one global decode state of `max_slots` rows; each row is an independent
  sequence with its own length counter (continuous batching = rows join and
  leave between jitted `decode_step` calls, no recompilation),
- `prefill` runs the prompt at a bucketed length and returns per-layer KV to
  be inserted into a free row (`insert_sequence`, donated buffers → in-place
  dynamic-update-slice in HBM),
- `decode_step` advances ALL rows one token with per-row masks; inactive rows
  are masked out, so the hot loop is one fixed-shape program on the MXU.

The reference delegates all of this to vLLM (paged attention, CUDA);
(reference: python/ray/llm/_internal/serve/engines/vllm/vllm_engine.py:114 —
capability parity target, not a design source). A contiguous [slots, max_len]
cache replaces vLLM's paged KV: XLA prefers static dense layouts, and HBM
capacity planning is done by slot count instead of page tables.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu import ops
from ray_tpu.models.transformer import (TransformerConfig, _dense_mlp, _mla_expand,
                                        _mla_project, _moe_mlp, _norm, scan_layers)


def _per_head_kv_only(cfg: TransformerConfig, what: str) -> None:
    """The paths not carried to the latent cache or to two kinds of layer."""
    if cfg.mla or cfg.n_dense_layers:
        raise NotImplementedError(
            f"{what} caches per-head K and V over one kind of layer; a model "
            "with latent attention (kv_lora_rank) or leading dense layers is "
            "served from the paged layout (models/decoding_paged.py)")


def init_decode_state(cfg: TransformerConfig, max_slots: int, max_len: int) -> dict:
    """Allocate the global decode state: per-layer KV + per-row bookkeeping."""
    _per_head_kv_only(cfg, "the slot layout")
    L, Hkv, Dh = cfg.n_layers, cfg.kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((L, max_slots, max_len, Hkv, Dh), cfg.dtype),
        "v": jnp.zeros((L, max_slots, max_len, Hkv, Dh), cfg.dtype),
        "length": jnp.zeros((max_slots,), jnp.int32),     # tokens in cache
        "last_token": jnp.zeros((max_slots,), jnp.int32),  # next input per row
        "active": jnp.zeros((max_slots,), jnp.bool_),
    }


def _rope(cfg):
    if cfg.pos == "rope":
        return ops.rope_frequencies(cfg.rope_dim, cfg.max_seq_len, theta=cfg.rope_theta)
    return None, None


def init_lora_bank(cfg: TransformerConfig, num_adapters: int,
                   rank: int) -> dict:
    """Device-resident multi-LoRA bank for batched per-slot adapters
    (reference capability: multi-LoRA serving —
    python/ray/llm/_internal/serve/utils/lora_serve_utils.py loads adapters
    onto vLLM's punica kernels; here the bank is plain stacked tensors the
    jitted forward gathers per row — S-LoRA-style, XLA does the batching).

    Adapter slot 0 is the NULL adapter and stays all-zero: a row with
    index 0 computes base + 0, bit-identical to the base model. Banks are
    LAYER-major ([L, N+1, ...]) so lax.scan consumes them directly.
    Targets q and v projections (the standard LoRA target set)."""
    _per_head_kv_only(cfg, "the LoRA bank")
    L, E = cfg.n_layers, cfg.d_model
    H, Hkv, Dh = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    N = num_adapters + 1
    return {
        "A_q": jnp.zeros((L, N, E, rank), cfg.dtype),
        "B_q": jnp.zeros((L, N, rank, H, Dh), cfg.dtype),
        "A_v": jnp.zeros((L, N, E, rank), cfg.dtype),
        "B_v": jnp.zeros((L, N, rank, Hkv, Dh), cfg.dtype),
        "scale": jnp.zeros((N,), jnp.float32),
    }


def _attn_qkv(x, p, cfg, lora_l=None, lora_idx=None, lora_scale=None):
    """QKV projections; when a LoRA layer-slice is given, adds the per-row
    low-rank q/v deltas. `lora_idx` is [B] (per decode row) or a scalar
    (single-sequence prefill); `lora_scale` the matching alpha/r gather."""
    dt = cfg.dtype
    q = jnp.einsum("bte,ehd->bthd", x, p["wq"].astype(dt))
    k = jnp.einsum("bte,ehd->bthd", x, p["wk"].astype(dt))
    v = jnp.einsum("bte,ehd->bthd", x, p["wv"].astype(dt))
    if lora_l is not None:
        aq, bq, av, bv = lora_l
        if lora_idx.ndim == 0:  # one sequence: scalar gather
            dq = jnp.einsum("bte,er->btr", x, aq[lora_idx].astype(dt))
            dq = jnp.einsum("btr,rhd->bthd", dq, bq[lora_idx].astype(dt))
            dv = jnp.einsum("bte,er->btr", x, av[lora_idx].astype(dt))
            dv = jnp.einsum("btr,rhd->bthd", dv, bv[lora_idx].astype(dt))
            s = lora_scale.astype(dt)
            q = q + dq * s
            v = v + dv * s
        else:  # per-row adapters: batched gather + matmul
            dq = jnp.einsum("bte,ber->btr", x, aq[lora_idx].astype(dt))
            dq = jnp.einsum("btr,brhd->bthd", dq, bq[lora_idx].astype(dt))
            dv = jnp.einsum("bte,ber->btr", x, av[lora_idx].astype(dt))
            dv = jnp.einsum("btr,brhd->bthd", dv, bv[lora_idx].astype(dt))
            s = lora_scale.astype(dt)[:, None, None, None]
            q = q + dq * s
            v = v + dv * s
    if cfg.bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    return q, k, v


def _mlp_block(normed, layer_p, cfg):
    if "router" in layer_p["mlp"]:
        delta, _aux = _moe_mlp(normed, layer_p["mlp"], cfg)
        return delta
    return _dense_mlp(normed, layer_p["mlp"], cfg)


def _mla_prefill_attn(normed, attn_p, cfg, cos, sin, positions=None,
                      prefix=None, mask=None):
    """Latent attention in expanded form over normed [1, T, E], after an
    optional cached `prefix` [Tp, latent_lanes] (then `mask` [T, Tp + T]
    says what each query sees; without one: causal). Returns (the block's
    output before the residual [1, T, E], the rows to cache [T, lanes])."""
    dt = cfg.dtype
    q, latent = _mla_project(normed, attn_p, cfg, cos, sin, positions)
    rows = latent if prefix is None else jnp.concatenate(
        [prefix[None].astype(dt), latent], axis=1)
    k, v = _mla_expand(rows, attn_p, cfg)
    if mask is None:
        out = ops.attention(q, k, v, causal=True, scale=cfg.qk_dim ** -0.5,
                            impl="reference")
    else:
        scores = jnp.einsum("bthd,bshd->bhts", q, k) / (cfg.qk_dim ** 0.5)
        scores = jnp.where(mask[None, None], scores.astype(jnp.float32), -1e30)
        out = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(scores, axis=-1).astype(dt), v)
    return jnp.einsum("bthd,hde->bte", out, attn_p["wo"].astype(dt)), latent[0]


@functools.partial(jax.jit, static_argnames=("cfg",))
def prefill(params, tokens, length, cfg: TransformerConfig,
            lora_bank=None, lora_idx=None):
    """Run one prompt [1, T] (T = bucket size, padded; true length `length`).

    Returns (logits_at_last [V], kv {k,v: [L, T, Hkv, Dh]}; with latent
    attention kv is {k: [L, T, latent_lanes]}, the rows the cache holds).
    With `lora_bank` + scalar `lora_idx`, applies that adapter's q/v
    deltas (init_lora_bank; idx 0 = null adapter = exact base model).
    """
    dt = cfg.dtype
    B, T = tokens.shape
    x = params["embed"].astype(dt)[tokens]
    if cfg.pos == "learned":
        x = x + params["pos_embed"][:T].astype(dt)
    cos, sin = _rope(cfg)
    lscale = None if lora_bank is None else lora_bank["scale"][lora_idx]

    def block(h, layer_in):
        if lora_bank is None:
            layer_p, lora_l = layer_in, None
        else:
            layer_p, aq, bq, av, bv = layer_in
            lora_l = (aq, bq, av, bv)
        normed = _norm(h, layer_p["norm1"], cfg)
        if cfg.mla:
            out, rows = _mla_prefill_attn(normed, layer_p["attn"], cfg, cos, sin)
            h = h + out
            h = h + _mlp_block(_norm(h, layer_p["norm2"], cfg), layer_p, cfg)
            return h, (rows,)
        q, k, v = _attn_qkv(normed, layer_p["attn"], cfg, lora_l, lora_idx,
                            lscale)
        if cfg.pos == "rope":
            q = ops.apply_rope(q, cos, sin)
            k = ops.apply_rope(k, cos, sin)
        out = ops.attention(q, k, v, causal=True)
        out = jnp.einsum("bthd,hde->bte", out, layer_p["attn"]["wo"].astype(dt))
        if cfg.bias:
            out = out + layer_p["attn"]["bo"].astype(dt)
        h = h + out
        h = h + _mlp_block(_norm(h, layer_p["norm2"], cfg), layer_p, cfg)
        return h, (k[0], v[0])

    if lora_bank is None:
        x, kv = scan_layers(block, x, params, cfg)
    else:
        x, kv = jax.lax.scan(block, x, (
            params["layers"], lora_bank["A_q"], lora_bank["B_q"],
            lora_bank["A_v"], lora_bank["B_v"]))
    x = _norm(x, params["final_norm"], cfg)
    last = x[0, length - 1]
    if cfg.tie_embeddings:
        logits = last @ params["embed"].astype(dt).T
    else:
        logits = last @ params["lm_head"].astype(dt)
    return logits.astype(jnp.float32), dict(zip("kv", kv))


@functools.partial(jax.jit, static_argnames=("cfg",))
def prefill_batch(params, tokens, lengths, cfg: TransformerConfig):
    """Batched prompt prefill: [B, T] (one shared bucket, padded; true
    per-row lengths in `lengths` [B]).

    Returns (logits_at_last [B, V], kv {k, v: [L, B, T, Hkv, Dh]}).

    The PD prefill tier's admission batching: several queued prompts
    share ONE forward instead of B sequential [1, T] calls — the
    dedicated tier can coalesce because it never interleaves with decode
    steps (llm/pd.py PrefillCoalescer). Causality keeps rows independent:
    positions past a row's length only produce KV that the consumer
    masks by length, exactly as in the single-prompt path."""
    _per_head_kv_only(cfg, "prefill_batch (the PD prefill tier)")
    dt = cfg.dtype
    B, T = tokens.shape
    x = params["embed"].astype(dt)[tokens]
    if cfg.pos == "learned":
        x = x + params["pos_embed"][:T].astype(dt)
    cos, sin = _rope(cfg)

    def block(h, layer_p):
        normed = _norm(h, layer_p["norm1"], cfg)
        q, k, v = _attn_qkv(normed, layer_p["attn"], cfg)
        if cfg.pos == "rope":
            q = ops.apply_rope(q, cos, sin)
            k = ops.apply_rope(k, cos, sin)
        out = ops.attention(q, k, v, causal=True)
        out = jnp.einsum("bthd,hde->bte", out, layer_p["attn"]["wo"].astype(dt))
        if cfg.bias:
            out = out + layer_p["attn"]["bo"].astype(dt)
        h = h + out
        h = h + _mlp_block(_norm(h, layer_p["norm2"], cfg), layer_p, cfg)
        return h, (k, v)

    x, kv = jax.lax.scan(block, x, params["layers"])
    x = _norm(x, params["final_norm"], cfg)
    last = jnp.take_along_axis(
        x, (lengths - 1)[:, None, None], axis=1)[:, 0]        # [B, E]
    if cfg.tie_embeddings:
        logits = last @ params["embed"].astype(dt).T
    else:
        logits = last @ params["lm_head"].astype(dt)
    return logits.astype(jnp.float32), {"k": kv[0], "v": kv[1]}


@functools.partial(jax.jit, donate_argnames=("state",), static_argnames=("cfg",))
def insert_sequence(state, slot, kv, length, first_token, cfg: TransformerConfig):
    """Graft a prefilled sequence into decode row `slot` (in place: donated)."""
    T = kv["k"].shape[1]
    pad = state["k"].shape[2] - T
    k_new = jnp.pad(kv["k"], ((0, 0), (0, pad), (0, 0), (0, 0)))[:, None]
    v_new = jnp.pad(kv["v"], ((0, 0), (0, pad), (0, 0), (0, 0)))[:, None]
    state = dict(state)
    state["k"] = jax.lax.dynamic_update_slice_in_dim(state["k"], k_new.astype(state["k"].dtype), slot, axis=1)
    state["v"] = jax.lax.dynamic_update_slice_in_dim(state["v"], v_new.astype(state["v"].dtype), slot, axis=1)
    state["length"] = state["length"].at[slot].set(length)
    state["last_token"] = state["last_token"].at[slot].set(first_token)
    state["active"] = state["active"].at[slot].set(True)
    return state


@functools.partial(jax.jit, donate_argnames=("state",), static_argnames=("cfg",))
def decode_step(params, state, cfg: TransformerConfig,
                lora_bank=None, slot_lora=None):
    """Advance every active row one token. Returns (state, logits [slots, V]).
    With `lora_bank` + `slot_lora` [B], each row adds its own adapter's
    q/v deltas in the SAME batched step (idx 0 = null = base model)."""
    dt = cfg.dtype
    S = state["k"].shape[2]
    B = state["length"].shape[0]
    tokens = state["last_token"][:, None]                      # [B, 1]
    pos = state["length"]                                      # [B]
    x = params["embed"].astype(dt)[tokens]
    if cfg.pos == "learned":
        x = x + params["pos_embed"].astype(dt)[pos][:, None]
    cos, sin = _rope(cfg)
    lscale = None if lora_bank is None else lora_bank["scale"][slot_lora]

    def block(carry, layer_in):
        h, = carry
        if lora_bank is None:
            layer_p, k_cache, v_cache = layer_in               # caches [B, S, Hkv, Dh]
            lora_l = None
        else:
            layer_p, k_cache, v_cache, aq, bq, av, bv = layer_in
            lora_l = (aq, bq, av, bv)
        normed = _norm(h, layer_p["norm1"], cfg)
        q, k, v = _attn_qkv(normed, layer_p["attn"], cfg, lora_l, slot_lora,
                            lscale)                            # [B, 1, H, Dh]
        if cfg.pos == "rope":
            q = ops.apply_rope(q, cos, sin, positions=pos[:, None])
            k = ops.apply_rope(k, cos, sin, positions=pos[:, None])
        # write this step's K/V at each row's position
        onehot = jax.nn.one_hot(pos, S, dtype=dt)              # [B, S]
        k_cache = k_cache * (1 - onehot)[..., None, None] + onehot[..., None, None] * k[:, 0][:, None]
        v_cache = v_cache * (1 - onehot)[..., None, None] + onehot[..., None, None] * v[:, 0][:, None]
        # grouped-query attention against the cache
        G = cfg.n_heads // cfg.kv_heads
        qh = q[:, 0].reshape(B, cfg.kv_heads, G, cfg.head_dim)
        scores = jnp.einsum("bkgd,bskd->bkgs", qh, k_cache.astype(dt)) / (cfg.head_dim ** 0.5)
        mask = jnp.arange(S)[None, :] <= pos[:, None]          # [B, S]
        scores = jnp.where(mask[:, None, None, :], scores.astype(jnp.float32), -1e30)
        w = jax.nn.softmax(scores, axis=-1).astype(dt)
        out = jnp.einsum("bkgs,bskd->bkgd", w, v_cache.astype(dt))
        out = out.reshape(B, 1, cfg.n_heads, cfg.head_dim)
        out = jnp.einsum("bthd,hde->bte", out, layer_p["attn"]["wo"].astype(dt))
        if cfg.bias:
            out = out + layer_p["attn"]["bo"].astype(dt)
        h = h + out
        h = h + _mlp_block(_norm(h, layer_p["norm2"], cfg), layer_p, cfg)
        return (h,), (k_cache, v_cache)

    xs = ((params["layers"], state["k"], state["v"]) if lora_bank is None
          else (params["layers"], state["k"], state["v"],
                lora_bank["A_q"], lora_bank["B_q"],
                lora_bank["A_v"], lora_bank["B_v"]))
    (x,), (k_new, v_new) = jax.lax.scan(block, (x,), xs)
    x = _norm(x, params["final_norm"], cfg)
    if cfg.tie_embeddings:
        logits = x[:, 0] @ params["embed"].astype(dt).T
    else:
        logits = x[:, 0] @ params["lm_head"].astype(dt)
    state = dict(state)
    state["k"], state["v"] = k_new, v_new
    state["length"] = jnp.where(state["active"], state["length"] + 1, state["length"])
    return state, logits.astype(jnp.float32)


@functools.partial(jax.jit, donate_argnames=("state",),
                   static_argnames=("cfg", "K"))
def verify_step(params, state, draft, cfg: TransformerConfig, K: int):
    """Speculative verification: advance every active row K tokens at once.

    Inputs per row are [last_token, draft_0 .. draft_{K-2}] at positions
    len .. len+K-1; returns (state, logits [slots, K, V]) where logits[:, j]
    is the next-token distribution AFTER input j. KV is written for all K
    inputs; `length`/`last_token` are NOT advanced — the host decides how
    many drafts were accepted and calls commit_accepted. Rejected inputs'
    KV rows sit beyond the committed length, where the attention mask
    already ignores them, so no rollback is needed (the memory-bound
    decode step has idle MXU headroom — verifying K tokens costs barely
    more than one, which is the whole speculative-decoding bet).

    (reference capability: vLLM speculative decoding / prompt-lookup;
    rebuilt as one fixed-shape XLA program like decode_step.)
    """
    dt = cfg.dtype
    S = state["k"].shape[2]
    B = state["length"].shape[0]
    tokens = jnp.concatenate([state["last_token"][:, None], draft], axis=1)
    pos = state["length"][:, None] + jnp.arange(K)[None, :]    # [B, K]
    x = params["embed"].astype(dt)[tokens]
    if cfg.pos == "learned":
        x = x + params["pos_embed"].astype(dt)[pos]
    cos, sin = _rope(cfg)

    def block(carry, layer_in):
        h, = carry
        layer_p, k_cache, v_cache = layer_in                   # [B, S, Hkv, Dh]
        normed = _norm(h, layer_p["norm1"], cfg)
        q, k, v = _attn_qkv(normed, layer_p["attn"], cfg)      # [B, K, H, Dh]
        if cfg.pos == "rope":
            q = ops.apply_rope(q, cos, sin, positions=pos)
            k = ops.apply_rope(k, cos, sin, positions=pos)
        # scatter the K new K/V rows (positions are distinct per row)
        oh = jax.nn.one_hot(pos, S, dtype=dt)                  # [B, K, S]
        any_mask = oh.sum(axis=1)                              # [B, S]
        k_cache = (k_cache * (1 - any_mask)[..., None, None]
                   + jnp.einsum("bks,bkhd->bshd", oh, k))
        v_cache = (v_cache * (1 - any_mask)[..., None, None]
                   + jnp.einsum("bks,bkhd->bshd", oh, v))
        G = cfg.n_heads // cfg.kv_heads
        qh = q.reshape(B, K, cfg.kv_heads, G, cfg.head_dim)
        scores = jnp.einsum("bkhgd,bshd->bhgks", qh,
                            k_cache.astype(dt)) / (cfg.head_dim ** 0.5)
        # causal within the window + full view of the committed cache
        mask = jnp.arange(S)[None, None, :] <= pos[:, :, None]  # [B, K, S]
        scores = jnp.where(mask[:, None, None, :, :],
                           scores.astype(jnp.float32), -1e30)
        w = jax.nn.softmax(scores, axis=-1).astype(dt)
        out = jnp.einsum("bhgks,bshd->bkhgd", w, v_cache.astype(dt))
        out = out.reshape(B, K, cfg.n_heads, cfg.head_dim)
        out = jnp.einsum("bthd,hde->bte", out, layer_p["attn"]["wo"].astype(dt))
        if cfg.bias:
            out = out + layer_p["attn"]["bo"].astype(dt)
        h = h + out
        h = h + _mlp_block(_norm(h, layer_p["norm2"], cfg), layer_p, cfg)
        return (h,), (k_cache, v_cache)

    (x,), (k_new, v_new) = jax.lax.scan(
        block, (x,), (params["layers"], state["k"], state["v"]))
    x = _norm(x, params["final_norm"], cfg)
    if cfg.tie_embeddings:
        logits = x @ params["embed"].astype(dt).T
    else:
        logits = x @ params["lm_head"].astype(dt)
    state = dict(state)
    state["k"], state["v"] = k_new, v_new
    return state, logits.astype(jnp.float32)


@functools.partial(jax.jit, donate_argnames=("state",))
def commit_accepted(state, new_last, counts):
    """Advance each active row by its accepted-token count (1 + accepted
    drafts) and set the new last (unverified) token."""
    state = dict(state)
    act = state["active"]
    state["length"] = jnp.where(act, state["length"] + counts,
                                state["length"])
    state["last_token"] = jnp.where(act, new_last, state["last_token"])
    return state


@functools.partial(jax.jit, donate_argnames=("state",))
def commit_tokens(state, next_tokens):
    """Record sampled tokens as the next decode inputs (active rows only)."""
    state = dict(state)
    state["last_token"] = jnp.where(state["active"], next_tokens, state["last_token"])
    return state


@functools.partial(jax.jit, donate_argnames=("state",))
def release_slot(state, slot):
    state = dict(state)
    state["active"] = state["active"].at[slot].set(False)
    state["length"] = state["length"].at[slot].set(0)
    return state


@jax.jit
def sample_per_row(logits, key, temperatures, top_ks):
    """Row-wise temperature + top-k sampling for the decode hot loop.
    logits [B, V], temperatures [B] (0 → greedy), top_ks [B] int32 (0 → off)."""
    V = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temperatures, 1e-6)[:, None]
    # per-row k-th largest as the cutoff (k=0 → cutoff -inf, i.e. no cut)
    sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    idx = jnp.clip(top_ks - 1, 0, V - 1)
    kth = jnp.take_along_axis(sorted_desc, idx[:, None], axis=-1)
    kth = jnp.where(top_ks[:, None] > 0, kth, -jnp.inf)
    scaled = jnp.where(scaled < kth, -1e30, scaled)
    sampled = jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)
    return jnp.where(temperatures <= 0.0, greedy, sampled)


@functools.partial(jax.jit, static_argnames=("top_k",))
def sample(logits, key, temperature: float, top_k: int = 0):
    """Greedy when temperature == 0, else (top-k) categorical. [B, V] → [B]."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    t = jnp.maximum(temperature, 1e-6)
    scaled = logits / t
    if top_k and top_k > 0:
        kth = jnp.sort(scaled, axis=-1)[:, -top_k][:, None]
        scaled = jnp.where(scaled < kth, -1e30, scaled)
    sampled = jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)
    return jnp.where(temperature <= 0.0, greedy, sampled)
