"""Prefill, sampling and the LoRA bank for the shared transformer core: the
parts of the TPU inference path that do not touch the KV cache's layout.

Everything is static-shape (XLA-first):
- `prefill` runs one prompt at a bucketed length and returns per-layer KV
  (`prefill_batch`: several prompts of one bucket, for the PD prefill tier);
  the engine writes it into pages of the cache (models/decoding_paged.py,
  which owns the layout, the decode step and the continuation prefill),
- `sample` / `sample_per_row` sample on the device and `commit_tokens`
  records a step's tokens as the next inputs, so only token ids cross to the
  host each step,
- `init_lora_bank` holds the adapters that `prefill` and the decode step
  gather per row (`_attn_qkv`).

The reference delegates all of this to vLLM (paged attention, CUDA);
(reference: python/ray/llm/_internal/serve/engines/vllm/vllm_engine.py:114 —
capability parity target, not a design source).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_tpu import ops
from ray_tpu.models.transformer import (TransformerConfig, _dense_mlp, _mla_expand,
                                        _mla_project, _moe_mlp, _norm, _residual,
                                        attn_gated, close_pass, embed_tokens, lm_logits,
                                        qk_normed, recurrent_mixer, rope_by_kind,
                                        scan_layers)


def _per_head_kv_only(cfg: TransformerConfig, what: str) -> None:
    """The paths not carried to the latent cache, to two kinds of layer, to
    a looped stack or to a recurrent state."""
    if (cfg.mla or cfg.n_dense_layers or cfg.window or cfg.n_passes > 1
            or cfg.sandwich_norms or cfg.ssm or cfg.kv_packed or cfg.attn_gate
            or cfg.qk_norm):
        raise NotImplementedError(
            f"{what} is built for per-head K and V over one kind of layer, "
            "each applied once; a model with latent attention (kv_lora_rank), "
            "leading dense layers, window layers, a looped stack (n_passes), "
            "sandwich norms, state-space layers (a recurrent state a row), "
            "packed KV rows (kv_packed), an attention gate or q/k norms is "
            "served without it")


def _rope(cfg):
    """(cos, sin) of a stack of one kind of layer (None, None without rope)."""
    return rope_by_kind(cfg)[False]


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
    if lora_l:
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
    q, k = qk_normed(q, k, p, cfg)
    return q, k, v


def counts_experts(cfg: TransformerConfig) -> bool:
    """Whether the model's expert layers hold a share of the experts: its
    blocks then give `ops.share_counts` a layer beside what they gave."""
    return cfg.moe is not None and cfg.moe.share


def _mlp_block(normed, layer_p, cfg):
    return _mlp_counted(normed, layer_p, cfg)[0]


def _mlp_counted(normed, layer_p, cfg):
    """The block's MLP -> (its output, a tuple that is empty, or for a model
    that `counts_experts` holds the layer's int32 [2] `ops.share_counts`:
    zeros for a layer without a router)."""
    if "router" in layer_p["mlp"]:
        delta, _aux, *counts = _moe_mlp(normed, layer_p["mlp"], cfg)
        return delta, tuple(counts)
    counts = (jnp.zeros((2,), jnp.int32),) if counts_experts(cfg) else ()
    return _dense_mlp(normed, layer_p["mlp"], cfg), counts


def _close_block(h, mixed, layer_p, cfg):
    """A block's second half: the mixer's (attention's, or the state-space
    mixer's) output `mixed` joins the residual, then the MLP's does. Returns
    (h, `_mlp_counted`'s tuple of counts)."""
    h = _residual(h, mixed, layer_p, "post_attn_norm", cfg)
    delta, counts = _mlp_counted(_norm(h, layer_p["norm2"], cfg), layer_p, cfg)
    return _residual(h, delta, layer_p, "post_mlp_norm", cfg), counts


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
        out = ops.attention(q, k, v, causal=True, scale=cfg.softmax_scale,
                            impl="reference")
    else:
        # (a division, as this program has always had: see prefill_with_prefix)
        scores = jnp.einsum("bthd,bshd->bhts", q, k) / (1.0 / cfg.softmax_scale)
        scores = jnp.where(mask[None, None], scores.astype(jnp.float32), -1e30)
        out = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(scores, axis=-1).astype(dt), v)
    return jnp.einsum("bthd,hde->bte", out, attn_p["wo"].astype(dt)), latent[0]


@functools.partial(jax.jit, static_argnames=("cfg",))
def prefill(params, tokens, length, cfg: TransformerConfig,
            lora_bank=None, lora_idx=None):
    """Run one prompt [1, T] (T = bucket size, padded; true length `length`).

    Returns (logits_at_last [V], kv {k,v: [L, T, Hkv, Dh]}; with latent
    attention kv is {k: [L, T, latent_lanes]}, the rows the cache holds).
    A looped stack (cfg.n_passes) returns kv by plane, [n_passes * L, T, ...]:
    every layer application's own keys and values, pass-major.
    With window layers kv still holds every layer's T positions: which of
    them a window layer keeps is the cache's business (decoding_paged.py).
    With state-space layers kv holds k, v [L_attn, T, Hkv, Dh] of the
    attention layers and, of the state-space layers, `ssm` [L_ssm, H, P, N]
    (float32; the gated delta rule's [L_ssm, H, D, D]) and `conv` [L_ssm,
    d_conv - 1, conv_dim]: the recurrent state
    and the convolution's tail AFTER position length - 1 (the bucket's
    padding does not advance them).
    With `lora_bank` + scalar `lora_idx`, applies that adapter's q/v
    deltas (init_lora_bank; idx 0 = null adapter = exact base model).
    """
    dt = cfg.dtype
    B, T = tokens.shape
    x = embed_tokens(params, tokens, cfg)
    if cfg.pos == "learned":
        x = x + params["pos_embed"][:T].astype(dt)
    rope = rope_by_kind(cfg)
    lscale = None if lora_bank is None else lora_bank["scale"][lora_idx]

    def block(h, layer_in, window=False, ssm=False):
        cos, sin = rope[window]
        if lora_bank is None:
            layer_p, lora_l = layer_in, None
        else:
            layer_p, aq, bq, av, bv = layer_in
            lora_l = (aq, bq, av, bv)
        normed = _norm(h, layer_p["norm1"], cfg)
        if ssm:
            mixer = recurrent_mixer(cfg)
            out, state, tail = mixer(normed[0], layer_p["mixer"], cfg, length)
            h, counts = _close_block(h, out[None], layer_p, cfg)
            return h, (state, tail, *counts)
        if cfg.mla:
            out, rows = _mla_prefill_attn(normed, layer_p["attn"], cfg, cos, sin)
            h = h + out
            h = h + _mlp_block(_norm(h, layer_p["norm2"], cfg), layer_p, cfg)
            return h, (rows,)
        q, k, v = _attn_qkv(normed, layer_p["attn"], cfg, lora_l, lora_idx,
                            lscale)
        if cos is not None:  # this kind of layer has the rope
            q = ops.apply_rope(q, cos, sin)
            k = ops.apply_rope(k, cos, sin)
        out = ops.attention(q, k, v, causal=True, scale=cfg.softmax_scale,
                            window=cfg.window if window else None)
        out = jnp.einsum("bthd,hde->bte", attn_gated(out, normed, layer_p["attn"], cfg),
                         layer_p["attn"]["wo"].astype(dt))
        if cfg.bias:
            out = out + layer_p["attn"]["bo"].astype(dt)
        h, counts = _close_block(h, out, layer_p, cfg)
        return h, (k[0], v[0], *counts)

    def close(h, t):  # the exit gate decides nothing about a prompt
        return close_pass(h, None, t, params, cfg)[0]

    if lora_bank is None:
        x, kv = scan_layers(block, x, params, cfg, close=close)
    else:
        x, kv = jax.lax.scan(block, x, (
            params["layers"], lora_bank["A_q"], lora_bank["B_q"],
            lora_bank["A_v"], lora_bank["B_v"]))
        x = close(x, 0)
    logits = lm_logits(x[0, length - 1], params, cfg)
    return logits.astype(jnp.float32), kv_tree(kv, cfg)


def kv_tree(kv, cfg: TransformerConfig) -> dict:
    """What `scan_layers` stacked of a prefill's blocks, by name: {k, v} (a
    latent cache: {k}); with state-space layers also {ssm, conv}; for a model
    that `counts_experts` also {expert_counts: int32 [2]}, the layers'
    `ops.share_counts` summed (no page's: the writers of pages leave it out)."""
    if cfg.ssm is not None:  # by kind: the attention layers', the recurrent layers'
        (k, v, *counts), (state, tail, *more) = kv
        out = {"k": k, "v": v, "ssm": state, "conv": tail}
        if counts:
            out["expert_counts"] = counts[0].sum(axis=0) + more[0].sum(axis=0)
        return out
    if counts_experts(cfg):
        *kv, counts = kv
        return {**dict(zip("kv", kv)), "expert_counts": counts.sum(axis=0)}
    return dict(zip("kv", kv))


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
    x = embed_tokens(params, tokens, cfg)
    if cfg.pos == "learned":
        x = x + params["pos_embed"][:T].astype(dt)
    cos, sin = _rope(cfg)

    def block(h, layer_p):
        normed = _norm(h, layer_p["norm1"], cfg)
        q, k, v = _attn_qkv(normed, layer_p["attn"], cfg)
        if cfg.pos == "rope":
            q = ops.apply_rope(q, cos, sin)
            k = ops.apply_rope(k, cos, sin)
        out = ops.attention(q, k, v, causal=True, scale=cfg.softmax_scale)
        out = jnp.einsum("bthd,hde->bte", out, layer_p["attn"]["wo"].astype(dt))
        if cfg.bias:
            out = out + layer_p["attn"]["bo"].astype(dt)
        return _close_block(h, out, layer_p, cfg)[0], (k, v)

    x, kv = jax.lax.scan(block, x, params["layers"])
    x = _norm(x, params["final_norm"], cfg)
    last = jnp.take_along_axis(
        x, (lengths - 1)[:, None, None], axis=1)[:, 0]        # [B, E]
    return lm_logits(last, params, cfg).astype(jnp.float32), {"k": kv[0], "v": kv[1]}


@functools.partial(jax.jit, donate_argnames=("state",))
def commit_tokens(state, next_tokens):
    """Record sampled tokens as the next decode inputs (active rows only)."""
    state = dict(state)
    state["last_token"] = jnp.where(state["active"], next_tokens, state["last_token"])
    return state


# The largest `k_bucket` short of the whole vocabulary. On the v5e the
# compiler's TopK costs about as many times its k = 1 cost as k is large
# (48 x 98,304 logits: 0.30 ms at 8, 1.13 at 64, 2.07 at 128, 3.96 at 256, 7.74
# at 512) where its sort of the vocabulary, which `lax.top_k` at k = V lowers
# to, costs 4.95; over 32 x 32,000 logits 256 already costs more than the sort
# (1.16 against 0.84 ms). PERF.md, section 6, PR 34.
TOP_K_MAX_BUCKET = 128


def top_k_bucket(k: int, vocab: int) -> int:
    """The static `k_bucket` of `sample_per_row` for a largest live `top_k`
    of `k`: the next power of two at or above it up to `TOP_K_MAX_BUCKET`,
    the whole vocabulary beyond (0 stays 0: no row cuts). Powers of two keep
    the compiled forms few."""
    if k <= 0:
        return 0
    bucket = 1 << (k - 1).bit_length()
    return bucket if bucket <= min(TOP_K_MAX_BUCKET, vocab) else vocab


@functools.partial(jax.jit, static_argnames=("sampling", "k_bucket"))
def sample_per_row(logits, key, temperatures, top_ks, sampling: bool,
                   k_bucket: int):
    """Row-wise temperature + top-k sampling for the decode hot loop.
    logits [B, V], temperatures [B] (0 → greedy), top_ks [B] int32 (0 → off).

    The two static arguments describe what the LIVE rows ask for; the engine
    keeps them on the host as rows join and leave, no user sets them, and
    the program does no more than they call for. `sampling` false (every
    live temperature <= 0): an argmax and nothing else. `k_bucket` 0 (no
    sampling live row has a `top_k`): categorical over the scaled logits,
    no cut. Otherwise `top_k_bucket` of the largest live `top_k`: the k-th
    largest value comes from `lax.top_k(scaled, k_bucket)`, which sorts the
    vocabulary only where a live row asks for more than `TOP_K_MAX_BUCKET`.
    For the same inputs every form that applies returns the same tokens for
    the live rows. A dead row's stale entry may ask for more than the form
    gives (its `top_k` is clipped to the bucket); `commit_tokens` drops its
    token."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if not sampling:
        return greedy
    scaled = logits / jnp.maximum(temperatures, 1e-6)[:, None]
    if k_bucket:
        # per-row k-th largest as the cutoff (k=0 → cutoff -inf, i.e. no cut):
        # the number a full descending sort holds at index k - 1
        top, _ = jax.lax.top_k(scaled, k_bucket)
        idx = jnp.clip(top_ks - 1, 0, k_bucket - 1)
        kth = jnp.take_along_axis(top, idx[:, None], axis=-1)
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
        k = min(top_k, logits.shape[-1])
        kth = jax.lax.top_k(scaled, k)[0][:, -1:]
        scaled = jnp.where(scaled < kth, -1e30, scaled)
    sampled = jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)
    return jnp.where(temperature <= 0.0, greedy, sampled)
