"""Ring attention: exact attention over a sequence-sharded axis.

Greenfield for this framework (the reference has NO sequence/context
parallelism — SURVEY.md §2.6: ring/Ulysses absent, delegated to engines).
Design follows the ring-attention construction (blockwise attention with
online softmax; KV blocks rotate around the `sp` mesh axis via ppermute so
each hop rides one ICI link while the local block matmul hides the transfer).

All functions are called INSIDE shard_map with q/k/v already sharded on the
sequence dimension; shapes are per-shard [B, T_local, H, D].
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.parallel.collectives import axis_size, pvary as _pvary, zeros_varying_like

_NEG_INF = -1e30


def _block_attend(q, k, v, m_prev, l_prev, o_prev, mask, scale):
    """One flash-attention-style accumulation step.

    q: [B,Tq,H,D]  k,v: [B,Tk,H,D]  mask: [Tq,Tk] bool (True = attend)
    m,l: [B,H,Tq]  o: [B,Tq,H,D]
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    s = jnp.where(mask[None, None, :, :], s, _NEG_INF)
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    # rows fully masked in this block contribute exp(-1e30 - m) ≈ 0 naturally
    correction = jnp.exp(m_prev - m_new)
    l_new = l_prev * correction + p.sum(axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    o_new = o_prev * correction.transpose(0, 2, 1)[..., None] + pv
    return m_new, l_new, o_new


def ring_attention(q, k, v, *, axis_name: str, causal: bool = True,
                   scale: float | None = None):
    """Exact (optionally causal) attention with KV rotating around `axis_name`.

    Per-shard inputs [B, T, H, D]; K/V heads must already match Q heads
    (repeat GQA KV heads before sharding). Returns per-shard [B, T, H, D].
    """
    B, T, H, D = q.shape
    if scale is None:
        scale = D ** -0.5
    n = axis_size(axis_name)
    my = lax.axis_index(axis_name)
    q_pos = my * T + jnp.arange(T)

    # init accumulators carrying q's full vma (not just the ring axis) so the
    # scan carry types line up with the per-shard outputs under shard_map
    qf = q.astype(jnp.float32)
    m0 = zeros_varying_like((B, H, T), jnp.float32, qf) + _NEG_INF
    l0 = zeros_varying_like((B, H, T), jnp.float32, qf)
    o0 = zeros_varying_like((B, T, H, D), jnp.float32, qf)

    def step(carry, idx):
        k_cur, v_cur, m, l, o = carry
        src = (my - idx) % n  # which shard's KV block we currently hold
        k_pos = src * T + jnp.arange(T)
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]
        else:
            mask = jnp.ones((T, T), dtype=bool)
        m, l, o = _block_attend(qf, k_cur.astype(jnp.float32),
                                v_cur.astype(jnp.float32), m, l, o, mask, scale)
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (k_nxt, v_nxt, m, l, o), None

    (_k, _v, m, l, o), _ = _scan_steps(step, (k, v, m0, l0, o0), n)
    out = o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def _scan_steps(step, carry, n):
    return lax.scan(step, carry, jnp.arange(n))


def reference_attention(q, k, v, *, causal: bool = True, scale: float | None = None,
                        window: int | None = None):
    """Unsharded reference used by tests and by the single-device path.
    `window` (causal only): query i sees key j iff 0 <= i - j < window."""
    B, T, H, D = q.shape
    if scale is None:
        scale = D ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.tril(jnp.ones((T, T), dtype=bool))
        if window is not None and window < T:
            mask &= ~jnp.tril(jnp.ones((T, T), dtype=bool), -window)
        s = jnp.where(mask[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)
