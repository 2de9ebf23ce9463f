"""Ragged paged attention for the decode step (Pallas TPU + reference).

One launch covers the WHOLE continuous batch against its paged KV: each
row attends over exactly the pages its block table names, up to its own
length — no per-slot gather of the full [max_pages, page] span, no
padding compute for short rows (arXiv 2604.15464, Ragged Paged Attention;
PAPERS.md). The previous decode step gathered every row's full block
table (`kp[state["block"]]` → [B, max_pages*page, Hkv, Dh]) and masked —
HBM traffic and FLOPs scale with the LONGEST POSSIBLE sequence for every
row, not with the tokens actually resident.

Two implementations with ONE accumulation order so they agree bitwise:

- ``_ragged_kernel`` — Pallas TPU kernel, grid (batch, page); the block
  table and per-row positions ride scalar prefetch so the page BlockSpec
  index map gathers each row's next page straight out of the HBM pool,
  and ``pl.when`` skips pages past the row's length (the ragged part —
  dead pages cost neither FLOPs nor VMEM bandwidth). Online-softmax
  accumulators live in VMEM scratch across the page sweep, like
  flash_attention.py.
- ``ragged_decode_attention_reference`` — pure JAX mirror of the same
  per-page online-softmax math (fori_loop over pages, f32 accumulators,
  identical op order), so tier-1 on ``JAX_PLATFORMS=cpu`` asserts the
  kernel (interpret mode) is bit-consistent with the path the CPU engine
  actually decodes with.

Latent (MLA) pools: `vp=None` and a pool of rows [num_pages, P, W] with no
head dimension. All query heads share the one row, and the row is its own
value (absorbed multi-head latent attention: q is (q_nope W_uk^T | q_rope |
0), the caller keeps the result's first kv_lora_rank columns).
``_latent_kernel`` reads each page ONCE; its two matmuls take the operands as
stored (bfloat16 products, float32 sums: [H, W] x [W, P] on the MXU), where
the per-head kernel multiplies in float32.

Window layers (`window=W`, a multiple of the page size): the launch sweeps
only the W/P + 1 logical pages that hold a row's last W positions. Step j of
row b is logical page q = pos // P - W // P + j, whose place in the pool the
caller's table column j names (the row's ring slot q % ring, see
models/decoding_paged.py; scratch page 0 where q < 0: dead, nothing
computed); its keys stand at positions q * P + lane and are masked to
pos - W < kpos <= pos. The same kernel body and the same mirror, launched as
`ragged_window_attention` so that a device trace tells the two kinds apart.

The engine bounds the page sweep host-side (`pages_bound` in
models/decoding_paged.py decode_step_paged_ragged): the block table is
sliced to the batch's live maximum before either impl runs, so even the
reference does work proportional to the longest RESIDENT row.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _ragged_kernel(tbl_ref, pos_ref, q_ref, kp_ref, vp_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, scale: float, page_size: int,
                   kv_heads: int, q_per_kv: int, window: int | None = None):
    b = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    H = kv_heads * q_per_kv

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    p0 = pos_ref[b]
    if window is None:
        # step j is the row's page j, cache positions [j*P, (j+1)*P); live
        # iff its first position is attendable (<= the row's current
        # position) — dead pages are skipped entirely, which is what makes
        # the sweep ragged
        page = j
        live = j * page_size <= p0
    else:
        # step j is logical page pos//P - W//P + j: the last one holds the
        # row's current position, those before the row's start are dead
        page = p0 // page_size - window // page_size + j
        live = page >= 0

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)              # [Hkv, G, Dh]
        k = kp_ref[0].astype(jnp.float32)             # [P, Hkv, Dh]
        v = vp_ref[0].astype(jnp.float32)
        s = jnp.einsum("kgd,pkd->kgp", q, k,
                       preferred_element_type=jnp.float32) * scale
        kpos = page * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 2)
        seen = kpos <= p0
        if window is not None:
            seen &= kpos > p0 - window
        s = jnp.where(seen, s, _NEG_INF)
        sf = s.reshape(H, page_size)
        m_prev = m_scr[:, :1]                         # [H, 1]
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, sf.max(axis=-1, keepdims=True))
        p = jnp.exp(sf - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)
        pv = jnp.einsum("kgp,pkd->kgd",
                        p.reshape(kv_heads, q_per_kv, page_size), v,
                        preferred_element_type=jnp.float32)
        acc_scr[:] = acc_scr[:] * corr + pv.reshape(H, -1)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == nj - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:, :1], 1e-30)
        out = (acc_scr[:] / l).reshape(kv_heads, q_per_kv, -1)
        o_ref[0] = out.astype(o_ref.dtype)


def _ragged_kernel_call(q, kp, vp, block_table, pos, *, scale: float,
                        interpret: bool, window: int | None = None):
    B, Hkv, G, Dh = q.shape
    P = kp.shape[1]
    nb = block_table.shape[1]
    H = Hkv * G
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block_table, pos
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec((1, Hkv, G, Dh), lambda b, j, tbl, pos: (b, 0, 0, 0)),
            # the ragged gather: page j of row b streams in from wherever
            # the block table says it lives in the pool
            pl.BlockSpec((1, P, Hkv, Dh),
                         lambda b, j, tbl, pos: (tbl[b, j], 0, 0, 0)),
            pl.BlockSpec((1, P, Hkv, Dh),
                         lambda b, j, tbl, pos: (tbl[b, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Hkv, G, Dh),
                               lambda b, j, tbl, pos: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, Dh), jnp.float32),
        ],
    )
    kernel = functools.partial(_ragged_kernel, scale=scale, page_size=P,
                               kv_heads=Hkv, q_per_kv=G, window=window)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, Dh), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        name="ragged_paged_attention" if window is None else "ragged_window_attention",
    )(block_table, pos, q, kp, vp)


def _latent_kernel(tbl_ref, pos_ref, q_ref, cp_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, scale: float, page_size: int):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    p0 = pos_ref[b]

    @pl.when(j * page_size <= p0)
    def _compute():
        q = q_ref[0]                                  # [H, W]
        c = cp_ref[0]                                 # [P, W]
        s = jax.lax.dot_general(q, c, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = j * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos <= p0, s, _NEG_INF)
        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)
        pv = jnp.dot(p.astype(c.dtype), c, preferred_element_type=jnp.float32)
        acc_scr[:] = acc_scr[:] * corr + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[0] = (acc_scr[:] / jnp.maximum(l_scr[:, :1], 1e-30)).astype(o_ref.dtype)


def _latent_kernel_call(q, cp, block_table, pos, *, scale: float, interpret: bool):
    B, H, W = q.shape
    P = cp.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block_table, pos
        grid=(B, block_table.shape[1]),
        in_specs=[
            pl.BlockSpec((1, H, W), lambda b, j, tbl, pos: (b, 0, 0)),
            pl.BlockSpec((1, P, W), lambda b, j, tbl, pos: (tbl[b, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, W), lambda b, j, tbl, pos: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, W), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_latent_kernel, scale=scale, page_size=P),
        out_shape=jax.ShapeDtypeStruct((B, H, W), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        name="ragged_latent_attention",
    )(block_table, pos, q, cp)


def _latent_reference(q, cp, block_table, pos, *, scale: float):
    """Pure-JAX mirror of `_latent_kernel`: the same page-by-page online
    softmax, products of the operands as stored, float32 sums."""
    B, H, W = q.shape
    P = cp.shape[1]

    def body(j, carry):
        m, l, acc = carry
        c = cp[block_table[:, j]]                      # [B, P, W]
        s = jnp.einsum("bhw,bpw->bhp", q, c,
                       preferred_element_type=jnp.float32) * scale
        kpos = j * P + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(kpos <= pos[:, None, None], s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1, keepdims=True)
        acc_new = acc * corr + jnp.einsum("bhp,bpw->bhw", p.astype(c.dtype), c,
                                          preferred_element_type=jnp.float32)
        live = (j * P <= pos)[:, None, None]
        return (jnp.where(live, m_new, m), jnp.where(live, l_new, l),
                jnp.where(live, acc_new, acc))

    m0 = jnp.full((B, H, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, 1), jnp.float32)
    a0 = jnp.zeros((B, H, W), jnp.float32)
    _m, l, acc = jax.lax.fori_loop(0, block_table.shape[1], body, (m0, l0, a0))
    return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)


def ragged_decode_attention_reference(q, kp, vp, block_table, pos, *,
                                      scale: float, window: int | None = None):
    """Pure-JAX mirror of the kernel: fori_loop over pages with the SAME
    f32 online-softmax accumulation per page, so the two are
    bit-consistent (asserted in tier-1). Dead pages keep the previous
    accumulators untouched — the where() twin of the kernel's pl.when."""
    B, Hkv, G, Dh = q.shape
    P = kp.shape[1]
    nb = block_table.shape[1]
    H = Hkv * G
    qf = q.astype(jnp.float32)
    p0 = pos[:, None, None, None]

    def body(j, carry):
        m, l, acc = carry
        pid = block_table[:, j]                        # [B]
        k = kp[pid].astype(jnp.float32)                # [B, P, Hkv, Dh]
        v = vp[pid].astype(jnp.float32)
        s = jnp.einsum("bkgd,bpkd->bkgp", qf, k,
                       preferred_element_type=jnp.float32) * scale
        # the step's logical page: j itself, or a row's own (kernel: `page`)
        page = j if window is None else (pos // P - window // P + j)[:, None, None, None]
        kpos = page * P + jax.lax.broadcasted_iota(jnp.int32, s.shape, 3)
        seen = kpos <= p0
        if window is not None:
            seen &= kpos > p0 - window
        s = jnp.where(seen, s, _NEG_INF)
        sf = s.reshape(B, H, P)
        m_new = jnp.maximum(m, sf.max(axis=-1, keepdims=True))
        p = jnp.exp(sf - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1, keepdims=True)
        pv = jnp.einsum("bkgp,bpkd->bkgd",
                        p.reshape(B, Hkv, G, P), v,
                        preferred_element_type=jnp.float32)
        acc_new = acc * corr + pv.reshape(B, H, Dh)
        live = (j * P <= pos)[:, None, None] if window is None else page[:, :, :, 0] >= 0
        return (jnp.where(live, m_new, m), jnp.where(live, l_new, l),
                jnp.where(live, acc_new, acc))

    m0 = jnp.full((B, H, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, 1), jnp.float32)
    a0 = jnp.zeros((B, H, Dh), jnp.float32)
    _m, l, acc = jax.lax.fori_loop(0, nb, body, (m0, l0, a0))
    out = acc / jnp.maximum(l, 1e-30)
    return out.reshape(B, Hkv, G, Dh).astype(q.dtype)


def ragged_decode_attention(q, kp, vp, block_table, pos, *,
                            scale: float | None = None,
                            impl: str = "reference",
                            interpret: bool = False,
                            window: int | None = None):
    """One decode-attention launch over the whole continuous batch.

    q: [B, Hkv, G, Dh] — this step's queries (one token per row, grouped
    by kv head); kp/vp: [num_pages, P, Hkv, Dh] — one layer's page pool;
    block_table: [B, nb] int32 page ids (pre-sliced to the batch's live
    page bound); pos: [B] int32 — row b attends cache positions <= pos[b].
    Returns [B, Hkv, G, Dh] in q's dtype.

    Latent rows: kp [num_pages, P, W], vp None, q [B, 1, H, W]; returns
    [B, 1, H, W], a weighted sum of rows.

    `window` (per-head pools only): row b attends positions pos[b] - window
    < p <= pos[b], and block_table is [B, window // P + 1]: column j names
    the pool page of logical page pos[b] // P - window // P + j.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if window is not None and (vp is None or window % kp.shape[1]
                               or block_table.shape[1] != window // kp.shape[1] + 1):
        raise ValueError(
            "a window is carried by the per-head kernel alone, is a multiple "
            "of the page size and sweeps window // page_size + 1 pages a row")
    if vp is None:
        if impl == "kernel":
            out = _latent_kernel_call(q[:, 0], kp, block_table, pos,
                                      scale=scale, interpret=interpret)
        elif impl == "reference":
            out = _latent_reference(q[:, 0], kp, block_table, pos, scale=scale)
        else:
            raise ValueError(f"impl must be 'kernel' or 'reference', got {impl!r}")
        return out[:, None]
    if impl == "kernel":
        return _ragged_kernel_call(q, kp, vp, block_table, pos, scale=scale,
                                   interpret=interpret, window=window)
    if impl != "reference":
        raise ValueError(f"impl must be 'kernel' or 'reference', got {impl!r}")
    return ragged_decode_attention_reference(q, kp, vp, block_table, pos,
                                             scale=scale, window=window)
