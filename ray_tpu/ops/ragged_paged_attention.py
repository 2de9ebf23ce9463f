"""Ragged paged attention for the decode step (Pallas TPU + reference).

One launch covers the WHOLE continuous batch against its paged KV: each
row attends over exactly the pages its block table names, up to its own
length: no per-slot gather of the full [max_pages, page] span, no padding
compute for short rows (arXiv 2604.15464, Ragged Paged Attention; PAPERS.md).

Per-head pools (`kp`, `vp` [num_pages, P, Hkv, Dh]), a kernel (of two forms,
by the pool's rows) and its mirror with ONE accumulation order, so they agree
bitwise:

- ``_ragged_kernel`` (pools of whole 128-lane rows: every cell's): one Pallas
  TPU program, no grid. The pools stay in
  HBM (`memory_space=pl.ANY`) and are read where they lie; the block table
  and the rows' positions lie in SMEM. A first loop plans every row's walk
  from `pos[b]` (`_row_walk`: its first table column, its blocks, and which
  row walks next), then a loop over the rows and inside it a loop over the
  row's own BLOCKS of pages: a page past the row's length costs nothing, a
  row at `pos < 0` (not active) one scalar test. A block is
  `pages_per_block` pages, copied into VMEM by `make_async_copy`, a copy a
  page and a pool, double-buffered: while one block is multiplied the next
  (the row's, or the next live row's first) is in flight. The online softmax
  runs once a block (`_block_softmax`), its accumulators in VMEM scratch.
- ``ragged_decode_attention_reference``: pure JAX mirror: the same blocks in
  the same order through the same `_block_softmax`, the rows side by side
  (a fori_loop over the table's blocks, a row past its last block keeping
  its accumulators), so tier-1 on ``JAX_PLATFORMS=cpu`` asserts the kernel
  (interpret mode) is bit-consistent with the path the CPU engine actually
  decodes with.

Both products take their operands AS STORED (the pool's dtype; float32
sums; the probabilities cast to V's dtype for the second, as
`_latent_kernel` and the flash kernels do), and the heads are the MXU's rows:
a block's [T, Hkv, Dh] is read as [T * Hkv, Dh], which is how it lies in
memory, so the scores of all H query heads against all Hkv KV heads are ONE
[H, Dh] x [Dh, T * Hkv] product, the columns of the other KV heads masked
before the softmax, and the values one [H, T * Hkv] x [T * Hkv, Dh]
product. That is Hkv times the FLOPs of the per-head contraction, on an MXU
that is idle at a decode step's sizes, instead of Hkv small batched products
over a strided view (one matrix-vector product a head where a KV head has
one query head).

How many pages a block holds is derived from what the launch observes
(`pages_per_block`: the bytes of a page, the double buffer, a VMEM budget):
2 for a page of 16 KV heads of 128 (262 KB a pool), 4 for 8 heads, 8 for 4
heads, never more than the table is wide.

- ``_paged_kernel``: the same walk, the same `_block_softmax` and the same
  mirror for a pool whose pages the kernel cannot copy by hand
  (`copies_pages`): Mosaic slices a pool in HBM only where its rows are whole
  128-lane tiles, and a pool of heads of 64 (Llama-1B, GPT-2; `granite` packs
  two heads a row instead, `TransformerConfig.kv_packed`) is stored padded to
  128 lanes. There the grid is (rows, table columns), the pipeline brings
  column j's page (the BlockSpec's index map reads the table), a block is
  that one page, and a column outside a row's walk is a grid step that
  computes nothing; the caller cuts the table to the batch's live bound
  (`table_width`), which bounds the sweep. Which of the two a launch is comes
  from the pool's shape alone; both go by the same two names.

Latent (MLA) pools: `vp=None` and a pool of rows [num_pages, P, W] with no
head dimension. All query heads share the one row, and the row is its own
value (absorbed multi-head latent attention: q is (q_nope W_uk^T | q_rope |
0), the caller keeps the result's first kv_lora_rank columns).
``_latent_kernel`` (grid (batch, page), `pl.when` over the dead pages, the
page's BlockSpec index map reading the table) reads each page ONCE; its two
matmuls take the operands as stored too.

Window layers (`window=W`, a multiple of the page size): the launch walks
only the W/P + 1 logical pages that hold a row's last W positions. Column j
of row b's table is logical page q = pos // P - W // P + j, whose place in
the pool the caller names (the row's ring slot q % ring, see
models/decoding_paged.py; scratch page 0 where q < 0: before the row's
start, not walked); its keys stand at positions q * P + lane and are masked
to pos - W < kpos <= pos. The same kernel body and the same mirror, launched
as `ragged_window_attention` so that a device trace tells the two kinds
apart; the walk is W/P + 1 pages whatever the row, so its blocks are the
even split of that.

The engine still bounds the table host-side for the launches that walk every
row through the table they are given (`pages_bound` in
models/decoding_paged.py decode_step_paged_ragged: the reference, the latent
kernel and `_paged_kernel` get the block table cut to the batch's live
maximum); `_ragged_kernel` gets the table whole, its work is bounded by each
row's own `pos`, and with the same shapes in every program an engine compiles
it is traced once (`_ragged_kernel_call` is jitted for that). What a launch is
handed and what it then walks are said HERE and nowhere else: `table_width`
(the step cuts the table by it, the engine counts by it) and
`walked_positions` (the positions the walked blocks hold, on the host, from
the same `_row_walk` the kernels and the mirror plan by), which is what
`stats()["cache"]["ragged_block_positions"]` adds up.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


# what a launch's page buffers may hold of VMEM: K and V, two blocks each (one
# being multiplied, one in flight). Read on the chip (PERF.md section 6, PR 43):
# at 2 MiB a block is 512 KiB a pool; the kernel alone, on row mixes made up
# to look like the cells', was at or within 5 % of its best of 1, 2, 4 and
# 8 MiB at each cell's shape, and traced runs of the cells at 1 against 2 MiB
# read `mellum2`'s two launches an eighth faster at 2 and `granite`'s a tenth
# slower (`ouro-2.6b` takes two pages a block at either)
_BLOCK_VMEM_BYTES = 2 << 20


def copies_pages(head_dim: int) -> bool:
    """Whether the per-head kernel copies a row's pages out of HBM by hand:
    Mosaic slices a pool in HBM only where its rows are whole 128-lane tiles
    (a pool of heads of 64 is stored padded to 128 lanes, and no slice of it
    is aligned to that). Other pools take the launch that has the pipeline
    bring a page a grid step (`_paged_kernel`)."""
    return head_dim % 128 == 0


def pages_per_block(page_size: int, kv_heads: int, head_dim: int, itemsize: int,
                    table_pages: int, window: int | None = None) -> int:
    """Pages a block of the per-head launch holds, from what the launch can
    observe: the bytes of a page (K and V), the double buffer and the VMEM
    budget; a power of two, at least two, never more than the table is wide;
    one where the pipeline brings the pages (`copies_pages`). A window launch
    walks `table_pages` = window // P + 1 pages whatever the row, so its
    blocks are the even split of that walk."""
    if not copies_pages(head_dim):
        return 1
    page_bytes = 2 * page_size * kv_heads * head_dim * itemsize
    n = max(2, _BLOCK_VMEM_BYTES // (2 * page_bytes))
    n = 1 << (n.bit_length() - 1)
    if window is not None:
        return -(-table_pages // -(-table_pages // n))
    return min(n, table_pages)


def table_width(table_pages: int, pages_bound: int, head_dim: int, kernel: bool) -> int:
    """Columns of the rows' block table that a full-attention launch over
    per-head pools is handed: the table whole where the kernel walks each
    row's own pages (its launch is then the same in every decode program an
    engine compiles, and traced once), cut to the batch's live bound for the
    launches whose sweep the table's width bounds (the reference, and the
    kernel whose grid is the table: `_paged_kernel`)."""
    return table_pages if kernel and copies_pages(head_dim) else pages_bound


def walked_positions(pos, *, page_size: int, kv_heads: int, head_dim: int,
                     itemsize: int, table_pages: int) -> int:
    """Positions held by the blocks of pages that a full-attention launch
    walks for rows at `pos` (a numpy vector, on the host) through a table of
    `table_pages` columns: what the engine counts beside the positions
    attended (`stats()["cache"]["ragged_block_positions"]`)."""
    n = pages_per_block(page_size, kv_heads, head_dim, itemsize, table_pages)
    _c0, blocks, _origin = _row_walk(pos, page_size=page_size, table_pages=table_pages,
                                     block_pages=n, window=None, xp=np)
    return int(blocks.sum()) * n * page_size


def _row_walk(pos, *, page_size: int, table_pages: int, block_pages: int,
              window: int | None, xp=jnp):
    """(first table column, blocks, the cache position of table column 0) of
    a row's walk, from its position: the columns [c0, c1) hold the pages it
    attends over, block i is the `block_pages` columns from c0 + i *
    block_pages. A row at pos < 0 is dead and walks nothing. [B] vectors in
    the mirror, a row's scalars in the kernels, numpy (`xp`) on the host."""
    live = pos >= 0
    at = xp.maximum(pos, 0) // page_size             # the page `pos` lies in
    if window is None:
        c0 = origin = xp.zeros_like(pos)
        c1 = xp.minimum(at + 1, table_pages)
    else:
        c0 = xp.maximum(window // page_size - at, 0)
        c1 = xp.full_like(pos, table_pages)
        origin = (at - window // page_size) * page_size
    blocks = xp.where(live, (c1 - c0 + block_pages - 1) // block_pages, 0)
    return c0, blocks, origin


def _block_softmax(q, k, v, m_prev, l_prev, acc_prev, *, first, p0, scale: float,
                   kv_heads: int, q_per_kv: int, window: int | None):
    """One block's online-softmax update, shared by the kernel and its mirror
    so that both sum in one order. q [.., H, Dh]; k, v [.., T * Hkv, Dh]: the
    block's T positions, a position's KV heads side by side as the pool stores
    them, multiplied as stored in ONE product a side with the other heads'
    columns masked before the softmax (float32 sums). `first` [.., 1, 1]: the
    cache position of the block's first row; `p0` [.., 1, 1]: the row's."""
    s = jnp.einsum("...hd,...cd->...hc", q, k,
                   preferred_element_type=jnp.float32) * scale
    H, C = s.shape[-2:]
    col = jax.lax.broadcasted_iota(jnp.int32, (H, C), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (H, C), 0)
    # column c is position first + c // Hkv of KV head c % Hkv; in columns,
    # kpos <= p0 is c < (p0 - first + 1) * Hkv. (lax.rem and lax.div: the
    # operands are not negative, and the launch is lowered again in every
    # decode program an engine compiles, so what it holds is kept short)
    ahead = (p0 - first + 1) * kv_heads
    seen = (jax.lax.rem(col, jnp.int32(kv_heads))
            == jax.lax.div(row, jnp.int32(q_per_kv))) & (col < ahead)
    if window is not None:
        seen &= col >= ahead - window * kv_heads
    s = jnp.where(seen, s, _NEG_INF)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)
    pv = jnp.einsum("...hc,...cd->...hd", p.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
    return m_new, l_new, acc_prev * corr + pv


def _reset(m_scr, l_scr, acc_scr):
    m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)


def _block_update(q, k, v, m_scr, l_scr, acc_scr, **softmax):
    """A row's accumulators (VMEM scratch) through one block's `_block_softmax`."""
    m_new, l_new, acc = _block_softmax(
        q, k, v, m_scr[:, :1], l_scr[:, :1], acc_scr[:], **softmax)
    acc_scr[:] = acc
    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)


def _normalised(l_scr, acc_scr, dtype):
    return (acc_scr[:] / jnp.maximum(l_scr[:, :1], 1e-30)).astype(dtype)


def _ragged_kernel(tbl_ref, pos_ref, q_ref, kp_hbm, vp_hbm, o_ref,
                   kbuf, vbuf, sems, walk_ref, m_scr, l_scr, acc_scr, *,
                   scale: float, window: int | None = None):
    B, H, Dh = q_ref.shape
    nb = tbl_ref.shape[1]
    _, n, P, Hkv, _ = kbuf.shape
    one = jnp.int32(1)

    # every row's walk into SMEM, last row first: walk_ref[:3, b] its first
    # column, its blocks and the position of its column 0, walk_ref[3, b + 1]
    # the first row after b that walks a block (B: none), walk_ref[3, 0] the
    # first of all
    def plan(r, following):
        b = B - 1 - r
        first, blocks, origin = _row_walk(
            pos_ref[b], page_size=P, table_pages=nb, block_pages=n, window=window)
        walk_ref[0, b], walk_ref[1, b], walk_ref[2, b] = first, blocks, origin
        walk_ref[3, b + 1] = following
        return jax.lax.select(blocks > 0, b, following)

    walk_ref[3, 0] = jax.lax.fori_loop(0, B, plan, jnp.int32(B))

    def copies(b, i, slot, do: str):
        """Start, or wait for, block i of row b into buffer `slot`: its pages'
        K and V, by table column (past the table's end: the last column
        again, masked). A loop and not n copies written out: the launch is
        lowered again in every decode program an engine compiles."""
        col = walk_ref[0, b] + i * n

        def page(t, carry):
            at = tbl_ref[b, jax.lax.min(col + t, jnp.int32(nb - 1))]
            for k, (pool, buf) in enumerate(((kp_hbm, kbuf), (vp_hbm, vbuf))):
                getattr(pltpu.make_async_copy(
                    pool.at[at], buf.at[slot, t], sems.at[k, slot]), do)()
            return carry

        jax.lax.fori_loop(0, n, page, None)

    def fetch(b, i, slot):
        @pl.when(b < B)
        def _start():
            copies(jax.lax.min(b, jnp.int32(B - 1)), i, slot, "start")

    fetch(walk_ref[3, 0], 0, 0)

    def row(b, slot):
        p0, blocks = pos_ref[b], walk_ref[1, b]
        _reset(m_scr, l_scr, acc_scr)

        def block(i, slot):
            # the next block, this row's or the next live row's first, is in
            # flight while this one is multiplied
            last = i + one == blocks
            fetch(jax.lax.select(last, walk_ref[3, b + one], b),
                  jax.lax.select(last, jnp.int32(0), i + one), one - slot)
            copies(b, i, slot, "wait")
            _block_update(
                q_ref[b], kbuf.at[slot].reshape(n * P * Hkv, Dh)[...],
                vbuf.at[slot].reshape(n * P * Hkv, Dh)[...], m_scr, l_scr, acc_scr,
                first=walk_ref[2, b] + (walk_ref[0, b] + i * n) * P, p0=p0,
                scale=scale, kv_heads=Hkv, q_per_kv=H // Hkv, window=window)
            return one - slot

        slot = jax.lax.fori_loop(0, blocks, block, slot)
        o_ref[b] = _normalised(l_scr, acc_scr, o_ref.dtype)
        return slot

    jax.lax.fori_loop(0, B, row, jnp.int32(0))


def _paged_kernel(tbl_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, scale: float, window: int | None = None):
    """The same walk for a pool whose pages the kernel cannot copy by hand
    (`copies_pages`): grid (rows, table columns), the pipeline brings column
    j's page (the BlockSpec's index map reads the table), a block is that one
    page, and a column outside the row's walk is a grid step that computes
    nothing. The table's width bounds the sweep, so the caller cuts it to the
    batch's live bound (`table_width`)."""
    b, j = pl.program_id(0), pl.program_id(1)
    _, P, Hkv, Dh = k_ref.shape
    H = q_ref.shape[1]
    nb = tbl_ref.shape[1]
    p0 = pos_ref[b]
    c0, blocks, origin = _row_walk(p0, page_size=P, table_pages=nb, block_pages=1,
                                   window=window)

    @pl.when(j == 0)
    def _init():
        _reset(m_scr, l_scr, acc_scr)

    @pl.when((j >= c0) & (j < c0 + blocks))
    def _block():
        _block_update(
            q_ref[0], k_ref[0].reshape(P * Hkv, Dh), v_ref[0].reshape(P * Hkv, Dh),
            m_scr, l_scr, acc_scr, first=origin + j * P, p0=p0,
            scale=scale, kv_heads=Hkv, q_per_kv=H // Hkv, window=window)

    @pl.when(j == nb - 1)
    def _finalize():
        o_ref[0] = _normalised(l_scr, acc_scr, o_ref.dtype)


# jitted for its trace cache: an engine compiles a decode program for every
# bound on the batch's pages and hands each the same launch (the table whole:
# `table_width`), which is then traced once and not once a program
@functools.partial(jax.jit, static_argnames=("scale", "interpret", "window"))
def _ragged_kernel_call(q, kp, vp, block_table, pos, *, scale: float,
                        interpret: bool, window: int | None = None):
    B, Hkv, G, Dh = q.shape
    P = kp.shape[1]
    H = Hkv * G
    nb = block_table.shape[1]
    accumulators = [pltpu.VMEM((H, 128), jnp.float32),
                    pltpu.VMEM((H, 128), jnp.float32),
                    pltpu.VMEM((H, Dh), jnp.float32)]
    if copies_pages(Dh):
        n = pages_per_block(P, Hkv, Dh, kp.dtype.itemsize, nb, window)
        smem = pl.BlockSpec(memory_space=pltpu.SMEM)
        kernel = _ragged_kernel
        specs = dict(
            in_specs=[
                smem, smem,                              # block_table, pos
                pl.BlockSpec(memory_space=pltpu.VMEM),   # q, whole
                pl.BlockSpec(memory_space=pl.ANY),       # the pools stay in HBM
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((2, n, P, Hkv, Dh), kp.dtype),
                pltpu.VMEM((2, n, P, Hkv, Dh), vp.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((4, B + 1), jnp.int32),       # the rows' walks
                *accumulators,
            ])
    else:
        def row(b, j, tbl, pos):
            return (b, 0, 0)

        def page(b, j, tbl, pos):
            return (tbl[b, j], 0, 0, 0)

        kernel = _paged_kernel
        specs = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,                       # block_table, pos
            grid=(B, nb),
            in_specs=[pl.BlockSpec((1, H, Dh), row),
                      pl.BlockSpec((1, P, Hkv, Dh), page),
                      pl.BlockSpec((1, P, Hkv, Dh), page)],
            out_specs=pl.BlockSpec((1, H, Dh), row),
            scratch_shapes=accumulators))
    out = pl.pallas_call(
        functools.partial(kernel, scale=scale, window=window),
        out_shape=jax.ShapeDtypeStruct((B, H, Dh), q.dtype),
        interpret=interpret,
        name="ragged_paged_attention" if window is None else "ragged_window_attention",
        **specs,
    )(block_table, pos, q.reshape(B, H, Dh).astype(kp.dtype), kp, vp)
    return out.reshape(B, Hkv, G, Dh)


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
    """Pure-JAX mirror of the kernel: the same blocks of pages in the same
    order, each through `_block_softmax`, so the two are bit-consistent
    (asserted in tier-1). A row past its last block keeps its accumulators:
    the where() twin of the kernel's trip count."""
    B, Hkv, G, Dh = q.shape
    P = kp.shape[1]
    nb = block_table.shape[1]
    H = Hkv * G
    n = pages_per_block(P, Hkv, Dh, kp.dtype.itemsize, nb, window)
    c0, blocks, origin = _row_walk(pos, page_size=P, table_pages=nb,
                                   block_pages=n, window=window)
    qf = q.reshape(B, H, Dh).astype(kp.dtype)
    p0 = pos[:, None, None]

    def body(i, carry):
        cols = c0[:, None] + i * n + jnp.arange(n)[None]            # [B, n]
        pid = jnp.take_along_axis(block_table, jnp.minimum(cols, nb - 1), axis=1)
        new = _block_softmax(
            qf, kp[pid].reshape(B, n * P * Hkv, Dh), vp[pid].reshape(B, n * P * Hkv, Dh),
            *carry, first=(origin + cols[:, 0] * P)[:, None, None], p0=p0, scale=scale,
            kv_heads=Hkv, q_per_kv=G, window=window)
        live = (i < blocks)[:, None, None]
        return tuple(jnp.where(live, x, old) for x, old in zip(new, carry))

    m0 = jnp.full((B, H, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, 1), jnp.float32)
    a0 = jnp.zeros((B, H, Dh), jnp.float32)
    _m, l, acc = jax.lax.fori_loop(0, -(-nb // n), body, (m0, l0, a0))
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
    block_table: [B, nb] int32 page ids (as wide as `table_width` says);
    pos: [B] int32 — row b attends cache positions <= pos[b];
    a row at pos[b] < 0 (not active) attends nothing, no page of it is read
    and zeros come back.
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
