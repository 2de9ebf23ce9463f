"""Flash attention forward + backward kernels for TPU (Pallas).

Blocked online-softmax attention: forward grid (B, H, nq, nk) with the kv
dimension innermost so the f32 accumulators live in VMEM scratch across kv
steps and the MXU sees [block_q, D] x [D, block_k] matmuls. Causal blocks
above the diagonal are skipped via predication; only a block the diagonal
crosses builds the mask, and a square one is computed in strips that leave
out most of what lies above the diagonal inside it (`_diagonal_tiles`). The
running max and sum of a row are kept on all 128 lanes of the row's vreg, in
scratch and in flight: narrowed to a [block_q, 1] column at every kv step
they cost the forward kernel as much again as all the rest of it (v5e, PR
31: 573 against 312 us a call at [4, 20, 1024, 64], blocks of 512). The
forward also emits the per-row logsumexp so the backward never rebuilds the
softmax normalizer.

The per-query side arrays (the log-sum-exp, and the backward's delta) cross
every kernel boundary as rows, [B, H, 1, T] float32, dense in HBM. Until PR 58
the forward wrote and the dq kernel read them as columns [B, H, T, 1]: one
value a 128-lane row, 42 MB an array at [4, 20, 1024] where the values are
0.33 MB, and GPT-2 large's train step spent 127 us a layer turning them
between that form and the rows that the dk/dv kernel and a layer's checkpoint
want (PR 56's traced copies, PERF.md section 5). Now the kernels turn them
where they hold them anyway on all 128 lanes: the forward transposes its
[block_q, 128] plane of m + log l once a q block, at `_finalize` (`_as_row`),
and the dq kernel spreads the two rows it is given over two [block_q, 128]
scratch planes once a q block, at j == 0 (`_on_lanes`), which its kv steps
read as the forward reads its running max (`_lanes`). A transposition moves
values and rounds none. With the columns gone that step is 7.1 ms shorter
(241.5 -> 234.4 ms on a v5e, PR 58: the copies, and the memory-bound ops
beside them a few us each).

Backward is the standard two-kernel flash decomposition (no [T, T] score
tensor is ever materialized):
  - dkv kernel, grid (B, H, nk, nq): for a fixed kv block, sweep q blocks
    accumulating dv += p^T dO and dk += ds^T q in VMEM scratch. It computes
    the scores already transposed (k q^T, [block_k, block_q]; lse and delta
    arrive as rows and are read as rows), so both accumulating products are
    plain and what is transposed for the MXU is a [block, D] operand, never a
    score block.
  - dq kernel, grid (B, H, nq, nk): for a fixed q block, sweep kv blocks
    accumulating dq += ds k; the same two rows, spread over lanes once a q
    block.
where p = exp(s - lse) is recomputed blockwise from the saved logsumexp and
delta = rowsum(dO * O) folds the softmax Jacobian into ds = p * (dp - delta).

Precision: every product takes its operands in the dtype they are stored in
(q, k, v, dO as read from their refs) and accumulates in float32. The
softmax (scores, scale, mask, running max and sum, exp, lse, delta) and the
scratch accumulators are float32. `p` and `ds` are rounded to the operand
dtype before their second product (p @ v, p^T dO, ds^T q, ds @ k): the one
rounding beyond the operands' own. With float32 operands the kernels' own
code rounds nothing; with bfloat16 operands the result agrees with a float32
reference to bfloat16 rounding (2e-3 of its norm, 1.25 times what rounding
the reference itself to bfloat16 costs), not bit for bit. Until PR 31 the
kernels cast every operand to float32 first. On the chip that bought nothing
and cost nothing: at Mosaic's default precision a float32 product is one
bfloat16 pass of the MXU, which rounds `p` and `ds` just as the cast here
does, so those kernels gave these results to the sixth digit in the same time.

The serving path's sibling (PR 47, the end of this file): a chunk's
continuation, `flash_prefix_attention`, is a forward of its own launch and
name; the train path's three keep their programs. The same `_softmax_step`,
the same precision.

(The reference framework has no attention kernels at all — attention lives in
vLLM/torch; this is the TPU-native compute path that replaces it.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
# Block sizes in order of preference: what a caller that names none gets is
# the first that divides T. All three kernels are fastest at 1024 on a v5e
# (PR 31: bf16, causal, d_head 64 and 128, T 1024 to 4096): they are bound by
# their softmax's VPU work and by fixed work a grid step, not by VMEM, so they
# take long blocks and leave the diagonal's savings to the strips inside one
# (_DIAG_STRIP).
_BLOCK_CHOICES = (1024, 512, 256, 128)


def _blocks(T: int, block_q: int | None, block_k: int | None):
    """The caller's block sizes, or for None the best that tiles T."""
    def fit(asked):
        if asked is not None:
            return min(asked, T)
        return next((b for b in _BLOCK_CHOICES if T % b == 0), T)

    block_q, block_k = fit(block_q), fit(block_k)
    if T % block_q or T % block_k:
        raise ValueError(f"T={T} must be divisible by block sizes {block_q},{block_k}")
    return block_q, block_k


_NT = (((1,), (1,)), ((), ()))   # a @ b^T


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    """Operands as stored, float32 accumulation."""
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _causal_mask(s, q0, k0, *, q_axis: int):
    """Scores of the queries from position q0 on (along `q_axis`) against the
    keys from k0 on (along the other axis): keys after the query masked out."""
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    return jnp.where(q_pos >= k_pos, s, _NEG_INF)


def _lanes(x, n: int):
    """x: [rows, 128] holding a row's value on every lane -> [rows, n]."""
    if n <= x.shape[1]:
        return x[:, :n]
    return pltpu.repeat(x, n // x.shape[1], axis=1)


def _as_row(x):
    """x: [rows, 128] holding a row's value on every lane -> [1, rows], the
    form in which a per-query array crosses a kernel's boundary. One aligned
    float32 transposition: values move, none is rounded."""
    return x.T[:1, :]


def _on_lanes(row):
    """row: [1, rows] -> [rows, 128] holding a row's value on every lane
    (`_as_row`'s inverse, the form `_lanes` reads)."""
    return jnp.broadcast_to(row, (128, row.shape[1])).T


_DIAG_STRIP = 256


def _diagonal_tiles(block: int, *, strip_of: str):
    """The (queries, keys) slices to compute of a square block whose corners
    the diagonal runs through: strips of _DIAG_STRIP queries, each against the
    keys up to its last query (`strip_of="q"`), or strips of keys, each against
    the queries from its first key on ("k"). What lies above the diagonal
    beyond a strip's own square is not computed: of a block of 1024, 5/8 is,
    where the part under the diagonal is 4/8."""
    if block <= _DIAG_STRIP or block % _DIAG_STRIP:
        return [(slice(0, block), slice(0, block))]
    edges = range(0, block, _DIAG_STRIP)
    if strip_of == "q":
        return [(slice(e, e + _DIAG_STRIP), slice(0, e + _DIAG_STRIP)) for e in edges]
    return [(slice(e, block), slice(e, e + _DIAG_STRIP)) for e in edges]


def _when_live(i, j, *, causal, block_q, block_k, strip_of="q"):
    """Decorator running `body(tiles, masked)` for q block i and kv block j,
    `tiles` the (queries, keys) slices of the block to compute: not at all for
    a causal block wholly above the diagonal (its first key lies after its
    last query); masked for a block the diagonal crosses (its last key lies
    after its first query), in strips if it is square (`_diagonal_tiles`: then
    i == j); whole and without the mask below the diagonal."""
    whole = [(slice(0, block_q), slice(0, block_k))]

    def run(body):
        if not causal:
            body(whole, False)
            return
        live = j * block_k <= (i + 1) * block_q - 1
        crossed = (j + 1) * block_k - 1 > i * block_q
        diagonal = (_diagonal_tiles(block_q, strip_of=strip_of)
                    if block_q == block_k else whole)
        pl.when(live & crossed)(functools.partial(body, diagonal, True))
        pl.when(live & jnp.logical_not(crossed))(functools.partial(body, whole, False))
    return run


def _softmax_step(s, v, m_scr, l_scr, acc_scr, rows):
    """The online softmax's update of `rows` of the scratch (a slice of its
    rows, or the index of a [rows, ..] plane of it) by one block of keys: s
    [rows, keys] their float32 scores, v [keys, D] the block's values as
    stored."""
    m_prev = m_scr[rows, :]                               # [rows, 128]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - _lanes(m_new, s.shape[1]))
    corr = jnp.exp(m_prev - m_new)
    l_scr[rows, :] = l_scr[rows, :] * corr + p.sum(axis=-1, keepdims=True)
    acc_scr[rows, :] = (acc_scr[rows, :] * _lanes(corr, acc_scr.shape[-1])
                        + _dot(p.astype(v.dtype), v))
    m_scr[rows, :] = m_new


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
                scale: float, causal: bool, block_q: int, block_k: int):
    i = pl.program_id(2)
    j = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @_when_live(i, j, causal=causal, block_q=block_q, block_k=block_k)
    def _compute(tiles, masked):
        for rows, keys in tiles:
            v = v_ref[0, 0, keys, :]                      # [keys, D]
            s = _dot(q_ref[0, 0, rows, :], k_ref[0, 0, keys, :], _NT) * scale
            if masked:                                    # [rows, keys]
                s = _causal_mask(s, i * block_q + rows.start,
                                 j * block_k + keys.start, q_axis=0)
            _softmax_step(s, v, m_scr, l_scr, acc_scr, rows)

    @pl.when(j == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0, 0] = (acc_scr[:] / _lanes(l, acc_scr.shape[1])).astype(o_ref.dtype)
        # turned once a q block, after the last kv step: [1, block_q]
        lse_ref[0, 0] = _as_row(m_scr[:] + jnp.log(l))


def _fwd_call(q, k, v, *, causal: bool, scale: float, block_q: int | None,
              block_k: int | None, interpret: bool):
    B, H, T, D = q.shape
    block_q, block_k = _blocks(T, block_q, block_k)
    if any(n > 128 and n % 128 for n in (block_k, D)):
        raise ValueError(f"block_k={block_k} and D={D} must be under 128 or "
                         "multiples of it")
    nq, nk = T // block_q, T // block_k
    grid = (B, H, nq, nk)

    def qo_map(b, h, i, j):
        return (b, h, i, 0)

    def kv_map(b, h, i, j):
        return (b, h, j, 0)

    def lse_map(b, h, i, j):
        return (b, h, 0, i)

    kwargs = {} if interpret else dict(memory_space=pltpu.VMEM)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k)
    scratch = [
        pltpu.VMEM((block_q, 128), jnp.float32),
        pltpu.VMEM((block_q, 128), jnp.float32),
        pltpu.VMEM((block_q, D), jnp.float32),
    ]
    return pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((B, H, T, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, 1, T), jnp.float32),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), qo_map, **kwargs),
            pl.BlockSpec((1, 1, block_k, D), kv_map, **kwargs),
            pl.BlockSpec((1, 1, block_k, D), kv_map, **kwargs),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, block_q, D), qo_map, **kwargs),
            pl.BlockSpec((1, 1, 1, block_q), lse_map, **kwargs),
        ),
        scratch_shapes=scratch,
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)


def flash_attention_forward(q, k, v, *, causal: bool = True,
                            scale: float | None = None,
                            block_q: int | None = None,
                            block_k: int | None = None,
                            interpret: bool = False):
    """q,k,v: [B, H, T, D] (heads-major). Returns [B, H, T, D]. Block sizes
    left None are chosen from T (`_BLOCK_CHOICES`)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    out, _ = _fwd_call(q, k, v, causal=causal, scale=scale,
                       block_q=block_q, block_k=block_k, interpret=interpret)
    return out


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *,
                scale: float, causal: bool, block_q: int, block_k: int):
    j = pl.program_id(2)   # kv block (outer)
    i = pl.program_id(3)   # q block (inner sweep)
    nq = pl.num_programs(3)

    @pl.when(i == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @_when_live(i, j, causal=causal, block_q=block_q, block_k=block_k,
                strip_of="k")
    def _compute(tiles, masked):
        for rows, keys in tiles:
            q = q_ref[0, 0, rows, :]                       # [rows, D]
            do = do_ref[0, 0, rows, :]                     # [rows, D]
            st = _dot(k_ref[0, 0, keys, :], q, _NT) * scale   # [keys, rows]
            if masked:
                st = _causal_mask(st, i * block_q + rows.start,
                                  j * block_k + keys.start, q_axis=1)
            pt = jnp.exp(st - lse_ref[0, 0, :, rows])      # lse, delta: [1, rows]
            # dv += p^T dO
            dv_scr[keys, :] = dv_scr[keys, :] + _dot(pt.astype(do.dtype), do)
            dpt = _dot(v_ref[0, 0, keys, :], do, _NT)
            dst = pt * (dpt - delta_ref[0, 0, :, rows]) * scale
            dk_scr[keys, :] = dk_scr[keys, :] + _dot(dst.astype(q.dtype), q)

    @pl.when(i == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dq_scr, lse_scr, delta_scr, *,
               scale: float, causal: bool, block_q: int, block_k: int):
    i = pl.program_id(2)   # q block (outer)
    j = pl.program_id(3)   # kv block (inner sweep)
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)
        # lse, delta: [1, block_q] rows, turned once a q block and not at
        # every kv step into planes that hold a query's value on every lane
        lse_scr[:] = _on_lanes(lse_ref[0, 0])
        delta_scr[:] = _on_lanes(delta_ref[0, 0])

    @_when_live(i, j, causal=causal, block_q=block_q, block_k=block_k)
    def _compute(tiles, masked):
        for rows, keys in tiles:
            k = k_ref[0, 0, keys, :]
            s = _dot(q_ref[0, 0, rows, :], k, _NT) * scale
            if masked:
                s = _causal_mask(s, i * block_q + rows.start,
                                 j * block_k + keys.start, q_axis=0)
            p = jnp.exp(s - _lanes(lse_scr[rows, :], s.shape[1]))
            dp = _dot(do_ref[0, 0, rows, :], v_ref[0, 0, keys, :], _NT)
            ds = p * (dp - _lanes(delta_scr[rows, :], s.shape[1])) * scale
            dq_scr[rows, :] = dq_scr[rows, :] + _dot(ds.astype(k.dtype), k)

    @pl.when(j == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def flash_attention_backward(q, k, v, o, lse, do, *, causal: bool,
                             scale: float,
                             block_q: int | None = None,
                             block_k: int | None = None,
                             interpret: bool = False):
    """Gradients (dq, dk, dv) for [B,H,T,D] flash attention; `lse` as the
    forward kernel wrote it, [B, H, 1, T]."""
    B, H, T, D = q.shape
    # delta_t = sum_d dO * O — folds the softmax Jacobian; tiny elementwise op,
    # XLA fuses it, no need for a kernel. Born [B, H, T] and handed to both
    # kernels as the row [B, H, 1, T] that the log-sum-exp is.
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(-1)[:, :, None, :]

    kwargs = {} if interpret else dict(memory_space=pltpu.VMEM)

    # both backward grids are (B, H, outer, inner): blocks swept by the inner
    # loop index with `inner`, blocks fixed per outer step index with `o_idx`
    def inner_map(b, h, o_idx, inner):
        return (b, h, inner, 0)

    def outer_map(b, h, o_idx, inner):
        return (b, h, o_idx, 0)

    def inner_row_map(b, h, o_idx, inner):
        return (b, h, 0, inner)

    def outer_row_map(b, h, o_idx, inner):
        return (b, h, 0, o_idx)

    block_q, block_k = _blocks(T, block_q, block_k)
    dkv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        out_shape=(
            jax.ShapeDtypeStruct((B, H, T, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, T, D), q.dtype),
        ),
        grid=(B, H, T // block_k, T // block_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), inner_map, **kwargs),
            pl.BlockSpec((1, 1, block_k, D), outer_map, **kwargs),
            pl.BlockSpec((1, 1, block_k, D), outer_map, **kwargs),
            pl.BlockSpec((1, 1, block_q, D), inner_map, **kwargs),
            pl.BlockSpec((1, 1, 1, block_q), inner_row_map, **kwargs),
            pl.BlockSpec((1, 1, 1, block_q), inner_row_map, **kwargs),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, block_k, D), outer_map, **kwargs),
            pl.BlockSpec((1, 1, block_k, D), outer_map, **kwargs),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )
    # its scores are transposed, so a query's lse and delta lie along a row,
    # as they arrive
    dk, dv = dkv(q, k, v, do, lse, delta)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        out_shape=jax.ShapeDtypeStruct((B, H, T, D), q.dtype),
        grid=(B, H, T // block_q, T // block_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), outer_map, **kwargs),
            pl.BlockSpec((1, 1, block_k, D), inner_map, **kwargs),
            pl.BlockSpec((1, 1, block_k, D), inner_map, **kwargs),
            pl.BlockSpec((1, 1, block_q, D), outer_map, **kwargs),
            pl.BlockSpec((1, 1, 1, block_q), outer_row_map, **kwargs),
            pl.BlockSpec((1, 1, 1, block_q), outer_row_map, **kwargs),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), outer_map, **kwargs),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32),
                        pltpu.VMEM((block_q, 128), jnp.float32),
                        pltpu.VMEM((block_q, 128), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


def _reference_bhtd(q, k, v, *, causal: bool, scale: float):
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    if causal:
        T = q.shape[2]
        mask = jnp.tril(jnp.ones((T, T), dtype=bool))
        s = jnp.where(mask[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal: bool = True, scale: float | None = None,
                    block_q: int | None = None, block_k: int | None = None,
                    interpret: bool = False):
    """Differentiable flash attention, [B,H,T,D]. Forward and backward are
    Pallas kernels on TPU; neither materializes the [T,T] score tensor. Block
    sizes left None are chosen from T (`_BLOCK_CHOICES`)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    return flash_attention_forward(q, k, v, causal=causal, scale=scale,
                                   block_q=block_q, block_k=block_k,
                                   interpret=interpret)


def _fa_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    if scale is None:
        scale = q.shape[-1] ** -0.5
    out, lse = _fwd_call(q, k, v, causal=causal, scale=scale,
                         block_q=block_q, block_k=block_k, interpret=interpret)
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v, o, lse = res
    if scale is None:
        scale = q.shape[-1] ** -0.5
    return flash_attention_backward(q, k, v, o, lse, g, causal=causal,
                                    scale=scale, block_q=block_q,
                                    block_k=block_k, interpret=interpret)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


# ---------------------------------------------------------------- a chunk's
# continuation: the chunk's queries over a gathered span of earlier keys and
# then over the chunk's own (models/decoding_paged.py prefill_with_prefix).
# One launch a layer, the forward alone (nothing differentiates through it):
# the online softmax of `_fwd_kernel` (`_softmax_step`), two operands of keys
# swept one after the other, and which keys are live said ONCE, by
# `_live_keys`, for the kernel's block test, its masks and its index maps.
#
# Layout: heads-major, as the train path's kernels take theirs. q [H, Ts, D]
# and the chunk's K and V [Hkv, Ts, D] are the model's own products, whose
# order of dimensions XLA is free to choose; the gathered span [Tspan, Hkv, D]
# is re-laid once a layer to [Hkv, Tspan, D] (a token's KV heads lie packed
# in one tile as the pool stores them: no DMA takes one head out of that).
# K and V are never repeated: the G query heads of a KV head are one q block
# [G, block_q, D], multiplied one after the other against the block of keys
# while it is in VMEM (plane g of the scratch).

# Blocks in order of preference, the first that divides: 512 queries (G * 512
# rows of scratch a step) against 512 keys. Read on the chip (v5e, bfloat16,
# d_head 128; PR 47, `_scratch/kbench.py`): 512 queries lead 256 by 4-14 % at
# every shape of the cells (48 heads on 8 over a 16 k span, 9 k of it live:
# 4.06 against 4.64 ms; a window layer 1.43 / 1.61). Blocks of 128 queries and
# of 1,024 keys read 1.5 and 3.4 times slower in PR 46's scratch runs (VMEM)
# and were not read again.
_PREFIX_BLOCKS = (512, 256, 128)
# The shortest bucket that takes the launch. Every program that holds it pays
# for it at a replica's set-up, warm or cold: the kernel's body is traced and
# lowered anew for each (0.2 s a program here on the CPU, 0.3 s on the chip's
# host). With every tail's bucket in the launch `mixtral-8x7b.doc-saturated`'s
# twelve continuation programs read a warm `setup_s` of 35.1-35.7 s against
# the parent's 31.4-35.3 (PR 47; the bound is 10 %), for the one tail chunk a
# prompt has, whose scores are small in proportion: buckets under this keep
# the XLA form and the whole chunks take the launch.
_PREFIX_MIN_CHUNK = 1024


def prefix_blocks(chunk: int, span: int, head_dim: int):
    """(block_q, span_block, chunk_block) of the continuation's launch for a
    chunk of `chunk` queries over a gathered span of `span` keys, or None
    where the launch does not tile the shapes or does not pay: heads that are
    not whole 128-lane rows, a chunk under `_PREFIX_MIN_CHUNK` or no multiple
    of the smallest block of queries, a span that no block of keys divides (a
    span is pages, a power of two of them: a multiple of a block or, under the
    smallest, one block)."""
    def fit(n, under=None):
        return next((b for b in _PREFIX_BLOCKS if n % b == 0), under)

    blocks = (fit(chunk), fit(span, span if span < _PREFIX_BLOCKS[-1] else None),
              fit(chunk))
    if None in blocks or head_dim % 128 or chunk < _PREFIX_MIN_CHUNK:
        return None
    return blocks


def _live_keys(q_first, q_last, *, prefix_len, span: int, window: int | None,
               chunk: bool):
    """[first, last] of the keys of one operand that are live for the queries
    at chunk positions q_first .. q_last, in the sense that SOME of them sees
    the key (one query: that query's keys; given a block's last and first
    query in that order: the keys EVERY query of the block sees). None are
    where first > last.

    `chunk`: the chunk's own keys, key j at chunk position j, causal and
    within `window` of the query. Else the gathered span's, `span` long, of
    which a full layer (`window` None) holds the row's first `prefix_len`
    positions and then padding, and a window layer the `span` positions that
    end where the chunk starts (key j before it by span - j; before the row's
    start, prefix_len back: dead), within `window` of the query."""
    if chunk:
        first = 0 if window is None else jnp.maximum(q_first - window + 1, 0)
        return first, q_last
    if window is None:
        return 0, prefix_len - 1
    return (jnp.maximum(jnp.maximum(span - prefix_len, span + q_first - window + 1), 0),
            span - 1)


def _prefix_kernel(plen_ref, q_ref, sk_ref, sv_ref, ck_ref, cv_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, scale: float, window: int | None,
                   span: int, block_q: int, span_block: int, chunk_block: int):
    i, j = pl.program_id(1), pl.program_id(2)
    n_span = span // span_block
    group, D = acc_scr.shape[0], acc_scr.shape[2]
    q0 = i * block_q

    def each_head(body):
        """body(g) for the G query heads of this KV head, as a loop and not
        unrolled: the kernel's body is lowered anew for every program that
        holds the launch, which a replica's set-up pays."""
        jax.lax.fori_loop(0, group, lambda g, _: body(g), None)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def sweep(k_ref, v_ref, k0, block: int, chunk: bool):
        """The operand's keys k0 .. k0 + block - 1 against the q block: not
        at all where none of them is live for any of its queries, without a
        mask where all are for all."""
        live_keys = functools.partial(_live_keys, prefix_len=plen_ref[0], span=span,
                                      window=window, chunk=chunk)
        first, last = live_keys(q0, q0 + block_q - 1)
        first_all, last_all = live_keys(q0 + block_q - 1, q0)
        live = (k0 <= last) & (k0 + block - 1 >= first)
        whole = (k0 >= first_all) & (k0 + block - 1 <= last_all)

        def head(g, masked: bool):
            s = _dot(q_ref[g], k_ref[...], _NT) * scale     # [block_q, block]
            if masked:
                q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                lo, hi = live_keys(q_pos, q_pos)
                s = jnp.where((k_pos >= lo) & (k_pos <= hi), s, _NEG_INF)
            _softmax_step(s, v_ref[...], m_scr, l_scr, acc_scr, g)

        pl.when(live & jnp.logical_not(whole))(
            lambda: each_head(functools.partial(head, masked=True)))
        pl.when(whole)(lambda: each_head(functools.partial(head, masked=False)))

    @pl.when(j < n_span)
    def _span():
        sweep(sk_ref, sv_ref, j * span_block, span_block, False)

    @pl.when(j >= n_span)
    def _chunk():
        sweep(ck_ref, cv_ref, (j - n_span) * chunk_block, chunk_block, True)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        def head(g):
            l = jnp.maximum(l_scr[g], 1e-30)
            o_ref[g] = (acc_scr[g] / _lanes(l, D)).astype(o_ref.dtype)

        each_head(head)


@functools.partial(jax.jit, static_argnames=("scale", "window", "blocks", "interpret"))
def flash_prefix_attention(q, span_k, span_v, k, v, prefix_len, *, scale: float,
                           window: int | None = None, blocks: tuple | None = None,
                           interpret: bool = False):
    """A chunk's queries q [Ts, H, D] (query i at position prefix_len + i)
    over the gathered span `span_k`, `span_v` [Tspan, Hkv, D] and then the
    chunk's own `k`, `v` [Ts, Hkv, D]; `prefix_len` (a traced scalar: one
    program for every prefix of a span) and `window` say which keys are live
    (`_live_keys`). Returns [Ts, H, D]. A block of keys with no live key for a
    block of queries is neither multiplied nor brought (its index map stays on
    a live block). `blocks`: (block_q, span_block, chunk_block),
    `prefix_blocks`' where None."""
    (Ts, H, D), (span, Hkv, _) = q.shape, span_k.shape
    G = H // Hkv
    blocks = blocks or prefix_blocks(Ts, span, D)
    if blocks is None or Ts % blocks[0] or span % blocks[1] or Ts % blocks[2]:
        raise ValueError(f"blocks {blocks} do not tile a chunk of {Ts} over a "
                         f"span of {span} at heads of {D}")
    block_q, span_block, chunk_block = blocks
    n_span = span // span_block
    live_keys = functools.partial(_live_keys, span=span, window=window)

    def live_block(i, j, plen, block: int, chunk: bool):
        """Block j of the operand, or the nearest that holds a live key for
        q block i: a step that computes nothing brings nothing new."""
        first, last = live_keys(i * block_q, (i + 1) * block_q - 1,
                                prefix_len=plen[0], chunk=chunk)
        n = (Ts if chunk else span) // block
        return jnp.clip(j, jnp.minimum(first // block, n - 1),
                        jnp.minimum(jnp.maximum(last, 0) // block, n - 1))

    def q_map(h, i, j, plen):
        return (h, i, 0)

    def span_map(h, i, j, plen):
        return (h, live_block(i, j, plen, span_block, False), 0)

    def chunk_map(h, i, j, plen):
        return (h, live_block(i, j - n_span, plen, chunk_block, True), 0)

    kernel = functools.partial(
        _prefix_kernel, scale=scale, window=window, span=span, block_q=block_q,
        span_block=span_block, chunk_block=chunk_block)
    rows = G * block_q
    # q and o blocks and the key blocks, double-buffered, the accumulators,
    # and the scores of a head's step a few times over
    vmem = (4 * rows * D * q.dtype.itemsize
            + 4 * (span_block + chunk_block) * D * k.dtype.itemsize
            + rows * (256 + D) * 4 + 6 * block_q * max(span_block, chunk_block) * 4)

    def heads_major(t):
        return jnp.swapaxes(t, 0, 1)

    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((H, Ts, D), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                         # prefix_len
            grid=(Hkv, Ts // block_q, n_span + Ts // chunk_block),
            in_specs=[pl.BlockSpec((G, block_q, D), q_map),
                      pl.BlockSpec((None, span_block, D), span_map),
                      pl.BlockSpec((None, span_block, D), span_map),
                      pl.BlockSpec((None, chunk_block, D), chunk_map),
                      pl.BlockSpec((None, chunk_block, D), chunk_map)],
            out_specs=pl.BlockSpec((G, block_q, D), q_map),
            scratch_shapes=[pltpu.VMEM((G, block_q, 128), jnp.float32),
                            pltpu.VMEM((G, block_q, 128), jnp.float32),
                            pltpu.VMEM((G, block_q, D), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=max(32 * 2**20, 2 * vmem)),
        interpret=interpret,
        name="flash_prefix_attention",
    )(jnp.reshape(prefix_len, (1,)).astype(jnp.int32),
      *(heads_major(t) for t in (q, span_k, span_v, k, v)))
    return heads_major(out)
