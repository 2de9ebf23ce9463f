"""Grouped matrix product of the sorted expert dispatch (Pallas TPU).

`grouped_matmul(lhs [M, K], rhs [G, K, N], group_sizes [G]) -> [M, N]`: the
rows of `lhs` lie sorted by group, `group_sizes[g]` consecutive rows belong to
group g, and each row is multiplied by its group's matrix. What
`jax.lax.ragged_dot` computes, which stays the form every platform but the TPU
lowers, the backward pass, and the kernel's oracle (tests/test_grouped_matmul.py,
`chip_smoke.py`).

The kernel (after the pattern of jax's `megablox.gmm`): the rows are cut into
tiles of `tm`, and a grid step is one (row tile, group) pair that shares rows,
laid out group by group over the groups that HAVE rows, so an empty group costs
nothing however many there are (a layer scan hands over the experts of all L
layers, `[L*E, K, N]`, with one layer's sizes non-zero: `ops.moe_sorted`, which
says so by `groups_with_rows`). The step's index maps come from that schedule by
scalar prefetch: the row tile of `lhs` and of the output, and the group's
`[K, tn]` block of `rhs`, whole in K, so that the consecutive steps of one group
name the same block and the pipeline fetches it once. A call therefore reads the
experts' weights once and `lhs` once a column tile. A row tile that several
groups share is visited by each in turn and each stores only its own rows; rows
past the groups' sum come back zero, or, with `rows_past="skip"`, take no step
at all and come back as whatever the buffer held (an expert layer that holds a
share of the experts sorts the slots of the absent ones there and masks them:
`ops.moe_sorted`).

bfloat16 operands are multiplied as stored, summed in float32 and rounded once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# what one call's blocks may take of the chip's 128 MiB of VMEM, and the most a
# `[K, tn]` block of weights may (it is held twice: the pipeline's two buffers)
_VMEM_LIMIT = 100 * 2**20
_RHS_BLOCK_BYTES = 16 * 2**20


def _largest_tile(n: int, at_most: int) -> int:
    """The largest multiple of 128 that divides `n` and is <= `at_most`; `n`
    itself where it has no such divisor (a block may span a whole dimension)."""
    for t in range(min(at_most, n) // 128 * 128, 0, -128):
        if n % t == 0:
            return t
    return n


def tiles_for(M: int, K: int, N: int, groups: int, itemsize: int = 2) -> tuple[int, int, int]:
    """(tm, tk, tn) from the shapes of a call whose rows lie in at most `groups`
    groups.

    tn: as wide as the weight block's share of VMEM allows: `lhs` is read once a
    column tile, so a narrow tile multiplies that traffic (Mixtral's down
    projection, K 14,336: `lhs` is 59 MB a pass). tk: the in-kernel step over K
    (the block itself is whole in K). tm: the rows of a step. A row tile that two
    groups share is multiplied once for each, so a tile taller than a group
    multiplies mostly rows that are masked away: 128, the MXU's own height, while
    a group has under 256 rows on average (`kimi-vl-a3b`: 96, and the call is
    bound by the weight read). From 256 rows a group, 256: the next group's block
    is fetched one step ahead, and 256 rows of products are what hide that fetch
    on a v5e (its knee, 240 rows a weight matrix; Mixtral: 256 rows a group)."""
    tn = _largest_tile(N, max(_RHS_BLOCK_BYTES // (K * itemsize), 128))
    tk = _largest_tile(K, 4096)
    tm = 256 if M // groups >= 256 else min(M, 128)
    return tm, tk, tn


def _schedule(group_sizes, M: int, tm: int, groups: int, tail_group: bool = True):
    """Per grid step: (group, row tile, first row, row past the last) of the
    (row tile, group) pairs that share rows, group by group, and their number.
    Rows past the groups' sum are one more group that stores zeros (`tail_group`),
    or take no step (then a call none of whose groups has a row still takes
    one, which stores zeros in the first tile: a grid is never empty)."""
    G = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    if tail_group:
        sizes = jnp.concatenate([sizes, M - ends[-1:]])
        ends = jnp.concatenate([ends, jnp.full((1,), M, jnp.int32)])
    else:
        sizes = jnp.concatenate([sizes, jnp.zeros((1,), jnp.int32)])
        ends = jnp.concatenate([ends, ends[-1:]])
    starts = ends - sizes
    first_tile = starts // tm
    n_tiles = jnp.where(sizes > 0, (ends - 1) // tm - first_tile + 1, 0)
    tile_ends = jnp.cumsum(n_tiles)
    # a group with rows takes the tiles it spans: at most one more than the
    # tiles there are, for each such group after the first
    steps = jnp.arange(pl.cdiv(M, tm) + min(groups, M - 1), dtype=jnp.int32)
    g = jnp.minimum(jnp.sum(steps[:, None] >= tile_ends[None, :], axis=1), G)
    tile = first_tile[g] + steps - (tile_ends[g] - n_tiles[g])
    tail = g == G
    lo = jnp.where(tail, M, starts[g])
    n_steps = tile_ends[-1] if tail_group else jnp.maximum(tile_ends[-1], 1)
    return jnp.minimum(g, G - 1), tile, lo, jnp.where(tail, M, ends[g]), n_steps


def _kernel(gid_ref, tile_ref, lo_ref, hi_ref, lhs_ref, rhs_ref, out_ref, *, tk: int):
    del gid_ref  # the index map of rhs reads it
    t = pl.program_id(1)
    tm, tn = out_ref.shape
    K = lhs_ref.shape[1]
    tile = tile_ref[t]
    # the pipeline keeps the output block while consecutive steps name it: the
    # first of them finds what the buffer last held
    first = jnp.logical_or(t == 0, tile_ref[jnp.maximum(t - 1, 0)] != tile)
    kept = jnp.where(first, jnp.zeros_like(out_ref), out_ref[...])

    def chunk(i, acc):
        k0 = pl.multiple_of(i * tk, tk)
        return acc + jnp.dot(lhs_ref[:, pl.ds(k0, tk)], rhs_ref[pl.ds(k0, tk), :],
                             preferred_element_type=jnp.float32)

    if K == tk:
        acc = jnp.dot(lhs_ref[...], rhs_ref[...], preferred_element_type=jnp.float32)
    else:
        acc = jax.lax.fori_loop(0, K // tk, chunk, jnp.zeros((tm, tn), jnp.float32))
    rows = tile * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 0)
    mine = jnp.logical_and(rows >= lo_ref[t], rows < hi_ref[t])
    out_ref[...] = jnp.where(mine, acc.astype(out_ref.dtype), kept)


def grouped_matmul_kernel(lhs, rhs, group_sizes, groups_with_rows: int | None = None,
                          rows_past: str = "zero", *, tiles=None, interpret: bool = False):
    """The Pallas kernel itself (`grouped_matmul` picks it on the TPU); `tiles`
    (tm, tk, tn) for a sweep, by default `tiles_for` the shapes."""
    (M, K), (G, _, N) = lhs.shape, rhs.shape
    groups = min(groups_with_rows or G, G)
    itemsize = jnp.dtype(lhs.dtype).itemsize
    # (a caller whose rows are spread over more groups than it holds says so
    # by `groups_with_rows` above G: the tiles follow the rows a group)
    tm, tk, tn = tiles or tiles_for(M, K, N, max(groups_with_rows or G, groups), itemsize)
    gid, tile, lo, hi, n_steps = _schedule(group_sizes, M, tm, groups, rows_past != "skip")
    blocks = 2 * (tm * K + K * tn + tm * tn) * itemsize + 3 * tm * tn * 4
    return pl.pallas_call(
        functools.partial(_kernel, tk=tk),
        out_shape=jax.ShapeDtypeStruct((M, N), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(pl.cdiv(N, tn), n_steps),
            in_specs=[
                pl.BlockSpec((tm, K), lambda n, t, gid, tile, lo, hi: (tile[t], 0)),
                pl.BlockSpec((None, K, tn), lambda n, t, gid, tile, lo, hi: (gid[t], 0, n)),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda n, t, gid, tile, lo, hi: (tile[t], n)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=min(max(blocks + 8 * 2**20, 32 * 2**20), _VMEM_LIMIT)),
        cost_estimate=pl.CostEstimate(
            flops=2 * M * K * N, transcendentals=0,
            bytes_accessed=(min(groups, M) * K * N + pl.cdiv(N, tn) * M * K + M * N) * itemsize),
        interpret=interpret,
        name="grouped_matmul",
    )(gid, tile, lo, hi, lhs, rhs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def grouped_matmul(lhs, rhs, group_sizes, groups_with_rows: int | None = None,
                   rows_past: str = "zero"):
    """lhs [M, K] x rhs [G, K, N] by `group_sizes` [G] -> [M, N]: the kernel
    where the program is lowered for a TPU, `jax.lax.ragged_dot` elsewhere.
    `groups_with_rows`: at most so many groups have rows, where the caller knows
    (of a stack's L*E groups one layer's E): the tiles follow the rows a group.
    A layer that holds a share of the experts gives the experts its rows were
    routed over (more than it holds: most rows lie past its groups).
    `rows_past`: the rows past the groups' sum come back "zero", or with "skip"
    undefined (the kernel takes no step for them; the caller masks them)."""
    return jax.lax.platform_dependent(
        lhs, rhs, group_sizes, default=jax.lax.ragged_dot,
        tpu=functools.partial(grouped_matmul_kernel, groups_with_rows=groups_with_rows,
                              rows_past=rows_past))


def _fwd(lhs, rhs, group_sizes, groups_with_rows, rows_past):
    return (grouped_matmul(lhs, rhs, group_sizes, groups_with_rows, rows_past),
            (lhs, rhs, group_sizes))


def _bwd(_, __, res, g):
    lhs, rhs, group_sizes = res
    _, vjp = jax.vjp(lambda a, b: jax.lax.ragged_dot(a, b, group_sizes), lhs, rhs)
    return (*vjp(g), np.zeros(group_sizes.shape, jax.dtypes.float0))


grouped_matmul.defvjp(_fwd, _bwd)
