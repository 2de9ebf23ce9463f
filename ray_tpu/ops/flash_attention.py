"""Flash attention forward + backward kernels for TPU (Pallas).

Blocked online-softmax attention: forward grid (B, H, nq, nk) with the kv
dimension innermost so the f32 accumulators live in VMEM scratch across kv
steps and the MXU sees [block_q, D] x [D, block_k] matmuls. Causal blocks
above the diagonal are skipped via predication. The forward also emits the
per-row logsumexp so the backward never rebuilds the softmax normalizer.

Backward is the standard two-kernel flash decomposition (no [T, T] score
tensor is ever materialized):
  - dkv kernel, grid (B, H, nk, nq): for a fixed kv block, sweep q blocks
    accumulating dv += p^T dO and dk += ds^T q in VMEM scratch.
  - dq kernel, grid (B, H, nq, nk): for a fixed q block, sweep kv blocks
    accumulating dq += ds k.
where p = exp(s - lse) is recomputed blockwise from the saved logsumexp and
delta = rowsum(dO * O) folds the softmax Jacobian into ds = p * (dp - delta).

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
DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 256


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

    # causal: kv block j is live iff its first key position <= last q position
    live = (j * block_k <= (i + 1) * block_q - 1) if causal else True

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # [block_q, D]
        k = k_ref[0, 0].astype(jnp.float32)          # [block_k, D]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                     # [block_q, block_k]
        if causal:
            q_pos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        m_prev = m_scr[:, :1]                         # [block_q, 1]
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0, 0] = (acc_scr[:] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_scr[:, :1] + jnp.log(l)


def _fwd_call(q, k, v, *, causal: bool, scale: float, block_q: int,
              block_k: int, interpret: bool):
    B, H, T, D = q.shape
    block_q = min(block_q, T)
    block_k = min(block_k, T)
    if T % block_q or T % block_k:
        raise ValueError(f"T={T} must be divisible by block sizes {block_q},{block_k}")
    nq, nk = T // block_q, T // block_k
    grid = (B, H, nq, nk)

    def qo_map(b, h, i, j):
        return (b, h, i, 0)

    def kv_map(b, h, i, j):
        return (b, h, j, 0)

    def lse_map(b, h, i, j):
        return (b, h, i, 0)

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
            jax.ShapeDtypeStruct((B, H, T, 1), jnp.float32),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), qo_map, **kwargs),
            pl.BlockSpec((1, 1, block_k, D), kv_map, **kwargs),
            pl.BlockSpec((1, 1, block_k, D), kv_map, **kwargs),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, block_q, D), qo_map, **kwargs),
            pl.BlockSpec((1, 1, block_q, 1), lse_map, **kwargs),
        ),
        scratch_shapes=scratch,
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)


def flash_attention_forward(q, k, v, *, causal: bool = True,
                            scale: float | None = None,
                            block_q: int = DEFAULT_BLOCK_Q,
                            block_k: int = DEFAULT_BLOCK_K,
                            interpret: bool = False):
    """q,k,v: [B, H, T, D] (heads-major). Returns [B, H, T, D]."""
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

    live = ((i + 1) * block_q - 1 >= j * block_k) if causal else True

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)            # [bk, D]
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)          # [bq, D]
        lse = lse_ref[0, 0]                            # [bq, 1]
        delta = delta_ref[0, 0]                        # [bq, 1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                       # [bq, bk]
        if causal:
            q_pos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse)                            # [bq, bk]
        # dv += p^T dO
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale                   # [bq, bk]
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(i == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dq_scr, *,
               scale: float, causal: bool, block_q: int, block_k: int):
    i = pl.program_id(2)   # q block (outer)
    j = pl.program_id(3)   # kv block (inner sweep)
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    live = (j * block_k <= (i + 1) * block_q - 1) if causal else True

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if causal:
            q_pos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale                   # [bq, bk]
        dq_scr[:] = dq_scr[:] + jnp.dot(
            ds, k, preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def flash_attention_backward(q, k, v, o, lse, do, *, causal: bool,
                             scale: float,
                             block_q: int = DEFAULT_BLOCK_Q,
                             block_k: int = DEFAULT_BLOCK_K,
                             interpret: bool = False):
    """Gradients (dq, dk, dv) for [B,H,T,D] flash attention."""
    B, H, T, D = q.shape
    block_q = min(block_q, T)
    block_k = min(block_k, T)
    nq, nk = T // block_q, T // block_k
    # delta_t = sum_d dO * O — folds the softmax Jacobian; tiny elementwise op,
    # XLA fuses it, no need for a kernel.
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(-1, keepdims=True)  # [B,H,T,1]

    kwargs = {} if interpret else dict(memory_space=pltpu.VMEM)

    # both backward grids are (B, H, outer, inner): blocks swept by the inner
    # loop index with `inner`, blocks fixed per outer step index with `o_idx`
    def inner_map(b, h, o_idx, inner):
        return (b, h, inner, 0)

    def outer_map(b, h, o_idx, inner):
        return (b, h, o_idx, 0)

    dkv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        out_shape=(
            jax.ShapeDtypeStruct((B, H, T, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, T, D), q.dtype),
        ),
        grid=(B, H, nk, nq),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), inner_map, **kwargs),
            pl.BlockSpec((1, 1, block_k, D), outer_map, **kwargs),
            pl.BlockSpec((1, 1, block_k, D), outer_map, **kwargs),
            pl.BlockSpec((1, 1, block_q, D), inner_map, **kwargs),
            pl.BlockSpec((1, 1, block_q, 1), inner_map, **kwargs),
            pl.BlockSpec((1, 1, block_q, 1), inner_map, **kwargs),
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
    dk, dv = dkv(q, k, v, do, lse, delta)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        out_shape=jax.ShapeDtypeStruct((B, H, T, D), q.dtype),
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), outer_map, **kwargs),
            pl.BlockSpec((1, 1, block_k, D), inner_map, **kwargs),
            pl.BlockSpec((1, 1, block_k, D), inner_map, **kwargs),
            pl.BlockSpec((1, 1, block_q, D), outer_map, **kwargs),
            pl.BlockSpec((1, 1, block_q, 1), outer_map, **kwargs),
            pl.BlockSpec((1, 1, block_q, 1), outer_map, **kwargs),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), outer_map, **kwargs),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
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
                    block_q: int = DEFAULT_BLOCK_Q, block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = False):
    """Differentiable flash attention, [B,H,T,D]. Forward and backward are
    Pallas kernels on TPU; neither materializes the [T,T] score tensor."""
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
