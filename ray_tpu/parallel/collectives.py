"""Device collectives: thin names over XLA's, usable inside shard_map/jit.

TPU-native replacement for the reference's actor-attached NCCL collectives
(reference: python/ray/util/collective/collective.py:325-738 — allreduce/
reduce/broadcast/allgather/reducescatter/send/recv/barrier over NCCL).
Here the collectives are *in-program*: XLA schedules them on ICI, overlapped
with compute. Host-side (CPU tensor) collectives over actor groups live in
ray_tpu.util.collective instead.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def pvary(x, axes):
    """Mark x as varying over `axes` (shard_map's vma typing)."""
    return lax.pcast(x, axes, to="varying")


def zeros_varying_like(shape, dtype, ref):
    """Zeros of `shape` carrying `ref`'s varying-manual-axes type (vma), so
    scan carries initialized from constants type-check under shard_map."""
    return jnp.zeros(shape, dtype) + (ref.ravel()[0] * 0).astype(dtype)


def allreduce(x, axis_name: str):
    return lax.psum(x, axis_name)


def allreduce_mean(x, axis_name: str):
    return lax.pmean(x, axis_name)


def reducescatter(x, axis_name: str, *, scatter_dimension: int = 0, tiled: bool = True):
    return lax.psum_scatter(x, axis_name, scatter_dimension=scatter_dimension, tiled=tiled)


def allgather(x, axis_name: str, *, axis: int = 0, tiled: bool = True):
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def broadcast(x, axis_name: str, *, root: int = 0):
    """Every member gets root's value (select + psum keeps it one collective)."""
    idx = lax.axis_index(axis_name)
    masked = jnp.where(idx == root, x, jnp.zeros_like(x))
    return lax.psum(masked, axis_name)


def ring_permute(x, axis_name: str, *, shift: int = 1):
    n = axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm)


def all_to_all(x, axis_name: str, *, split_axis: int, concat_axis: int, tiled: bool = True):
    return lax.all_to_all(x, axis_name, split_axis=split_axis, concat_axis=concat_axis, tiled=tiled)


def axis_index(axis_name: str):
    return lax.axis_index(axis_name)


def axis_size(axis_name: str):
    """STATIC size of a named mesh axis from inside shard_map."""
    return lax.axis_size(axis_name)
