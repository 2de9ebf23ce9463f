"""Attention dispatcher: picks the best implementation for the platform.

Models call `attention(q, k, v, ...)` with [B, T, H, D] activations (GQA
allowed: fewer KV heads). On TPU the Pallas flash kernel runs; elsewhere (or
for odd shapes) the XLA reference path does — the same numerics to the
rounding of the operands' dtype (flash_attention.py, "Precision"), so tests
on the CPU mesh validate the model code that the TPU executes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ray_tpu._private.constants import (MESH_AXIS_DP, MESH_AXIS_FSDP,
                                        MESH_AXIS_TP)
from ray_tpu.ops.flash_attention import (_fwd_call, flash_attention,
                                         flash_attention_backward)
from ray_tpu.parallel.ring_attention import reference_attention, ring_attention


def repeat_kv(k, *, n_rep: int):
    """[B, T, Hkv, D] → [B, T, Hkv*n_rep, D] by repeating each kv head."""
    if n_rep == 1:
        return k
    B, T, Hkv, D = k.shape
    return jnp.repeat(k, n_rep, axis=2)


# what `_flash`'s forward rule names (jax.ad_checkpoint.checkpoint_name) for
# a layer's checkpoint to keep: the kernel's output and log-sum-exp, all the
# backward kernels need of the forward besides q, k and v
FLASH_KEPT = ("flash_out", "flash_lse")


def _flash_ok(q) -> bool:
    if q.shape[1] % 256 != 0:  # seq must tile into flash blocks
        return False
    # measured on v5e with the kernels as they were before PR 31 (a sweep at
    # b8 h16 d128 whose script went in PR 48): the Pallas kernel won from seq
    # 1024 up once fwd AND bwd were kernels — 2.4x at s2048 (12.96 vs 31.22 ms
    # fwd+bwd); PR 31's run 1.4 to 2.6 times faster than those and the
    # threshold was not measured again — and it is the only path that runs at
    # s4096+ (XLA's quadratic score tensor OOMs HBM)
    return jax.default_backend() == "tpu" and q.shape[1] >= 1024


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal: bool, scale: float | None, interpret: bool = False):
    """The flash kernels on the model's own [B, T, H, D]. Where nothing
    differentiates this is `flash_attention` between two transpositions; where
    something does, the rule below is the kernels' differentiation boundary,
    so that what the backward pass needs of the forward has a name a layer's
    checkpoint can keep (models/transformer.py `forward`)."""
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    # block sizes: the kernels' own choice for this T (flash_attention.py)
    out = flash_attention(qt, kt, vt, causal, scale, None, None, interpret)
    return out.transpose(0, 2, 1, 3)


def _flash_fwd(q, k, v, causal, scale, interpret):
    B, T, H, D = q.shape
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    out, lse = _fwd_call(
        qt, kt, vt, causal=causal, scale=D ** -0.5 if scale is None else scale,
        block_q=None, block_k=None, interpret=interpret)
    # named lane-dense: in the kernel's [B, H, T, 64] a stack of layers pads
    # 64 lanes to 128. The log-sum-exp is lane-dense as the kernel writes it,
    # rows [B, H, 1, T], and is named, stacked and handed back to the backward
    # kernels as that very array: as [B, H, T] the compiler re-tiled it twice
    # a backward layer, and as the column [B, H, T, 1] it had until PR 58 (one
    # value a 128-lane row, 42 MB for 0.33) the re-laying cost 127 us a layer
    # of gpt2-large's step (PERF.md section 5)
    out = checkpoint_name(out.transpose(0, 2, 1, 3).reshape(B, T, H * D), FLASH_KEPT[0])
    lse = checkpoint_name(lse, FLASH_KEPT[1])
    return out.reshape(B, T, H, D), (q, k, v, out, lse)


def _flash_bwd(causal, scale, interpret, res, g):
    q, k, v, out, lse = res
    B, T, H, D = q.shape
    qt, kt, vt, ot, gt = (x.transpose(0, 2, 1, 3)
                          for x in (q, k, v, out.reshape(B, T, H, D), g))
    grads = flash_attention_backward(
        qt, kt, vt, ot, lse, gt, causal=causal,
        scale=D ** -0.5 if scale is None else scale, interpret=interpret)
    return tuple(x.transpose(0, 2, 1, 3) for x in grads)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _flash_per_shard(q, k, v, *, causal: bool, scale: float | None):
    """GSPMD cannot partition a Mosaic kernel, so under a multi-device mesh
    the kernel runs per shard inside shard_map: batch over the data axes,
    heads over tp (the activation rules of parallel/mesh.py), whichever of
    them the ambient mesh names. The caller makes the mesh ambient —
    `train/spmd.py` and the engine do; without one the compiler refuses the
    sharded program by name rather than replicating it."""
    mesh = jax.sharding.get_abstract_mesh()
    # every axis must be manual around the kernel; those an enclosing
    # shard_map already made manual (pp, sp programs) stay as they are
    names = frozenset(mesh.axis_names) - frozenset(mesh.manual_axes)
    if mesh.empty or mesh.size == 1 or not names:
        return _flash(q, k, v, causal, scale)
    batch = tuple(a for a in (MESH_AXIS_DP, MESH_AXIS_FSDP) if a in names)
    heads = MESH_AXIS_TP if MESH_AXIS_TP in names else None
    spec = P(batch or None, None, heads, None)
    return jax.shard_map(
        functools.partial(_flash, causal=causal, scale=scale), mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=spec, axis_names=names,
        check_vma=False)(q, k, v)


# float32 scores of every head at once, [B, H, T, T], up to this many bytes;
# over it the masked window attention takes one KV head's group of query heads
# after another (48 heads over a bucket of 8,192: 12.9 GB at once, 1.6 GB a
# group of 6), as a chunk's continuation does (models/decoding_paged.py)
_SCORES_AT_ONCE = 2 << 30


def _window_attention(q, k, v, *, scale, window: int):
    """The masked XLA attention of a sequence longer than its window."""
    (B, T, H, D), Hkv = q.shape, k.shape[2]
    G = H // Hkv
    if 4 * B * H * T * T <= _SCORES_AT_ONCE:
        return reference_attention(q, repeat_kv(k, n_rep=G), repeat_kv(v, n_rep=G),
                                   causal=True, scale=scale, window=window)

    def group(one):
        qg, kg, vg = one                                   # [B, T, G, D], [B, T, D] x 2
        return reference_attention(qg, repeat_kv(kg[:, :, None], n_rep=G),
                                   repeat_kv(vg[:, :, None], n_rep=G),
                                   causal=True, scale=scale, window=window)

    out = jax.lax.map(group, (jnp.moveaxis(q.reshape(B, T, Hkv, G, D), 2, 0),
                              jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)))
    return jnp.moveaxis(out, 0, 2).reshape(B, T, H, D)


def attention(q, k, v, *, causal: bool = True, scale: float | None = None,
              sp_axis: str | None = None, impl: str | None = None,
              window: int | None = None):
    """q: [B, T, H, D]; k, v: [B, T, Hkv, D]. Returns [B, T, H, D].

    impl: None=auto, "flash", "reference". sp_axis: when set, runs ring
    attention over that mesh axis (inputs must be sequence-sharded and the
    call made inside shard_map). window: query i sees key j iff
    0 <= i - j < window; a sequence no longer than the window is the plain
    causal case, a longer one takes the masked XLA path (the flash kernels
    carry no window), a KV head's group at a time where the scores of all heads
    at once would pass `_SCORES_AT_ONCE`.
    """
    H, Hkv = q.shape[2], k.shape[2]
    if H % Hkv != 0:
        raise ValueError(f"q heads {H} not a multiple of kv heads {Hkv}")
    if window is not None and window < q.shape[1]:
        if not causal or sp_axis is not None or impl == "flash":
            raise ValueError("a window is causal, unsharded over the sequence "
                             "and not in the flash kernels")
        return _window_attention(q, k, v, scale=scale, window=window)
    k = repeat_kv(k, n_rep=H // Hkv)
    v = repeat_kv(v, n_rep=H // Hkv)

    if sp_axis is not None:
        return ring_attention(q, k, v, axis_name=sp_axis, causal=causal, scale=scale)

    if impl == "flash" or (impl is None and _flash_ok(q)):
        return _flash_per_shard(q, k, v, causal=causal, scale=scale)
    return reference_attention(q, k, v, causal=causal, scale=scale)
