"""A chunk's continuation in the flash launch (ops/flash_attention.py
`flash_prefix_attention`, interpret mode on the CPU) against `attend`'s
float32 `jax.numpy` form (models/decoding_paged.py prefill_with_prefix): the
same truth table of live keys for a full layer (a gathered span of which the
first `prefix_len` keys are real) and a window layer (the positions that end
where the chunk starts, within the window and not before the row's start).
"""

import importlib
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import flash_prefix_attention, prefix_blocks

pytestmark = pytest.mark.pd

BLOCKS = (16, 16, 16)       # of queries, of the span's keys, of the chunk's
HKV = 2


def _mask(Ts, span, prefix_len, window):
    """`prefill_with_prefix`'s masks, [Ts, span + Ts]."""
    if window is None:
        prefix = jnp.broadcast_to(jnp.arange(span)[None, :] < prefix_len, (Ts, span))
        causal = jnp.arange(Ts)[:, None] >= jnp.arange(Ts)[None, :]
        return jnp.concatenate([prefix, causal], axis=1)
    kpos = jnp.concatenate([jnp.arange(span) - span, jnp.arange(Ts)])[None, :]
    qpos = jnp.arange(Ts)[:, None]
    return (kpos <= qpos) & (qpos - kpos < window) & (kpos >= -prefix_len)


def _attend(q, span_k, span_v, k, v, prefix_len, scale, window):
    """`attend` on the same values, computed and returned in float32."""
    (Ts, H, D), Hkv = q.shape, k.shape[1]
    f32 = lambda t: jnp.asarray(t, jnp.float32)  # noqa: E731
    k_all = jnp.concatenate([f32(span_k), f32(k)])
    v_all = jnp.concatenate([f32(span_v), f32(v)])
    scores = jnp.einsum("tkgd,skd->tkgs", f32(q).reshape(Ts, Hkv, H // Hkv, D), k_all) * scale
    mask = _mask(Ts, span_k.shape[0], prefix_len, window)
    w = jax.nn.softmax(jnp.where(mask[:, None, None, :], scores, -1e30), axis=-1)
    return jnp.einsum("tkgs,skd->tkgd", w, v_all).reshape(Ts, H, D)


def _case(seed, Ts, span, G, D, dtype):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.standard_normal(s), dtype) for s in
            [(Ts, HKV * G, D)] + [(span, HKV, D)] * 2 + [(Ts, HKV, D)] * 2]


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


# name: (window, span, prefix_len). A full layer's span is four blocks of
# keys; a window layer's is the positions that end where the chunk starts.
KINDS = {
    # a prefix of nothing, one that ends inside a block, a whole span, and
    # one short of a span that is mostly padding
    "full-none": (None, 64, 0), "full-mid-block": (None, 64, 23),
    "full-whole-span": (None, 64, 64), "full-mostly-padding": (None, 64, 5),
    # the window the span's length on a long row (every key of the span real)
    "window-long-row": (32, 32, 200), "window-whole-span": (32, 32, 32),
    # a row younger than its window: it starts inside the gathered span, at
    # its very end, or has no prefix at all
    "window-young-row": (32, 32, 23), "window-one-key": (32, 32, 1),
    "window-none": (32, 32, 0),
    # a window shorter than the span, and one longer than prefix and chunk
    # together (nothing is cut)
    "window-under-span": (16, 32, 200), "window-over-all": (256, 32, 29),
}
# every kind at groups of 1, 4 and 6 query heads a KV head, chunks of one
# and three blocks; then a scale that is not D ** -0.5, and a chunk whose
# true length lies under its bucket (the keys past it are noise, the rows up
# to it are compared)
CASES = [dict(kind=kind, G=G, chunk=chunk)
         for kind, G, chunk in itertools.product(KINDS, (1, 4, 6), (16, 48))]
CASES += [dict(kind=kind, G=4, chunk=48, scale=0.3)
          for kind in ("full-mid-block", "window-young-row")]
CASES += [dict(kind=kind, G=6, chunk=48, length=length)
          for kind in ("full-mid-block", "window-long-row") for length in (1, 20, 37)]


def _id(case):
    return "-".join(f"{k}{v}" if k != "kind" else v for k, v in case.items())


@pytest.mark.parametrize("case", CASES, ids=_id)
def test_launch_agrees_with_attend(case):
    """float32 operands to 1e-5. bfloat16 operands to bfloat16 rounding: the
    launch rounds `p` and its result (flash_attention.py, "Precision"), which
    costs 1.25 times what rounding the reference itself does on a causal
    sweep (2e-3 of the norm, the header's reading) and up to 1.4 times where
    every query has a span of keys before it and none has only a few (the two
    roundings in quadrature, read 1.30 to 1.36)."""
    window, span, prefix_len = KINDS[case["kind"]]
    G, chunk, length = case["G"], case["chunk"], case.get("length")
    for dtype, D in ((jnp.float32, 16), (jnp.bfloat16, 32)):
        scale = case.get("scale", D ** -0.5)
        q, sk, sv, k, v = _case(0, chunk, span, G, D, dtype)
        got = flash_prefix_attention(q, sk, sv, k, v, jnp.int32(prefix_len), scale=scale,
                                     window=window, blocks=BLOCKS, interpret=True)
        assert got.shape == q.shape and got.dtype == dtype
        if length is not None:  # the real tokens' keys alone, the real rows
            k, v = k[:length], v[:length]
            got, q = got[:length], q[:length]
        want = _attend(q, sk, sv, k, v, prefix_len, scale, window)
        if dtype == jnp.float32:
            np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
        else:
            rounding = _rel(want.astype(jnp.bfloat16), want)
            assert _rel(got, want) <= max(2e-3, 1.4 * rounding)


@pytest.mark.parametrize("window,span,prefix_len", [
    (None, 64, 20), (32, 32, 5), (16, 32, 200)],
    ids=["padding", "before-the-row", "before-the-window"])
def test_a_block_without_a_live_key_is_skipped_not_masked(window, span, prefix_len):
    """NaN in every key and value of the blocks in which no key is live for
    any query (the span's padding; on a window layer what lies before the
    row's start or more than the window before the chunk's first query): the
    result is what it was, bit for bit. Masked, 0 * NaN would have been NaN."""
    q, sk, sv, k, v = _case(1, 48, span, 4, 16, jnp.float32)
    call = lambda sk, sv: flash_prefix_attention(  # noqa: E731
        q, sk, sv, k, v, jnp.int32(prefix_len), scale=0.25, window=window,
        blocks=BLOCKS, interpret=True)
    live = np.asarray(_mask(48, span, prefix_len, window))[:, :span].any(axis=0)
    dead = ~live.reshape(-1, 16).any(axis=1)            # by block of the span
    assert dead.any() and not dead.all()
    poison = jnp.asarray(np.repeat(dead, 16))[:, None, None]
    got = call(jnp.where(poison, jnp.nan, sk), jnp.where(poison, jnp.nan, sv))
    assert np.array_equal(np.asarray(got), np.asarray(call(sk, sv)))
    assert np.isfinite(np.asarray(got)).all()


@pytest.mark.parametrize("window,span", [(None, 64), (32, 32)], ids=["full", "window"])
def test_one_program_serves_every_prefix_of_a_span(window, span):
    """`prefix_len` is a traced scalar: the lengths of one span bucket share
    a program, as the engine's count of programs a cell warms has it."""
    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    q, sk, sv, k, v = _case(2, 16, span, 1, 16, jnp.float32)
    before = fa.flash_prefix_attention._cache_size()
    for prefix_len in (0, 7, 19, span):
        flash_prefix_attention(q, sk, sv, k, v, jnp.int32(prefix_len), scale=0.25,
                               window=window, blocks=BLOCKS, interpret=True)
    assert fa.flash_prefix_attention._cache_size() - before <= 1


def test_the_blocks_come_from_the_shapes():
    """Heads of whole 128-lane rows and a bucket of 1,024 or more in whole
    blocks of queries take the launch; a span under a block of keys is one
    block."""
    assert prefix_blocks(2048, 32768, 128) == prefix_blocks(2048, 4096, 128)
    block_q, span_block, chunk_block = prefix_blocks(1024, 64, 128)
    assert 1024 % block_q == 0 and span_block == 64 and 1024 % chunk_block == 0
    assert prefix_blocks(1024, 8192, 64) is None        # heads of 64
    assert prefix_blocks(64, 8192, 128) is None         # under a block of queries
    assert prefix_blocks(512, 8192, 128) is None         # a tail's bucket: the XLA form
    assert prefix_blocks(1152, 8192, 128) == (128, 512, 128)
    assert prefix_blocks(2048, 8192, 256) is not None
    with pytest.raises(ValueError, match="do not tile"):
        flash_prefix_attention(*_case(3, 48, 64, 1, 16, jnp.float32), jnp.int32(3), scale=1.0)
