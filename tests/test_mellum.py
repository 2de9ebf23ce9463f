"""Mellum 2 (window and full attention layers in one stack, YaRN on the full
layers, 64-of-8 softmax experts) against its plain reference
(`chipbench/reference/mellum.py`) at a tiny size on the CPU, seeded weights,
and the cache that knows the layer's kind: full layers' pages grow with the
row, window layers hold a ring.

Tolerances: everything runs in float32 here, so program and reference differ
by summation order only: 1e-4 of the largest logit (measured 1e-7 to 3e-6).
The window kernel against its mirror is compared bitwise.
"""

import dataclasses
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench.reference import mellum as reference  # noqa: E402
from ray_tpu import ops  # noqa: E402
from ray_tpu.models import decoding, mellum_config, mixtral_config, transformer  # noqa: E402
from ray_tpu.models import decoding_paged as dp  # noqa: E402
from ray_tpu.ops.ragged_paged_attention import ragged_decode_attention  # noqa: E402

VOCAB, PAGE, MAX_LEN = 300, 16, 640
TOL = 1e-4
MANY = ops.moe.SORTED_MIN_TOKENS + 8     # a call of this many tokens sorts its slots


def _cfg(**kw):
    return mellum_config("tiny", vocab_size=VOCAB, max_seq_len=1024, dtype=jnp.float32, **kw)


W = _cfg().window                          # 128 = 8 pages


def _sizes(cfg):
    y = cfg.yarn
    return dict(n_layers=cfg.n_layers, rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps,
                top_k=cfg.moe.top_k, num_experts=cfg.moe.num_experts, window=cfg.window,
                window_period=cfg.window_period,
                yarn=dict(factor=y.factor, original_max_position=y.original_max_position,
                          beta_fast=y.beta_fast, beta_slow=y.beta_slow,
                          attention_factor=y.scale))


def _params(cfg, seed=3):
    p = transformer.init(jax.random.PRNGKey(seed), cfg)
    # norm weights away from one, so that a norm left out would show
    return jax.tree.map(lambda x: x + 0.01 * jax.random.normal(
        jax.random.PRNGKey(7), x.shape, x.dtype), p)


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, n, dtype=np.int32)


def _close(got, want):
    return float(jnp.abs(jnp.asarray(got) - want).max() / jnp.abs(want).max())


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return cfg, _params(cfg)


@pytest.fixture
def kernel_interpreted(monkeypatch):
    import ray_tpu.ops.ragged_paged_attention as rpa

    real = rpa._ragged_kernel_call
    monkeypatch.setattr(rpa, "_ragged_kernel_call",
                        lambda *a, interpret, **kw: real(*a, interpret=True, **kw))


# --------------------------------------------------- the layer, whole forward


@pytest.mark.parametrize("n_tokens", [48, 3 * W + 21, MANY])
def test_forward_agrees_with_the_reference(model, n_tokens):
    """Inside the window, several windows long (the window cuts keys off on
    six layers in eight), and at a size whose experts take the sorted form."""
    cfg, p = model
    tokens = _tokens(n_tokens)
    logits, aux = transformer.forward(p, tokens[None], cfg)
    want, margin = reference.forward(p, jnp.asarray(tokens), _sizes(cfg))
    assert _close(logits[0], want) < TOL
    assert margin.shape == (cfg.n_layers, n_tokens, 2)


@pytest.mark.parametrize("broken,what", [
    (dict(window=None), "the window ignored"),
    (dict(yarn=None), "YaRN left out on the full layers"),
    (dict(yarn=ops.Yarn(16.0, 256, attention_factor=1.0)), "the attention factor left out"),
    (dict(window_period=2), "every other layer a full layer"),
])
def test_the_reference_tells_a_wrong_layer(model, broken, what):
    cfg, p = model
    tokens = _tokens(3 * W)
    want, _ = reference.forward(p, jnp.asarray(tokens), _sizes(cfg))
    got, _ = transformer.forward(p, tokens[None], dataclasses.replace(cfg, **broken))
    assert _close(got[0], want) > 100 * TOL, what


def test_the_train_step_traces_and_differentiates(model):
    cfg, p = model
    tokens = jnp.asarray(_tokens(2 * 40).reshape(2, 40))
    loss, grads = jax.value_and_grad(lambda q: transformer.loss_fn(q, tokens, cfg))(p)
    assert np.isfinite(float(loss))
    assert float(jnp.abs(grads["layers"]["attn"]["wq"]).max()) > 0


def test_yarn_is_the_published_one():
    """`low`, `high` and the attention factor of ISSUE 32, which are those of
    transformers' `_compute_yarn_parameters` for the published config; the
    program's tables against the reference's formulas; the two kinds of
    layer rotate differently."""
    yarn = mellum_config("12b-a2.5b").yarn
    assert yarn.ramp_bounds(128, 500000.0) == (18, 35)
    assert reference.yarn_bounds(128, 500000.0, dataclasses.asdict(yarn)) == (18, 35)
    assert yarn.scale == 1.2772588722239782
    assert abs(ops.Yarn(16.0, 8192).scale - yarn.scale) < 1e-12   # 0.1 ln 16 + 1
    cfg = mellum_config("12b-a2.5b", max_seq_len=4096)
    cos_w, sin_w = transformer.rope_tables(cfg, window=True)
    cos_f, sin_f = transformer.rope_tables(cfg, window=False)
    plain = ops.rope_frequencies(128, 4096, theta=500000.0)
    assert float(jnp.abs(cos_w - plain[0]).max()) == 0.0
    # frequencies under `low` turn as the plain ones do, times the factor
    assert float(jnp.abs(cos_f[:, :18] - yarn.scale * cos_w[:, :18]).max()) < 1e-5
    # from `high` up they turn 16 times slower
    t = jnp.arange(4096, dtype=jnp.float32)[:, None]
    inv = 1.0 / 500000.0 ** (jnp.arange(35, 64) * 2 / 128)
    assert float(jnp.abs(sin_f[:, 35:] - yarn.scale * jnp.sin(t * inv / 16)).max()) < 1e-4
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 64, 2, 128))
    a, b = ops.apply_rope(x, cos_w, sin_w), ops.apply_rope(x, cos_f, sin_f)
    want_b = reference._rope(x[0], 500000.0, (16.0, 8192, 32.0, 1.0, yarn.scale))
    assert float(jnp.abs(a - b).max()) > 0.1 and float(jnp.abs(b[0] - want_b).max()) < 1e-4


@pytest.mark.parametrize("kwargs,what", [
    (dict(kv_lora_rank=32, n_kv_heads=None), "latent attention"),
    (dict(pos="learned", yarn=None), "learned positions"),
    (dict(n_layers=6), "whole periods"),
    (dict(window=None, pos="learned"), "yarn rescales rotary"),
])
def test_what_the_layer_does_not_carry_is_refused(kwargs, what):
    with pytest.raises(ValueError, match=what):
        transformer.init(jax.random.PRNGKey(0), _cfg(**kwargs))
    if "n_layers" not in kwargs:
        return
    with pytest.raises(ValueError, match="sequence-parallel"):
        cfg = _cfg()
        transformer.forward(_params(cfg), _tokens(8)[None], cfg, sp_axis="sp")


# ------------------------------------------------ the window launch, bitwise


@pytest.mark.parametrize("positions", [
    [3, 40, 127, 128],              # shorter than the window, and just past it
    [129, 200, 255, 300],           # the ring has wrapped once
    [512, 1000, 1023, 4100],        # many times over, page boundaries
])
def test_window_kernel_is_bitwise_its_mirror_and_the_dense_window(positions):
    """Rings of 9, 11 and 13 slots (rows hold different numbers), logical
    page q in slot q % held; against the mirror bitwise, and against a dense
    softmax over the last `window` positions."""
    rng = np.random.default_rng(len(positions) + positions[0])
    B, Hkv, G, Dh, steps = len(positions), 2, 4, 16, W // PAGE + 1
    pos = np.asarray(positions, np.int32)
    held = np.asarray([9, 11, 13, 13][:B], np.int32)
    num_pages = 1 + int(held.sum())
    ids = np.split(1 + rng.permutation(num_pages - 1), np.cumsum(held)[:-1])
    kp = np.zeros((num_pages, PAGE, Hkv, Dh), np.float32)
    vp = np.zeros_like(kp)
    keys = rng.standard_normal((B, int(pos.max()) + 1, Hkv, Dh)).astype(np.float32)
    vals = rng.standard_normal(keys.shape).astype(np.float32)
    tbl = np.zeros((B, steps), np.int32)
    for b in range(B):     # write as a row does: position t into slot (t // P) % held
        for t in range(pos[b] + 1):
            page = ids[b][(t // PAGE) % held[b]]
            kp[page, t % PAGE], vp[page, t % PAGE] = keys[b, t], vals[b, t]
        for j in range(steps):
            q = pos[b] // PAGE - (steps - 1) + j
            tbl[b, j] = ids[b][q % held[b]] if q >= 0 else 0
    kp[0], vp[0] = 7.0, 7.0        # scratch: never read where it would count
    q = rng.standard_normal((B, Hkv, G, Dh)).astype(np.float32)
    args = tuple(map(jnp.asarray, (q, kp, vp, tbl, pos)))
    mirror = ragged_decode_attention(*args, impl="reference", window=W)
    kernel = ragged_decode_attention(*args, impl="kernel", interpret=True, window=W)
    assert np.array_equal(np.asarray(mirror), np.asarray(kernel))
    for b in range(B):
        lo = max(0, pos[b] - W + 1)
        k, v = keys[b, lo:pos[b] + 1], vals[b, lo:pos[b] + 1]
        s = np.einsum("kgd,tkd->kgt", q[b], k) / np.sqrt(Dh)
        w = np.exp(s - s.max(-1, keepdims=True))
        want = np.einsum("kgt,tkd->kgd", w / w.sum(-1, keepdims=True), v)
        assert np.abs(np.asarray(mirror[b]) - want).max() < 1e-5, b


def test_the_window_launch_refuses_what_it_does_not_carry():
    q = jnp.zeros((1, 2, 4, 16))
    pool = jnp.zeros((4, PAGE, 2, 16))
    with pytest.raises(ValueError, match="window"):   # a table of the wrong sweep
        ragged_decode_attention(q, pool, pool, jnp.zeros((1, 4), jnp.int32),
                                jnp.zeros((1,), jnp.int32), window=W)
    with pytest.raises(ValueError, match="window"):   # latent rows
        ragged_decode_attention(q, pool[:, :, 0], None, jnp.zeros((1, 9), jnp.int32),
                                jnp.zeros((1,), jnp.int32), window=W)


# ----------------------------------------- prefill + decode through the cache


def _prefilled(cfg, p, tokens, n, bucket, ring_ids=None, slot=1):
    """One-shot prefill of `n` tokens at `bucket`, inserted at `slot` of a
    state whose full pool has 48 pages: (logits, state, the row's pages)."""
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :n] = tokens[:n]
    logits, kv = decoding.prefill(p, jnp.asarray(padded), jnp.int32(n), cfg)
    state = dp.init_paged_state(cfg, 3, MAX_LEN, 48, PAGE)
    row = np.zeros((MAX_LEN // PAGE,), np.int32)
    need = max(bucket // PAGE, (n + 40) // PAGE + 1)
    # ids under 28: the default ring is the row's leading ids, and the window
    # pool of 3 slots has 3 * 9 + 1 pages
    row[:need] = np.random.default_rng(1).permutation(
        np.concatenate([1 + np.arange(27), 28 + np.arange(20)]))[:need] if ring_ids is not None \
        else np.concatenate([1 + np.random.default_rng(1).permutation(27), 28 + np.arange(20)])[:need]
    state = dp.insert_sequence_paged(
        state, slot, kv, jnp.int32(n), jnp.int32(tokens[n]), jnp.asarray(row), cfg,
        None if ring_ids is None else jnp.asarray(ring_ids))
    return logits, kv, state, row


@pytest.mark.parametrize("kernel", [False, True], ids=["mirror", "kernel"])
@pytest.mark.parametrize("ring", ["default", "granted"])
def test_prefill_then_paged_decode_agrees_with_the_full_forward(model, kernel_interpreted,
                                                                kernel, ring):
    """A context three windows long: the unchunked prefill (24 pages) writes
    only its last pages into a ring of 9, every decode step (across page
    boundaries and ring wrap-arounds) against the reference's full forward,
    logits. `default`: the ring derived from the row's own page ids, as the
    serve check leaves it; `granted`: ids of the window pool's own."""
    cfg, p = model
    n, steps = 3 * W - 7, 40
    tokens = _tokens(n + steps + 1)
    want, _ = reference.forward(p, jnp.asarray(tokens[:-1]), _sizes(cfg))
    ring_ids = None if ring == "default" else np.asarray([3, 8, 1, 9, 4, 2, 7, 6, 5], np.int32)
    logits, _, state, row = _prefilled(cfg, p, tokens, n, 384, ring_ids)
    assert state["wblock"].shape == (3, W // PAGE + 1) and int(state["wring"][1]) == 9
    assert state["wkp"].shape[:2] == (6, 3 * 9 + 1) and state["kp"].shape[:2] == (2, 48)
    assert _close(logits, want[n - 1]) < TOL
    for i in range(steps):
        state, step = dp.decode_step_paged_ragged(p, state, cfg, 32, kernel)
        assert _close(step[1], want[n + i]) < TOL, i
        state = decoding.commit_tokens(state, jnp.full((3,), tokens[n + i + 1], jnp.int32))
    assert int(state["length"][1]) == n + steps
    # the inactive rows wrote scratch page 0 of both pools and nothing else
    held = set(np.asarray(state["wblock"][1]).tolist())
    for page in set(range(1, state["wkp"].shape[1])) - held:
        assert float(jnp.abs(state["wkp"][:, page]).max()) == 0.0


def test_a_short_row_holds_a_page_for_every_page_it_reaches(model):
    """A row with fewer pages than a ring: no wrap-around, `wring` is what it
    was given, and the steps agree with the reference from inside the window."""
    cfg, p = model
    n, steps = 40, 12
    tokens = _tokens(n + steps + 1)
    want, _ = reference.forward(p, jnp.asarray(tokens[:-1]), _sizes(cfg))
    logits, _, state, _ = _prefilled(cfg, p, tokens, n, 64, np.asarray(
        [5, 2, 9, 4, 0, 0, 0, 0, 0], np.int32))
    assert int(state["wring"][1]) == 4 and _close(logits, want[n - 1]) < TOL
    for i in range(steps):
        state, step = dp.decode_step_paged_ragged(p, state, cfg, 8, False)
        assert _close(step[1], want[n + i]) < TOL, i
        state = decoding.commit_tokens(state, jnp.full((3,), tokens[n + i + 1], jnp.int32))


def _chunked(cfg, p, tokens, n, chunk, ring, kernel=False):
    """The engine's staged prefill by hand: chunks of `chunk` (the tail
    padded to it), full layers' pages by the table, window layers' through
    the row's ring (a permutation: ring slot != page id). Returns (last
    logits, state, row, ring ids)."""
    state = dp.init_paged_state(cfg, 2, MAX_LEN, 48, PAGE, ring=ring)
    span = -(-n // chunk) * chunk
    row = np.zeros((MAX_LEN // PAGE,), np.int32)
    need = min(span // PAGE + 2, MAX_LEN // PAGE)
    row[:need] = 1 + np.arange(need)
    held = np.asarray(1 + np.random.default_rng(2).permutation(ring), np.int32)

    for done in range(0, n, chunk):
        live = min(chunk, n - done)
        padded = np.zeros((1, chunk), np.int32)
        padded[0, :live] = tokens[done:done + live]
        if done == 0:
            logits, kv = decoding.prefill(p, jnp.asarray(padded), jnp.int32(live), cfg)
        else:
            npad = 1
            while npad < done // PAGE:
                npad *= 2
            ids = np.zeros((npad,), np.int32)
            ids[:done // PAGE] = row[:done // PAGE]
            pk, pv = dp.gather_prefix_pages(state["kp"], state["vp"], jnp.asarray(ids))
            wk, wv = dp.gather_window_pages(state, jnp.asarray(held), jnp.int32(done), cfg)
            logits, kv = dp.prefill_with_prefix(
                p, jnp.asarray(padded), pk, pv, jnp.int32(done), jnp.int32(live), cfg, wk, wv,
                kernel=kernel)
        pages = range(done // PAGE, (done + chunk) // PAGE)
        state = dp.write_kv_pages(state, kv, jnp.asarray(row[list(pages)]),
                                  jnp.asarray(held), jnp.int32(done))
    return logits, state, row, held


@pytest.mark.parametrize("chunks,chunk", [(2, 64), (3, 64), (5, 64), (5, 128)])
@pytest.mark.parametrize("form", ["xla", "flash"])
def test_chunked_prefill_agrees_with_one_shot_prefill(model, flash_interpreted, chunks, chunk,
                                                      form):
    """2, 3 and 5 chunks with a padded tail chunk, the later ones past the
    window (a window layer attends over ring pages, a full layer over the
    whole prefix), then decode steps from the chunked state against the
    reference: what the chunks left in the ring is what a decode step needs."""
    cfg, p = model
    n, steps = chunk * chunks - 11, 6
    tokens = _tokens(n + steps + 1)
    ring = dp.window_ring(cfg, PAGE, chunk)
    padded = np.zeros((1, 640), np.int32)
    padded[0, :n] = tokens[:n]
    want_logits, _ = decoding.prefill(p, jnp.asarray(padded), jnp.int32(n), cfg)
    logits, state, row, held = _chunked(cfg, p, tokens, n, chunk, ring, form == "flash")
    # every continuation's attention went through the launch, on both kinds of layer
    assert {w is not None for w in flash_interpreted} == ({True, False} if form == "flash"
                                                          else set())
    assert _close(logits, want_logits) < TOL
    want, _ = reference.forward(p, jnp.asarray(tokens[:-1]), _sizes(cfg))
    assert _close(logits, want[n - 1]) < TOL
    state = dp.activate_slot(state, 0, jnp.asarray(row), jnp.int32(n),
                             jnp.int32(tokens[n]), jnp.asarray(held))
    for i in range(steps):
        state, step = dp.decode_step_paged_ragged(p, state, cfg, 64, False)
        assert _close(step[0], want[n + i]) < TOL, i
        state = decoding.commit_tokens(state, jnp.full((2,), tokens[n + i + 1], jnp.int32))


def test_an_unchunked_prompt_longer_than_the_ring_writes_its_last_pages(model):
    """24 pages of prefill into a ring of 9: logical pages 15..23 lie at
    slot q % 9, the ones before are nowhere, page 0 holds no real row."""
    cfg, p = model
    n = 3 * W - 7                                  # its last position is in page 23
    tokens = _tokens(n + 1)
    ring_ids = np.asarray([3, 8, 1, 9, 4, 2, 7, 6, 5], np.int32)
    _, kv, state, _ = _prefilled(cfg, p, tokens, n, 384, ring_ids)
    wk = kv["k"].reshape(2, 4, 384, 2, 16)[:, :3].reshape(6, 24, PAGE, 2, 16)
    for q in range(15, 24):
        assert float(jnp.abs(state["wkp"][:, ring_ids[q % 9]] - wk[:, q]).max()) == 0.0, q
    assert float(jnp.abs(state["kp"][:, 0]).max()) == 0.0


# ------------------------------------------- whole pages into a pool, in place


def _scatter_pages(pool, ids, src):
    """The form `_set_pages` replaced (one scatter along the page axis): the
    oracle. Where ids repeat (scratch page 0) its order is unspecified."""
    return pool.at[:, ids].set(src.astype(pool.dtype))


def _ring_ids(held, first, n):
    """Page ids of `n` consecutive logical pages from `first` on, in a ring
    of 9 slots of which the row holds `held` (the ids a permutation)."""
    ids = np.zeros((9,), np.int32)
    ids[:held] = [3, 8, 1, 9, 4, 2, 7, 6, 5][:held]
    return dp._ring_pages(jnp.asarray(ids), jnp.int32(held), first + jnp.arange(n))


# name -> (pool shape, pool dtype, source dtype, page ids)
F32, BF16 = jnp.float32, jnp.bfloat16
PAGE_WRITES = {
    "full_pool": ((2, 12, PAGE, 2, 16), F32, F32, lambda: jnp.asarray([7, 2, 11, 5])),
    "bucket_padding": ((2, 12, PAGE, 2, 16), F32, F32, lambda: jnp.asarray([4, 9, 0, 0])),
    "ring_wraps": ((6, 10, PAGE, 2, 16), F32, F32, lambda: _ring_ids(9, 15, 9)),
    "ring_before_the_row": ((6, 10, PAGE, 2, 16), F32, F32, lambda: _ring_ids(4, -5, 9)),
    "latent_rows": ((3, 9, PAGE, 40), BF16, BF16, lambda: jnp.asarray([8, 1, 3])),
    "looped_planes": ((12, 7, PAGE, 4, 8), F32, F32, lambda: jnp.asarray([6, 1, 4, 2, 5])),
    "cast_to_the_pool": ((2, 12, PAGE, 2, 16), BF16, F32, lambda: jnp.asarray([1, 10])),
}


@pytest.mark.parametrize("case", list(PAGE_WRITES))
def test_pages_written_one_at_a_time_are_the_scatters(case):
    """`_set_pages` against the scatter it replaced, bit for bit on every page
    but scratch 0: a full pool, bucket padding (several ids 0), a ring across
    its wrap-around (slot q % held, ids out of order) and one whose first
    logical pages lie before the row's start (scratch), latent rows, a looped
    stack's planes, a source wider than the pool's dtype; jitted with the pool
    donated, as the writers call it."""
    shape, pool_dtype, src_dtype, ids = PAGE_WRITES[case]
    ids = ids()
    k1, k2 = jax.random.split(jax.random.PRNGKey(len(case)))
    pool = jax.random.normal(k1, shape, jnp.float32).astype(pool_dtype)
    src = jax.random.normal(k2, (shape[0], ids.shape[0], *shape[2:]), jnp.float32).astype(src_dtype)
    want = np.asarray(_scatter_pages(pool, ids, src).astype(jnp.float32))
    if case.startswith("ring"):
        assert sorted(np.asarray(ids).tolist()) != np.asarray(ids).tolist()
    if case in ("bucket_padding", "ring_before_the_row"):
        assert int((ids == 0).sum()) > 1
    before = np.asarray(pool.astype(jnp.float32))
    got = jax.jit(dp._set_pages, donate_argnums=0)(pool, ids, src)
    assert got.dtype == pool_dtype and got.shape == shape
    got = np.asarray(got.astype(jnp.float32))
    assert np.array_equal(got[:, 1:], want[:, 1:])
    untouched = sorted(set(range(1, shape[1])) - set(np.asarray(ids).tolist()))
    assert np.array_equal(got[:, untouched], before[:, untouched])
    if int((ids == 0).sum()) > 1:   # the last writer of scratch stays
        last = int(np.flatnonzero(np.asarray(ids) == 0)[-1])
        assert np.array_equal(got[:, 0], np.asarray(src[:, last].astype(pool_dtype)
                                                    .astype(jnp.float32)))


@pytest.mark.parametrize("writer", ["insert", "insert_prefix", "chunk", "ring_only"])
def test_the_writers_leave_what_the_scatter_left(model, monkeypatch, writer):
    """The three programs (and `_write_ring` alone, a prompt whose bucket is
    longer than the ring, `length` inside its third page from the end) with
    `_set_pages` swapped for the scatter: the same pools on every page but
    scratch 0, the same tables."""
    cfg, _ = model
    T = 24 * PAGE
    k1, k2 = jax.random.split(jax.random.PRNGKey(11))
    kv = {"k": jax.random.normal(k1, (cfg.n_layers, T, 2, 16)),
          "v": jax.random.normal(k2, (cfg.n_layers, T, 2, 16))}
    ring = jnp.asarray([3, 8, 1, 9, 4, 2, 7, 6, 5], jnp.int32)
    row = np.zeros((MAX_LEN // PAGE,), np.int32)
    row[:26] = 10 + np.random.default_rng(4).permutation(26)
    row = jnp.asarray(row)
    n = jnp.int32(T - 2 * PAGE - 3)

    def run():
        state = dp.init_paged_state(cfg, 3, MAX_LEN, 48, PAGE)
        if writer == "insert":
            return dp.insert_sequence_paged.__wrapped__(
                state, 1, kv, n, jnp.int32(5), row, cfg, ring)
        if writer == "insert_prefix":
            return dp.insert_sequence_paged_prefix.__wrapped__(
                state, 1, kv, row[:24], row, n, jnp.int32(5), cfg, ring)
        if writer == "chunk":
            for start in range(0, T, 8 * PAGE):
                chunk = {x: t[:, start:start + 8 * PAGE] for x, t in kv.items()}
                state = dp.write_kv_pages.__wrapped__(
                    state, chunk, row[start // PAGE:start // PAGE + 8], ring,
                    jnp.int32(start))
            return state
        state = dp._set_ring(state, 1, ring)
        return dp._write_ring(state, dp._split_kinds(kv, state)[1], 1, n)

    got = run()
    monkeypatch.setattr(dp, "_set_pages", _scatter_pages)
    want = run()
    assert set(got) == set(want)
    for name in want:
        a, b = np.asarray(got[name]), np.asarray(want[name])
        if name in ("kp", "vp", "wkp", "wvp"):
            a, b = a[:, 1:], b[:, 1:]
            assert np.abs(b).max() > 0 or (writer == "ring_only" and name[0] != "w"), name
        assert np.array_equal(a, b), name


@pytest.mark.parametrize("family", ["mellum", "mixtral"])
def test_chunk_by_chunk_writes_equal_the_whole_prompts_insert(model, family):
    """`write_kv_pages` a chunk at a time (the window layers' part through
    the ring, later pages over earlier ones) and `activate_slot`, against
    `insert_sequence_paged` of the whole bucket: the same full pool and the
    same ring on every page but scratch, the same row. The prompt ends in its
    bucket's last page, so both leave the ring's last logical pages."""
    cfg = model[0] if family == "mellum" else mixtral_config(
        "tiny", vocab_size=VOCAB, max_seq_len=1024, dtype=jnp.float32)
    chunk, T = 4 * PAGE, 24 * PAGE
    ring = dp.window_ring(cfg, PAGE, chunk) if cfg.window else None
    k1, k2 = jax.random.split(jax.random.PRNGKey(5))
    kv = {"k": jax.random.normal(k1, (cfg.n_layers, T, cfg.kv_heads, cfg.head_dim)),
          "v": jax.random.normal(k2, (cfg.n_layers, T, cfg.kv_heads, cfg.head_dim))}
    row = np.zeros((MAX_LEN // PAGE,), np.int32)
    row[:26] = 1 + np.random.default_rng(3).permutation(40)[:26]
    held = None if ring is None else jnp.asarray(
        1 + np.random.default_rng(2).permutation(ring), jnp.int32)
    n, first = jnp.int32(T - 5), jnp.int32(17)

    def fresh():
        return dp.init_paged_state(cfg, 2, MAX_LEN, 48, PAGE, ring=ring)

    want = dp.insert_sequence_paged(fresh(), 1, kv, n, first, jnp.asarray(row), cfg, held)
    got = fresh()
    for start in range(0, T, chunk):
        part = {x: t[:, start:start + chunk] for x, t in kv.items()}
        ids = jnp.asarray(row[start // PAGE:(start + chunk) // PAGE])
        got = dp.write_kv_pages(got, part, ids, held,
                                None if held is None else jnp.int32(start))
    got = dp.activate_slot(got, 1, jnp.asarray(row), n, first, held)
    assert set(got) == set(want) and ("wkp" in want) == (family == "mellum")
    for name in want:
        a, b = np.asarray(got[name]), np.asarray(want[name])
        if name.endswith("p"):
            a, b = a[:, 1:], b[:, 1:]
            assert np.abs(b).max() > 0, name
        assert np.array_equal(a, b), name


# ------------------------------------------------------------ the engine


def _greedy(cfg, p, prompt, k):
    tokens, out = list(prompt), []
    for _ in range(k):
        logits, _ = transformer.forward(p, jnp.asarray(tokens)[None], cfg)
        out.append(int(jnp.argmax(logits[0, -1])))
        tokens.append(out[-1])
    return out


def _engine(cfg, p, **kw):
    from ray_tpu.llm.engine import TPUEngine

    kw = {**dict(max_slots=3, max_len=MAX_LEN, min_bucket=32, page_size=PAGE,
                 num_pages=100, prefill_chunk=64), **kw}
    return TPUEngine(cfg, p, **kw)


def _idle(eng, timeout=30.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if not (eng._by_slot or eng._prefilling or eng._backlog or eng._waiting.qsize()):
            return
        time.sleep(0.01)
    raise AssertionError("the engine did not come to rest")


def _all_pages_free(eng):
    assert sorted(eng._free_pages) == list(range(1, eng.num_pages))
    assert sorted(eng._free_wpages) == list(range(1, eng.window_pages))
    assert not eng._slot_pages and not eng._slot_wpages and len(eng._free) == eng.max_slots


@pytest.mark.parametrize("kwargs,what", [
    (dict(max_loras=2), "max_loras"),
    (dict(mesh="a mesh"), "tensor-parallel mesh"),
    (dict(enable_prefix_cache=True), "enable_prefix_cache"),
])
def test_what_the_engine_does_not_carry_is_refused_at_construction(model, kwargs, what):
    cfg, p = model
    with pytest.raises(ValueError, match=what):
        _engine(cfg, p, **kwargs)
    with pytest.raises(NotImplementedError, match="window layers"):
        decoding.init_lora_bank(cfg, 2, 4)
    with pytest.raises(ValueError, match="multiple of page_size"):
        dp.window_ring(cfg, 48)


def test_engine_serves_both_kinds_of_layer_and_counts_them(model):
    """Through TPUEngine: a prompt three windows long in 6 chunks (a padded
    tail), greedy decode, the same tokens as the model's own forward; a long
    row holds a ring for life; the counters move as the rows do."""
    from ray_tpu.llm.engine import SamplingParams

    cfg, p = model
    eng = _engine(cfg, p)
    try:
        assert eng.ring == W // PAGE + 1 + 4 and eng.window_pages == 3 * eng.ring + 1
        with pytest.raises(NotImplementedError, match="window layers"):
            eng.submit_prefilled(length=4)
        prompt = _tokens(3 * W - 23).tolist()
        req = eng.submit(prompt, SamplingParams(max_tokens=6, temperature=0.0))
        seen = set()
        while not seen or eng._slot_wpages:
            for pages in list(eng._slot_wpages.values()):
                seen.add(tuple(pages))
            time.sleep(0.001)
        from ray_tpu.llm.engine import _iter_request

        assert list(_iter_request(req)) == _greedy(cfg, p, prompt, 6)
        _idle(eng)
        assert len(seen) == 1 and len(next(iter(seen))) == eng.ring   # one ring, for life
        cache = eng.stats()["cache"]
        n = len(prompt)
        assert cache["bytes_per_token"] == cfg.n_layers * 2 * 2 * 16 * 4
        assert cache["context_tokens"] == sum(n + i + 1 for i in range(5))
        assert cache["window_context_tokens"] == 5 * W
        assert cache["held_token_steps"] == cache["context_tokens"]
        row_pages = 512 // PAGE            # the whole prompt's bucket, staged or not
        page_bytes = PAGE * 2 * 2 * 16 * 4                  # K and V of one layer
        assert cache["held_byte_steps"] == 5 * page_bytes * (
            2 * row_pages + 6 * eng.ring)
        assert cache["window_page_steps_used"] == 5 * eng.ring
        assert cache["window_page_steps_total"] == 5 * (eng.window_pages - 1)
        _all_pages_free(eng)
    finally:
        eng.shutdown()


def test_a_mix_of_rows_never_exceeds_either_pool_and_returns_both(model):
    """Short rows (a page for each page they reach) and long ones (a ring)
    through three slots and a full pool too small for three long rows (the
    window pool, a ring for every slot, never binds before it): every row
    is served with the model's own greedy tokens, neither pool is ever
    overdrawn, page 0 of either is never granted, and aborts (a staged
    prefill, a live row) return both kinds."""
    from ray_tpu.llm.engine import RequestCancelledError, SamplingParams, _iter_request

    cfg, p = model
    eng = _engine(cfg, p, num_pages=60)
    granted, wgranted = set(), set()
    try:
        prompts = [_tokens(n, seed=n).tolist() for n in (20, 300, 50, 410, 33, 140, 360)]
        reqs = [eng.submit(t, SamplingParams(max_tokens=4, temperature=0.0)) for t in prompts]
        doomed = eng.submit(_tokens(500, seed=9).tolist(),
                            SamplingParams(max_tokens=200, temperature=0.0))
        aborted = False
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline and (eng._by_slot or eng._prefilling
                                               or eng._backlog or eng._waiting.qsize()):
            for held, seen, size in ((eng._slot_pages, granted, eng.num_pages),
                                     (eng._slot_wpages, wgranted, eng.window_pages)):
                pages = [q for v in list(held.values()) for q in v]
                assert len(pages) == len(set(pages)) < size
                seen.update(pages)
            if not aborted and (doomed in eng._prefilling or doomed.slot in eng._by_slot):
                eng.abort_request(doomed.rid)
                aborted = True
            time.sleep(0.0005)
        for req, prompt in zip(reqs, prompts):
            assert list(_iter_request(req)) == _greedy(cfg, p, prompt, 4), len(prompt)
        with pytest.raises(RequestCancelledError):
            list(_iter_request(doomed))
        _idle(eng)
        assert 0 not in granted and 0 not in wgranted and granted and wgranted
        _all_pages_free(eng)
        assert eng._live_tokens == eng._live_beyond_window == eng._staged_tokens == 0
    finally:
        eng.shutdown()


def test_a_failed_stream_returns_both_kinds_of_page(model):
    """`_fail_stream` is the PD plane's, which window layers are refused on;
    its release is the one every path shares, so hold it to both lists."""
    from ray_tpu.llm.engine import SamplingParams, _Request

    cfg, p = model
    eng = _engine(cfg, p)
    try:
        req = _Request(99, [1, 2, 3], SamplingParams(max_tokens=2))
        req.slot = eng._free.pop()
        eng._slot_pages[req.slot] = [eng._free_pages.pop() for _ in range(3)]
        eng._grant_ring(req.slot, 3)
        assert len(eng._slot_wpages[req.slot]) == 3
        eng._streaming.append(req)
        eng._fail_stream(req, RuntimeError("the transfer died"))
        _all_pages_free(eng)
    finally:
        eng.shutdown()


@pytest.mark.parametrize("family", ["mixtral", "mellum"])
def test_chunked_admission_without_the_prefix_cache_frees_its_pages(model, family):
    """ROADMAP D14's leak: with `prefill_chunk` set and the prefix cache off,
    a prompt no longer than a chunk took the admission's unstaged branch,
    which never recorded its pages, so release freed nothing and a pool of
    60 pages was gone after about 20 requests. 300 short requests, more
    than the pool could hold 10 times over, and every page is free after."""
    from ray_tpu.llm.engine import SamplingParams

    if family == "mellum":
        cfg, p = model
    else:
        cfg = mixtral_config("tiny", vocab_size=VOCAB, max_seq_len=1024, dtype=jnp.float32,
                             d_model=64, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=64)
        p = _params(cfg)
    eng = _engine(cfg, p, num_pages=60, enable_prefix_cache=False)
    try:
        reqs = [eng.submit(_tokens(5 + i % 40, seed=i).tolist(),
                           SamplingParams(max_tokens=2, temperature=0.0))
                for i in range(300)]
        from ray_tpu.llm.engine import _iter_request

        assert all(len(list(_iter_request(r))) == 2 for r in reqs)
        _idle(eng)
        _all_pages_free(eng)
    finally:
        eng.shutdown()
