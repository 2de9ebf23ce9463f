"""Trinity Large (gated attention with q/k norms, window layers under rope and
full layers without positions, sandwich norms, a scaled embedding, leading
dense layers, expert layers that hold a SHARE of the experts) against its plain
reference (`chipbench/reference/trinity.py`) at a tiny size on the CPU, seeded
weights; the shares of a layer add up to the uncut layer; every new field is
inert at its default.

Tolerances: everything runs in float32 here, so program and reference differ
by summation order only: 1e-4 of the largest logit (measured 3e-7 to 1e-6).
"""

import dataclasses
import hashlib
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench.reference import kimi_vl as kimi_reference  # noqa: E402
from chipbench.reference import trinity as reference  # noqa: E402
from ray_tpu import models, ops  # noqa: E402
from ray_tpu.models import decoding, transformer, trinity_config  # noqa: E402
from ray_tpu.models import decoding_paged as dp  # noqa: E402
import ray_tpu.ops.grouped_matmul  # noqa: E402, F401

gmm = sys.modules["ray_tpu.ops.grouped_matmul"]   # `ops.grouped_matmul` is the function

VOCAB, PAGE, MAX_LEN = 300, 16, 640
TOL = 1e-4
MANY = ops.moe.SORTED_MIN_TOKENS + 8     # a call of this many tokens sorts its slots
SHARE = dict(experts_held=8, first_expert=16)


def _cfg(**kw):
    return trinity_config("tiny", vocab_size=VOCAB, max_seq_len=1024, dtype=jnp.float32,
                          select_bias_init_std=0.02, **kw)


W = _cfg().window                          # 32 = 2 pages


def _sizes(cfg):
    s = dict(n_layers=cfg.n_layers, n_dense_layers=cfg.n_dense_layers,
             rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps, top_k=cfg.moe.top_k,
             window=cfg.window, window_period=cfg.window_period,
             routed_scaling_factor=cfg.moe.routed_scaling_factor,
             embedding_multiplier=cfg.embedding_multiplier)
    if cfg.moe.share:
        s["experts_held"] = list(range(cfg.moe.first_expert,
                                       cfg.moe.first_expert + cfg.moe.held))
    return s


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
def whole():
    cfg = _cfg()
    return cfg, _params(cfg)


@pytest.fixture(scope="module")
def model():
    """This chip's share: experts 16-23 of the 64."""
    cfg = _cfg(**SHARE)
    return cfg, _params(cfg)


@pytest.fixture
def kernel_interpreted(monkeypatch):
    import ray_tpu.ops.ragged_paged_attention as rpa

    real = rpa._ragged_kernel_call
    monkeypatch.setattr(rpa, "_ragged_kernel_call",
                        lambda *a, interpret, **kw: real(*a, interpret=True, **kw))


# --------------------------------------------------- the layer, whole forward


@pytest.mark.parametrize("share", [False, True], ids=["all_held", "share"])
@pytest.mark.parametrize("n_tokens", [12, 3 * W + 5, MANY])
def test_forward_agrees_with_the_reference(whole, model, share, n_tokens):
    """Inside the window, several windows long, and at a size whose experts
    take the sorted form; with every expert held (the uncut model) and with a
    share (12 tokens of a share sort as well: under one slot a held expert)."""
    cfg, p = model if share else whole
    tokens = _tokens(n_tokens)
    logits, _ = transformer.forward(p, tokens[None], cfg)
    want, margin = reference.forward(p, jnp.asarray(tokens), _sizes(cfg))
    assert _close(logits[0], want) < TOL
    assert margin.shape == (cfg.n_layers, n_tokens, 2)
    assert bool(jnp.isinf(margin[:cfg.n_dense_layers]).all())


def test_the_sorted_form_is_taken_where_a_share_has_few_rows():
    assert not ops.sorted_pays(12) and ops.sorted_pays(12, 12 * 4 / 64)
    assert not ops.sorted_pays(100, 100 * 4 / 64) and ops.sorted_pays(MANY, MANY * 4 / 64)


@pytest.mark.parametrize("broken,what", [
    (dict(attn_gate=False), "the gate left out"),
    (dict(qk_norm=False), "q/k norms left out"),
    (dict(full_layer_rope=True), "rope on the full layers"),
    (dict(window=4 * W), "the window ignored"),
    (dict(sandwich_norms=False), "the post-sublayer norms left out"),
    (dict(embedding_multiplier=1.0), "the embedding unscaled"),
    (dict(n_dense_layers=2, window_period=2), "every other layer a full layer"),
])
def test_the_reference_tells_a_wrong_layer(model, broken, what):
    cfg, p = model
    tokens = _tokens(3 * W)
    want, _ = reference.forward(p, jnp.asarray(tokens), _sizes(cfg))
    got, _ = transformer.forward(p, tokens[None], dataclasses.replace(cfg, **broken))
    assert _close(got[0], want) > 100 * TOL, what


def test_the_reference_tells_another_share(model):
    """The same weights read as experts 24-31: another result."""
    cfg, p = model
    tokens = _tokens(3 * W)
    want, _ = reference.forward(p, jnp.asarray(tokens), _sizes(cfg))
    moved = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, first_expert=24))
    got, _ = transformer.forward(p, tokens[None], moved)
    assert _close(got[0], want) > 100 * TOL


def test_the_kinds_of_layer_count_from_the_first_after_the_dense_ones():
    cfg = _cfg()
    assert [transformer.is_full_layer(cfg, l) for l in range(10)] == [
        False, False, False, False, False, True, False, False, False, True]
    assert transformer.kind_index(cfg) == [0, 1, 2, 3, 4, 0, 5, 6, 7, 1]
    assert (cfg.n_full_layers, cfg.n_layers - cfg.n_full_layers) == (2, 8)
    rope = transformer.rope_by_kind(cfg)
    assert rope[False] == (None, None) and rope[True][0].shape == (1024, 8)
    # a stack of one kind, and Mellum's: as they were
    mellum = models.mellum_config("tiny", vocab_size=VOCAB, max_seq_len=256)
    assert transformer.kind_index(mellum) == [0, 1, 2, 0, 3, 4, 5, 1]
    assert transformer.rope_by_kind(mellum)[False][0] is not None
    plain = models.llama_config("tiny", vocab_size=VOCAB)
    assert transformer.kind_index(plain) == list(range(plain.n_layers))
    state = {"kp": jnp.zeros((2, 4, PAGE, 2, 16)), "wkp": jnp.zeros((8, 4, PAGE, 2, 16))}
    rows = jnp.arange(10.0)[:, None, None, None] * jnp.ones((10, PAGE, 2, 16))
    full, window = dp._split_kinds({"k": rows}, state, dense=2)
    assert full["k"][:, 0, 0, 0].tolist() == [5.0, 9.0]
    assert window["wk"][:, 0, 0, 0].tolist() == [0.0, 1.0, 2.0, 3.0, 4.0, 6.0, 7.0, 8.0]


# ------------------------------------------------------- the shares add up


@pytest.mark.parametrize("n_tokens", [100, MANY], ids=["onehot", "sorted"])
def test_eight_shares_add_up_to_the_uncut_layer(whole, n_tokens):
    """One expert layer of 64 experts as 8 shares of 8: what each share's
    held experts give, the shared expert (which every chip computes alike)
    counted once, sums to the uncut reference's layer; and the program's
    counts of one share are the count by hand."""
    cfg, p = whole
    layer = jax.tree.map(lambda a: a[3], p["layers"])
    x = jax.random.normal(jax.random.PRNGKey(n_tokens), (1, n_tokens, cfg.d_model))
    # the uncut reference's layer: all 64 experts, one after another
    stacked = jax.tree.map(lambda a: a[None], layer)
    h, gates, _ = kimi_reference._route(
        {**stacked, "norm2": {"w": jnp.ones((1, cfg.d_model))}}, 0, x[0],
        jnp.zeros((n_tokens,), jnp.int32), top_k=4, scale=2.448, eps=0.0)
    x = h[None]                     # the layer's input as the router normed it
    shared = kimi_reference._swiglu(stacked["mlp"]["shared"], 0, h)
    routed = reference._experts(stacked["mlp"], 0, h, gates, cap=n_tokens)
    total = jnp.zeros_like(h)
    for c in range(8):
        share = _cfg(experts_held=8, first_expert=8 * c)
        mlp = {**layer["mlp"], **{k: layer["mlp"][k][8 * c:8 * c + 8]
                                  for k in ("gate", "up", "down")}}
        y, _, counts = transformer._moe_mlp(x, mlp, share)
        total = total + (y[0] - shared)
        mine = np.asarray(gates[:, 8 * c:8 * c + 8] > 0)
        assert counts.tolist() == [int(mine.sum()), int(mine.any(axis=0).sum())]
    assert float(jnp.abs(total - routed).max() / jnp.abs(routed).max()) < TOL
    y, _ = transformer._moe_mlp(x, layer["mlp"], cfg)       # and the program's own uncut
    assert float(jnp.abs(y[0] - (routed + shared)).max() / jnp.abs(routed).max()) < TOL


def test_sorted_share_masks_the_absent_slots_and_skips_their_rows():
    """`moe_sorted(first=)` against experts computed one by one; and the
    grouped product's kernel (interpreted) with `rows_past="skip"`: the held
    rows as `ragged_dot` gives them, no step for the rows past them, one
    step (zeros) for a call none of whose groups has a row."""
    k1, k2, k3, k4, k5 = jax.random.split(jax.random.PRNGKey(0), 5)
    N, D, F, E, first = 40, 128, 128, 4, 6
    x = jax.random.normal(k1, (N, D))
    idx = jax.random.randint(k2, (N, 2), 0, 16)
    w = jax.random.uniform(k3, (N, 2))
    gate, up = (jax.random.normal(k, (E, D, F)) * 0.1 for k in (k4, k5))
    down = jax.random.normal(k1, (E, F, D)) * 0.1
    want = jnp.zeros((N, D))
    for e in range(E):
        ye = (jax.nn.silu(x @ gate[e]) * (x @ up[e])) @ down[e]
        want = want + ye * jnp.where(idx == first + e, w, 0.0).sum(-1)[:, None]
    got = ops.moe_sorted(x, idx, w, gate, up, down, first=first)
    assert float(jnp.abs(got - want).max()) < 1e-4
    counts = ops.share_counts(idx, first, E)
    mine = (np.asarray(idx) >= first) & (np.asarray(idx) < first + E)
    assert counts.tolist() == [int(mine.sum()), len(set(np.asarray(idx)[mine].tolist()))]
    # the kernel: 256 rows of which 100 belong to groups
    lhs = jax.random.normal(k2, (256, D))
    sizes = jnp.asarray([0, 60, 0, 40], jnp.int32)
    out = gmm.grouped_matmul_kernel(lhs, gate, sizes, rows_past="skip", interpret=True)
    ref = jax.lax.ragged_dot(lhs, gate, sizes)
    assert float(jnp.abs(out[:100] - ref[:100]).max()) < 1e-4
    *_, steps = gmm._schedule(sizes, 256, 128, 4, False)
    *_, zero_steps = gmm._schedule(sizes, 256, 128, 4, True)
    assert (int(steps), int(zero_steps)) == (2, 4)      # a tile each, +2 for the rows past
    none = jnp.zeros((4,), jnp.int32)
    gid, tile, lo, hi, steps = gmm._schedule(none, 256, 128, 4, False)
    assert int(steps) == 1 and int(tile[0]) == 0 and int(lo[0]) == int(hi[0])


def test_window_attention_a_group_at_a_time_is_the_attention_at_once(monkeypatch):
    """Past `_SCORES_AT_ONCE` the masked window attention maps over the KV
    heads: the same numbers (a group of 3, as this family's 6 no power of 2)."""
    att = sys.modules["ray_tpu.ops.attention"]
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 96, 6, 16))
    k, v = (jax.random.normal(jax.random.PRNGKey(i), (1, 96, 2, 16)) for i in (1, 2))
    at_once = ops.attention(q, k, v, window=W, impl="reference")
    monkeypatch.setattr(att, "_SCORES_AT_ONCE", 1024)
    grouped = ops.attention(q, k, v, window=W, impl="reference")
    assert float(jnp.abs(grouped - at_once).max()) < 1e-6


# ----------------------------------------- prefill + decode through the cache


def _prefilled(cfg, p, tokens, n, bucket, slot=1):
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :n] = tokens[:n]
    logits, kv = decoding.prefill(p, jnp.asarray(padded), jnp.int32(n), cfg)
    state = dp.init_paged_state(cfg, 3, MAX_LEN, 48, PAGE)
    row = np.zeros((MAX_LEN // PAGE,), np.int32)
    need = max(bucket // PAGE, (n + 40) // PAGE + 1)
    # the default ring is the row's leading ids: under the window pool's 10 pages
    row[:need] = (1 + np.random.default_rng(1).permutation(9))[:3].tolist() + list(
        range(10, 7 + need))
    state = dp.insert_sequence_paged(state, slot, kv, jnp.int32(n), jnp.int32(tokens[n]),
                                     jnp.asarray(row), cfg)
    return logits, kv, state


@pytest.mark.parametrize("kernel", [False, True], ids=["mirror", "kernel"])
def test_prefill_then_paged_decode_agrees_with_the_full_forward(model, kernel_interpreted,
                                                                kernel):
    """A context three windows long: the unchunked prefill (8 pages) writes
    only its last pages into a ring of 3 on the 8 window layers (the two
    dense ones among them), all of them on the 2 full layers; every decode
    step (across page boundaries and ring wrap-arounds, rope on one kind and
    none on the other) against the reference's full forward; the step's
    counts come back in the state."""
    cfg, p = model
    n, steps = 3 * W - 7, 24
    tokens = _tokens(n + steps + 1)
    want, _ = reference.forward(p, jnp.asarray(tokens[:-1]), _sizes(cfg))
    logits, kv, state = _prefilled(cfg, p, tokens, n, 128)
    assert state["wkp"].shape[:2] == (8, 3 * 3 + 1) and state["kp"].shape[:2] == (2, 48)
    assert kv["k"].shape[0] == 10 and kv["expert_counts"].shape == (2,)
    assert 0 < int(kv["expert_counts"][0]) < 128 * 4 * 8
    assert _close(logits, want[n - 1]) < TOL
    for i in range(steps):
        state, step = dp.decode_step_paged_ragged(p, state, cfg, 16, kernel)
        assert _close(step[1], want[n + i]) < TOL, i
        held, groups = state["expert_counts"].tolist()
        assert 0 <= groups <= held <= 3 * 4 * 8
        state = decoding.commit_tokens(state, jnp.full((3,), tokens[n + i + 1], jnp.int32))
    assert int(state["length"][1]) == n + steps


def _chunked(cfg, p, tokens, n, chunk, ring, kernel=False):
    """The engine's staged prefill by hand (tests/test_mellum.py `_chunked`)."""
    state = dp.init_paged_state(cfg, 2, MAX_LEN, 48, PAGE, ring=ring)
    span = -(-n // chunk) * chunk
    row = np.zeros((MAX_LEN // PAGE,), np.int32)
    need = min(span // PAGE + 2, MAX_LEN // PAGE)
    row[:need] = 1 + np.arange(need)
    held = np.asarray(1 + np.random.default_rng(2).permutation(ring), np.int32)
    counted = 0
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
        counted += int(kv["expert_counts"][0])
        pages = range(done // PAGE, (done + chunk) // PAGE)
        state = dp.write_kv_pages(state, kv, jnp.asarray(row[list(pages)]),
                                  jnp.asarray(held), jnp.int32(done),
                                  dense_layers=cfg.n_dense_layers)
    return logits, state, row, held, counted


@pytest.mark.parametrize("chunks,chunk", [(2, 32), (3, 32), (5, 32), (5, 64)])
@pytest.mark.parametrize("form", ["xla", "flash"])
def test_chunked_prefill_agrees_with_one_shot_prefill(model, flash_interpreted, chunks, chunk,
                                                      form):
    """2, 3 and 5 chunks with a padded tail chunk, the later ones past the
    window (a window layer attends over ring pages, a full layer over the
    whole prefix, without positions), then decode steps from the chunked
    state against the reference."""
    cfg, p = model
    n, steps = chunk * chunks - 11, 6
    tokens = _tokens(n + steps + 1)
    ring = dp.window_ring(cfg, PAGE, chunk)
    padded = np.zeros((1, 512), np.int32)
    padded[0, :n] = tokens[:n]
    want_logits, _ = decoding.prefill(p, jnp.asarray(padded), jnp.int32(n), cfg)
    logits, state, row, held, counted = _chunked(cfg, p, tokens, n, chunk, ring, form == "flash")
    # every continuation's attention went through the launch, on both kinds of layer
    assert {w is not None for w in flash_interpreted} == ({True, False} if form == "flash"
                                                          else set())
    assert _close(logits, want_logits) < TOL and counted > 0
    want, _ = reference.forward(p, jnp.asarray(tokens[:-1]), _sizes(cfg))
    assert _close(logits, want[n - 1]) < TOL
    state = dp.activate_slot(state, 0, jnp.asarray(row), jnp.int32(n),
                             jnp.int32(tokens[n]), jnp.asarray(held))
    for i in range(steps):
        state, step = dp.decode_step_paged_ragged(p, state, cfg, 32, False)
        assert _close(step[0], want[n + i]) < TOL, i
        state = decoding.commit_tokens(state, jnp.full((2,), tokens[n + i + 1], jnp.int32))


# ------------------------------------------- every new field inert at its default

# from the parent commit's tree (PR 43), by the same calls: the parameter
# tree's paths, shapes and dtypes; the sum of |parameter|; the sum of |logit|
# of a forward over 256 tokens and of a prefill's last position. PR 44 read
# them bit-equal (sha256 of the bytes) on the parent and on the change
PARENTS = {
    "mellum": ("cd62d5d96da97f83", 7660.994140625, 9839.21875, 43.67662811279297),
    "kimi_vl": ("19110ceb4ab7263c", 3686.9892578125, 9736.6494140625, 42.38722610473633),
    "ouro": ("93e01423080750c6", 4105.1162109375, 9719.546875, 40.46120071411133),
    "granite": ("d9c0134db61251d7", 15460.310546875, 1271.0887451171875, 5.121464729309082),
}


@pytest.mark.parametrize("family", list(PARENTS))
def test_the_other_families_are_what_they_were(family):
    sig, psum, fwd, last = PARENTS[family]
    cfg = getattr(models, family + "_config")("tiny", vocab_size=VOCAB, max_seq_len=512,
                                              dtype=jnp.float32)
    assert not (cfg.attn_gate or cfg.qk_norm) and cfg.full_layer_rope
    assert cfg.moe is None or not cfg.moe.share
    p = transformer.init(jax.random.PRNGKey(3), cfg)
    paths = sorted((jax.tree_util.keystr(k), tuple(v.shape), str(v.dtype))
                   for k, v in jax.tree_util.tree_leaves_with_path(p))
    assert hashlib.sha256(repr(paths).encode()).hexdigest()[:16] == sig
    assert float(sum(jnp.abs(v.astype(jnp.float32)).sum()
                     for v in jax.tree.leaves(p))) == pytest.approx(psum, rel=1e-6)
    toks = np.random.default_rng(0).integers(0, VOCAB, (1, 256), dtype=np.int32)
    logits = transformer.forward(p, toks, cfg)[0]
    assert float(jnp.abs(logits).sum()) == pytest.approx(fwd, rel=1e-5)
    pl, kv = decoding.prefill(p, jnp.asarray(toks), jnp.int32(200), cfg)
    assert float(jnp.abs(pl).sum()) == pytest.approx(last, rel=1e-5)
    assert "expert_counts" not in kv


# ------------------------------------------------ what is built, what is refused


def _plain(**kw):
    base = dict(vocab_size=VOCAB, d_model=64, n_layers=4, n_heads=4, n_kv_heads=2, d_head=16,
                d_ff=64, max_seq_len=256, dtype=jnp.float32)
    base.update(kw)
    return transformer.TransformerConfig(**base)


MOE = transformer.MoEConfig(num_experts=8, top_k=2, capacity_factor=None)
BUILT = {
    "window_after_dense": dict(n_layers=5, n_dense_layers=1, d_ff_dense=96, window=32, moe=MOE),
    "sandwich_with_window": dict(window=32, sandwich_norms=True),
    "sandwich_with_dense": dict(n_dense_layers=1, d_ff_dense=96, sandwich_norms=True, moe=MOE),
    "scaled_embedding_with_all": dict(n_layers=5, n_dense_layers=1, d_ff_dense=96, window=32,
                                      moe=MOE, sandwich_norms=True, embedding_multiplier=8.0),
    "gate_and_norms_alone": dict(attn_gate=True, qk_norm=True),
    "no_positions_on_full": dict(window=32, full_layer_rope=False),
}


@pytest.mark.parametrize("case", list(BUILT))
def test_each_lifted_combination_is_built(case):
    """`_check` lets it through BECAUSE the paths carry it: the forward, the
    unchunked prefill and decode steps through the cache give one model."""
    cfg = _plain(**BUILT[case])
    p = _params(cfg)
    n, steps = 70, 5
    tokens = _tokens(n + steps + 1)
    want, _ = transformer.forward(p, tokens[None, :-1], cfg)
    padded = np.zeros((1, 128), np.int32)
    padded[0, :n] = tokens[:n]
    logits, kv = decoding.prefill(p, jnp.asarray(padded), jnp.int32(n), cfg)
    assert _close(logits, want[0, n - 1]) < TOL
    state = dp.init_paged_state(cfg, 2, 256, 24, PAGE)
    row = np.zeros((256 // PAGE,), np.int32)
    row[:10] = 1 + np.arange(10)
    state = dp.insert_sequence_paged(state, 0, kv, jnp.int32(n), jnp.int32(tokens[n]),
                                     jnp.asarray(row), cfg)
    for i in range(steps):
        state, step = dp.decode_step_paged_ragged(p, state, cfg, 8, False)
        assert _close(step[0], want[0, n + i]) < TOL, i
        state = decoding.commit_tokens(state, jnp.full((2,), tokens[n + i + 1], jnp.int32))


SSM = transformer.SSMConfig(n_heads=8, d_head=16, d_state=16, chunk=8, period=2, attn_at=1)
REFUSED = [
    (dict(ssm=SSM, attn_gate=True), "state-space layers"),
    (dict(ssm=SSM, window=32), "state-space layers"),
    (dict(ssm=SSM, sandwich_norms=True), "state-space layers"),
    (dict(residual_multiplier=0.5, window=32), "scalar multipliers"),
    (dict(kv_lora_rank=32, n_kv_heads=None, window=32), "latent attention"),
    (dict(kv_lora_rank=32, n_kv_heads=None, sandwich_norms=True), "latent attention"),
    (dict(kv_lora_rank=32, n_kv_heads=None, attn_gate=True), "latent attention"),
    (dict(n_passes=2, window=32), "looped stack"),
    (dict(n_passes=2, n_dense_layers=1, d_ff_dense=96), "looped stack"),
    (dict(exit_gate=True, window=32), "looped stack"),
    (dict(full_layer_rope=False), "full_layer_rope"),
    (dict(window=32, pos="learned"), "learned positions"),
    (dict(n_layers=6, n_dense_layers=1, d_ff_dense=96, window=32), "whole periods"),
    (dict(moe=dataclasses.replace(MOE, experts_held=4, capacity_factor=1.25)), "share"),
    (dict(moe=dataclasses.replace(MOE, experts_held=4, first_expert=6)), "share"),
]


@pytest.mark.parametrize("kwargs,reason", REFUSED,
                         ids=[f"{i}_{r.split()[0]}" for i, (_, r) in enumerate(REFUSED)])
def test_each_kept_refusal_is_raised_with_its_reason(kwargs, reason):
    with pytest.raises(ValueError, match=reason):
        transformer.init(jax.random.PRNGKey(0), _plain(**kwargs))


def test_the_paths_not_carried_refuse_the_new_fields(model):
    cfg, _ = model
    with pytest.raises(NotImplementedError, match="an attention gate or q/k norms"):
        decoding.init_lora_bank(_plain(attn_gate=True), 2, 4)
    with pytest.raises(NotImplementedError, match="window layers"):
        decoding.prefill_batch(None, jnp.zeros((2, 8), jnp.int32), jnp.zeros((2,)), cfg)
    with pytest.raises(ValueError, match="sequence-parallel"):
        transformer.forward(_params(cfg), _tokens(8)[None], cfg, sp_axis="sp")


# --------------------------------------------------------------- the engine


def _engine(cfg, p, **kw):
    from ray_tpu.llm.engine import TPUEngine

    base = dict(max_slots=3, max_len=MAX_LEN, min_bucket=32, page_size=PAGE, num_pages=100,
                prefill_chunk=64)
    base.update(kw)
    return TPUEngine(cfg, p, **base)


@pytest.mark.parametrize("kwargs,what", [
    (dict(max_loras=2), "max_loras"),
    (dict(mesh="a mesh"), "tensor-parallel mesh"),
    (dict(enable_prefix_cache=True), "enable_prefix_cache"),
])
def test_what_the_engine_does_not_carry_is_refused_at_construction(model, kwargs, what):
    cfg, p = model
    with pytest.raises(ValueError, match=what) as e:
        _engine(cfg, p, **kwargs)
    if "prefix" not in what:      # every kind of this stack by name
        assert "window layers, leading dense layers and sandwich norms" in str(e.value)
    else:
        assert "window layers" in str(e.value)


def _greedy(cfg, p, prompt, k):
    """The reference's own greedy continuation."""
    toks = list(prompt)
    for _ in range(k):
        logits, _ = reference.forward(p, jnp.asarray(toks, jnp.int32), _sizes(cfg))
        toks.append(int(jnp.argmax(logits[-1])))
    return toks[len(prompt):]


def test_engine_serves_the_share_and_counts_it(model):
    """Through TPUEngine: a prompt three windows long in chunks, greedy decode,
    the reference's own tokens; the PD plane refused; the counters of the
    share: routed slots and calls of what was dispatched, held slots and
    groups with rows as the device counted them, read with the tokens."""
    from ray_tpu.llm.engine import SamplingParams, _iter_request

    cfg, p = model
    eng = _engine(cfg, p)
    try:
        assert eng.ring == W // PAGE + 1 + 4 and eng.state["wkp"].shape[0] == 8
        with pytest.raises(NotImplementedError, match="window layers"):
            eng.submit_prefilled(length=4)
        prompt = _tokens(3 * W - 5).tolist()
        req = eng.submit(prompt, SamplingParams(max_tokens=5, temperature=0.0))
        assert list(_iter_request(req)) == _greedy(cfg, p, prompt, 5)
        deadline = time.monotonic() + 30.0
        while eng.stats()["active"] and time.monotonic() < deadline:
            time.sleep(0.01)
        experts = eng.stats()["experts"]
        # two chunks of 64 (the tail padded to 32: 27 tokens) and 4 decode
        # steps of 3 slots, 4 slots a token in each of 8 expert layers
        padded = 64 + 32 + 4 * 3
        assert experts["slots_routed"] == padded * 4 * 8
        assert experts["calls"] == (2 + 4) * 8
        assert experts["tokens_sorted"] == 4 * 3 and experts["tokens_onehot"] == 64 + 32
        assert 0 < experts["slots_held"] < experts["slots_routed"]
        assert 0 < experts["groups_with_rows"] <= min(experts["slots_held"], 8 * experts["calls"])
        # the count by hand of the two prefill programs, which this request's
        # counters hold beside the steps'
        by_hand = 0
        padded_prompt = np.zeros((1, 64), np.int32)
        padded_prompt[0] = prompt[:64]
        _, kv = decoding.prefill(p, jnp.asarray(padded_prompt), jnp.int32(64), cfg)
        by_hand += int(kv["expert_counts"][0])
        assert experts["slots_held"] > by_hand > 0
        assert eng.stats()["free_pages"] == eng.num_pages - 1
    finally:
        eng.shutdown()
