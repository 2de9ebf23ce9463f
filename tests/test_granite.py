"""Granite 4.0-H (state-space layers and attention layers in one stack: a
Mamba-2 mixer on nine layers of ten, a fixed recurrent state a slot beside
pages that grow, four scalar multipliers, no positions) against its plain
reference (`chipbench/reference/granite.py`: the recurrence token by token)
at a tiny size on the CPU, seeded weights: one whole period of ten layers in
the published order (five state-space layers, the attention layer, four
more), the scan's chunk 8.

Tolerances: everything runs in float32 here, so program and reference differ
by summation order only: 1e-4 of the largest logit (measured 1e-7 to 3e-6);
gradients 1e-3 of a leaf's norm (measured under 2e-5).
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

from chipbench.reference import granite as reference  # noqa: E402
from ray_tpu import ops  # noqa: E402
from ray_tpu.models import (decoding, granite_config, llama_config, mellum_config,  # noqa: E402
                            mixtral_config, ouro_config, transformer)
from ray_tpu.models import decoding_paged as dp  # noqa: E402

VOCAB, PAGE, MAX_LEN = 300, 16, 256
TOL = 1e-4


def _cfg(**kw):
    return granite_config("tiny", vocab_size=VOCAB, max_seq_len=1024, dtype=jnp.float32, **kw)


CHUNK = _cfg().ssm.chunk                             # 8
LM, LA = _cfg().n_ssm_layers, _cfg().n_attn_layers   # 9 and 1


def _sizes(cfg):
    return dict(n_layers=cfg.n_layers, norm_eps=cfg.norm_eps,
                attn_layers=[l for l in range(cfg.n_layers) if transformer.is_attn_layer(cfg, l)],
                ssm_heads=cfg.ssm.n_heads, ssm_d_state=cfg.ssm.d_state,
                embedding_multiplier=cfg.embedding_multiplier,
                residual_multiplier=cfg.residual_multiplier,
                attention_multiplier=cfg.softmax_scale, logits_scaling=cfg.logits_scaling)


def _params(cfg, seed=3):
    p = transformer.init(jax.random.PRNGKey(seed), cfg)
    # norm weights, the skip D and the convolution's bias away from one and
    # zero, so that one left out would show
    return jax.tree.map(lambda x: x + 0.05 * jax.random.normal(
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
def kernels_interpreted(monkeypatch):
    import ray_tpu.ops.ragged_paged_attention as rpa
    import ray_tpu.ops.ssm as ssm

    for module, name in ((rpa, "_ragged_kernel_call"), (ssm, "_update_kernel_call")):
        real = getattr(module, name)
        monkeypatch.setattr(module, name, lambda *a, interpret, _real=real, **kw: _real(
            *a, interpret=True, **kw))
    dp.decode_step_paged_ragged.clear_cache()
    yield
    dp.decode_step_paged_ragged.clear_cache()


def _bucket_prefill(cfg, p, tokens, n, bucket):
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :n] = tokens[:n]
    return decoding.prefill(p, jnp.asarray(padded), jnp.int32(n), cfg)


# --------------------------------------------------- the stack, whole forward


def test_the_layers_are_stacked_by_kind_in_the_published_order(model):
    cfg, p = model
    kinds = ["attention" if transformer.is_attn_layer(cfg, l) else "mamba"
             for l in range(cfg.n_layers)]
    assert kinds == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert "attn" in p["layers"] and "mixer" not in p["layers"]
    assert "mixer" in p["ssm_layers"] and "attn" not in p["ssm_layers"]
    assert p["layers"]["norm1"]["w"].shape[0] == LA and p["ssm_layers"]["norm1"]["w"].shape[0] == LM
    assert "pos_embed" not in p and "lm_head" not in p
    big = granite_config()
    assert [l for l in range(40) if transformer.is_attn_layer(big, l)] == [5, 15, 25, 35]
    assert big.num_params() == 3_191_396_096
    assert (big.ssm.d_inner, big.ssm.conv_dim, big.ssm.in_dim) == (4096, 4352, 8512)
    axes = transformer.logical_axes(cfg)
    assert jax.tree.structure(jax.tree.map(lambda a: 0, p)) == jax.tree.structure(
        jax.tree.map(lambda a: 0, axes, is_leaf=lambda x: isinstance(x, tuple)))


@pytest.mark.parametrize("n_tokens", [1, 7, 23, 100])
def test_forward_agrees_with_the_reference(model, n_tokens):
    cfg, p = model
    tokens = _tokens(n_tokens)
    want, _ = reference.forward(p, jnp.asarray(tokens), _sizes(cfg))
    got, _ = transformer.forward(p, jnp.asarray(tokens)[None], cfg)
    assert _close(got[0], want) < TOL


BROKEN = {
    "embedding_multiplier": dict(embedding_multiplier=1.0),
    "residual_multiplier": dict(residual_multiplier=1.0),
    "logits_scaling": dict(logits_scaling=1.0),
    "attention_scale_rsqrt": dict(attention_multiplier=None),
    "rope_applied": dict(pos="rope"),
}


@pytest.mark.parametrize("what", sorted(BROKEN))
def test_the_reference_tells_a_multiplier_left_out(model, what):
    cfg, p = model
    tokens = _tokens(40)
    want, _ = reference.forward(p, jnp.asarray(tokens), _sizes(cfg))
    got, _ = transformer.forward(p, jnp.asarray(tokens)[None],
                                 dataclasses.replace(cfg, **BROKEN[what]))
    assert _close(got[0], want) > 5 * TOL


def test_loss_and_every_gradient_agree_with_the_reference(model):
    cfg, p = model
    tokens = _tokens(20, seed=4)
    want, want_g = jax.value_and_grad(reference.loss)(p, jnp.asarray(tokens), _sizes(cfg))
    got, got_g = jax.value_and_grad(transformer.loss_fn)(p, jnp.asarray(tokens)[None], cfg)
    assert abs(float(got) - float(want)) < 1e-5
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want_g))
    for path, g in jax.tree_util.tree_leaves_with_path(got_g):
        w = flat_want[path]
        assert float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w)) < 1e-3, path


# ------------------------------------------------------ the scan and the state


def _mixer_inputs(T, seed=1):
    rng = np.random.default_rng(seed)
    H, P, N = 4, 8, 16
    return (jnp.asarray(rng.normal(size=(T, H, P)), jnp.float32),
            jnp.asarray(rng.uniform(0.01, 0.5, (T, H)), jnp.float32),
            -jnp.asarray(rng.uniform(1.0, 8.0, (H,)), jnp.float32),
            jnp.asarray(rng.normal(size=(T, N)), jnp.float32),
            jnp.asarray(rng.normal(size=(T, N)), jnp.float32))


@pytest.mark.parametrize("T", [1, 5, 8, 9, 21, 64])
def test_the_chunked_scan_is_the_recurrence(T):
    x, dt, A, B, C = _mixer_inputs(T)
    want_y, want_h = reference.recurrence(x, dt, A, B, C, jnp.zeros((4,)))
    y, h = ops.ssm_chunk_scan(x, dt, A, B, C, chunk=8, dtype=jnp.float32)
    assert _close(y, want_y) < 1e-5 and _close(h, want_h) < 1e-5
    # carried: the second part run on from the first part's state
    cut = T // 2
    if cut:
        _, h1 = ops.ssm_chunk_scan(x[:cut], dt[:cut], A, B[:cut], C[:cut], chunk=8,
                                   dtype=jnp.float32)
        y2, h2 = ops.ssm_chunk_scan(x[cut:], dt[cut:], A, B[cut:], C[cut:], chunk=8,
                                    state=h1, dtype=jnp.float32)
        assert _close(y2, want_y[cut:]) < 1e-5 and _close(h2, want_h) < 1e-5


@pytest.mark.parametrize("n", [1, 2, 3, CHUNK - 1, CHUNK, CHUNK + 1, 29])
def test_a_padded_bucket_leaves_state_and_tail_as_at_n(model, n):
    """Padding to a bucket must not advance the recurrent state, and the
    tail is the last three REAL inputs (zeros before the row's start)."""
    cfg, p = model
    tokens = _tokens(40, seed=n)
    _, exact = _bucket_prefill(cfg, p, tokens, n, n)            # no padding at all
    for bucket in (16, 32, 64):
        if bucket < n:
            continue
        logits, kv = _bucket_prefill(cfg, p, tokens + 1, n, bucket)  # other padding too
        _, kv = _bucket_prefill(cfg, p, tokens, n, bucket)
        assert kv["ssm"].shape == (LM, 8, 16, 16) and kv["conv"].shape == (LM, 3, 160)
        assert _close(kv["ssm"], exact["ssm"]) < 1e-5, bucket
        assert float(jnp.abs(kv["conv"] - exact["conv"]).max()) < 1e-6, bucket
    if n < 3:
        assert float(jnp.abs(exact["conv"][:, :3 - n]).max()) == 0.0


def _prefilled(cfg, p, tokens, n, bucket, slot=1, slots=3, state=None, pages=None):
    logits, kv = _bucket_prefill(cfg, p, tokens, n, bucket)
    if state is None:
        state = dp.init_paged_state(cfg, slots, MAX_LEN, 40, PAGE)
    row = np.zeros((MAX_LEN // PAGE,), np.int32)
    ids = pages if pages is not None else 1 + np.arange(MAX_LEN // PAGE)
    row[:len(ids)] = ids
    state = dp.insert_sequence_paged(state, slot, kv, jnp.int32(n), jnp.int32(tokens[n]),
                                     jnp.asarray(row), cfg)
    return logits, kv, state, row


@pytest.mark.parametrize("kernel", [False, True], ids=["mirror", "kernel"])
def test_prefill_then_paged_decode_agrees_with_the_full_forward(model, kernels_interpreted,
                                                                kernel):
    """A prompt in a padded bucket, then decode steps across page boundaries
    through the paged cache and the slot's recurrent state, against the
    reference's full forward at EVERY position."""
    cfg, p = model
    n, steps = 41, 24
    tokens = _tokens(n + steps + 1)
    want, _ = reference.forward(p, jnp.asarray(tokens[:-1]), _sizes(cfg))
    logits, _, state, _ = _prefilled(cfg, p, tokens, n, 64)
    assert state["kp"].shape == (LA, 40, PAGE, 1, 128)      # two heads of 64 a row
    assert state["ssm"].shape == (LM, 3, 8, 16, 16) and state["ssm"].dtype == jnp.float32
    assert _close(logits, want[n - 1]) < TOL
    for i in range(steps):
        state, step = dp.decode_step_paged_ragged(p, state, cfg, 8, kernel)
        assert _close(step[1], want[n + i]) < TOL, i
        # the rows that hold nothing keep what they had: zeros
        assert float(jnp.abs(state["ssm"][:, 0]).max()) == 0.0
        state = decoding.commit_tokens(state, jnp.full((3,), tokens[n + i + 1], jnp.int32))
    assert int(state["length"][1]) == n + steps


def test_rows_join_at_different_steps_and_a_slot_is_taken_again(model):
    """Two rows of different length admitted at different steps; the first
    released and its slot given to a third row: nothing of the last occupant
    is left in the slot's state."""
    cfg, p = model
    rows = {"a": (_tokens(60, seed=1), 19), "b": (_tokens(60, seed=2), 33),
            "c": (_tokens(60, seed=3), 7)}
    want = {k: reference.forward(p, jnp.asarray(t[:-1]), _sizes(cfg))[0]
            for k, (t, _) in rows.items()}
    pos = {k: n for k, (_, n) in rows.items()}

    def step(state, live):
        state, logits = dp.decode_step_paged_ragged(p, state, cfg, 8, False)
        nxt = np.zeros((2,), np.int32)
        for name, slot in live.items():
            assert _close(logits[slot], want[name][pos[name]]) < TOL, (name, pos[name])
            pos[name] += 1
            nxt[slot] = rows[name][0][pos[name]]
        return decoding.commit_tokens(state, jnp.asarray(nxt))

    _, _, state, _ = _prefilled(cfg, p, *rows["a"], 32, slot=0, slots=2,
                                pages=[1, 2, 3, 4])
    for _ in range(3):
        state = step(state, {"a": 0})
    _, _, state, _ = _prefilled(cfg, p, *rows["b"], 64, slot=1, state=state,
                                pages=[5, 6, 7, 8, 9])
    for _ in range(4):
        state = step(state, {"a": 0, "b": 1})
    state = dp.release_slot_paged(state, 0)
    state = step(state, {"b": 1})                      # slot 0 idle: stepped with dt 0
    left = state["ssm"][:, 0]
    assert float(jnp.abs(left).max()) > 0              # the last occupant's is still there
    _, kv, state, _ = _prefilled(cfg, p, *rows["c"], 16, slot=0, state=state,
                                 pages=[1, 2, 3, 4])
    assert float(jnp.abs(state["ssm"][:, 0] - kv["ssm"]).max()) == 0.0
    for _ in range(5):
        state = step(state, {"c": 0, "b": 1})


def _chunked(cfg, p, tokens, n, chunk):
    """The engine's staged prefill by hand: chunks of `chunk` (the tail padded
    to it), the attention layers' prefix gathered out of the pool, the
    recurrent state carried from chunk to chunk."""
    state = dp.init_paged_state(cfg, 2, MAX_LEN, 40, PAGE)
    row = np.zeros((MAX_LEN // PAGE,), np.int32)
    row[:] = 1 + np.random.default_rng(2).permutation(39)[:MAX_LEN // PAGE]
    carried = None
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
            assert pk.shape == (LA, npad * PAGE, 1, 128)
            logits, kv = dp.prefill_with_prefix(
                p, jnp.asarray(padded), pk, pv, jnp.int32(done), jnp.int32(live), cfg,
                row_state=carried)
        carried = {name: kv[name] for name in ("ssm", "conv")}
        pages = range(done // PAGE, (done + chunk) // PAGE)
        state = dp.write_kv_pages(state, kv, jnp.asarray(row[list(pages)]))
    return logits, state, row, carried


@pytest.mark.parametrize("chunks,chunk", [(2, 32), (3, 32), (5, 32), (3, 64)])
def test_chunked_prefill_agrees_with_one_shot_prefill(model, chunks, chunk):
    """2, 3 and 5 chunks with a padded tail chunk against one-shot prefill and
    the reference, then decode steps from the state the chunks carried."""
    cfg, p = model
    n, steps = chunk * chunks - 11, 5
    tokens = _tokens(n + steps + 1)
    one_shot, whole = _bucket_prefill(cfg, p, tokens, n, 256)
    want, _ = reference.forward(p, jnp.asarray(tokens[:-1]), _sizes(cfg))
    logits, state, row, carried = _chunked(cfg, p, tokens, n, chunk)
    assert _close(logits, one_shot) < TOL and _close(logits, want[n - 1]) < TOL
    assert _close(carried["ssm"], whole["ssm"]) < TOL
    assert _close(carried["conv"], whole["conv"]) < TOL
    assert float(jnp.abs(state["ssm"]).max()) == 0.0   # not in a slot before the row is live
    state = dp.activate_slot(state, 0, jnp.asarray(row), jnp.int32(n), jnp.int32(tokens[n]),
                             None, carried)
    for i in range(steps):
        state, step = dp.decode_step_paged_ragged(p, state, cfg, 16, False)
        assert _close(step[0], want[n + i]) < TOL, i
        state = decoding.commit_tokens(state, jnp.full((2,), tokens[n + i + 1], jnp.int32))


def test_prefill_continuation_and_decode_share_one_softmax_scale(model):
    """`TransformerConfig.softmax_scale` is the one place that gives the
    scale: with a multiplier that is NOT qk_dim ** -0.5, the one-shot prefill,
    a chunk's continuation and the decode step all follow it."""
    cfg, p = model
    assert cfg.softmax_scale == 1 / 32 != cfg.qk_dim ** -0.5
    assert llama_config("tiny").softmax_scale == llama_config("tiny").qk_dim ** -0.5
    odd = dataclasses.replace(cfg, attention_multiplier=2.0)
    n = 53
    tokens = _tokens(n + 4)
    want, _ = reference.forward(p, jnp.asarray(tokens[:-1]), _sizes(odd))
    one_shot, _ = _bucket_prefill(odd, p, tokens, n, 64)
    chunked, state, row, carried = _chunked(odd, p, tokens, n, 32)
    assert _close(one_shot, want[n - 1]) < TOL and _close(chunked, want[n - 1]) < TOL
    state = dp.activate_slot(state, 0, jnp.asarray(row), jnp.int32(n), jnp.int32(tokens[n]),
                             None, carried)
    state, step = dp.decode_step_paged_ragged(p, state, odd, 16, False)
    assert _close(step[0], want[n]) < TOL
    base, _ = _bucket_prefill(cfg, p, tokens, n, 64)
    assert _close(base, want[n - 1]) > 10 * TOL         # the scale is read


# ------------------------------------------------------------------ the kernel


def test_the_update_kernel_agrees_with_its_mirror_and_updates_in_place():
    rng = np.random.default_rng(5)
    L, R, H, P, N = 3, 4, 8, 16, 128
    state = jnp.asarray(rng.normal(size=(L, R, H, P, N)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(R, H, P)), jnp.bfloat16)
    dt = jnp.asarray(rng.uniform(0.01, 0.5, (R, H)), jnp.float32)
    A = -jnp.asarray(rng.uniform(1.0, 8.0, (H,)), jnp.float32)
    B, C = (jnp.asarray(rng.normal(size=(R, N)), jnp.bfloat16) for _ in range(2))
    live = jnp.asarray([True, True, False, True])
    rows, count = ops.live_rows(live)
    assert rows.tolist() == [0, 1, 3, 3] and count.tolist() == [3]
    assert ops.live_rows(jnp.zeros((4,), bool))[0].tolist() == [0, 0, 0, 0]
    want_s, want_y = ops.ssm_state_update(state, jnp.int32(1), x, dt, A, B, C, live=live)
    got_s, got_y = ops.ssm_state_update(state, jnp.int32(1), x, dt, A, B, C, live=live,
                                        impl="kernel", interpret=True)
    assert _close(got_y, want_y) < 1e-5 and _close(got_s, want_s) < 1e-6
    for s, y in ((got_s, got_y), (want_s, want_y)):
        assert float(jnp.abs(s[0] - state[0]).max()) == 0.0    # the other layers
        assert float(jnp.abs(s[2] - state[2]).max()) == 0.0
        assert float(jnp.abs(s[1, 2] - state[1, 2]).max()) == 0.0   # not live: bit for bit
        assert float(jnp.abs(y[2]).max()) == 0.0
        assert float(jnp.abs(s[1, 0] - state[1, 0]).max()) > 0
    # no row live (the engine never steps then): nothing moves
    idle_s, idle_y = ops.ssm_state_update(state, jnp.int32(1), x, dt, A, B, C,
                                          live=jnp.zeros((R,), bool), impl="kernel",
                                          interpret=True)
    assert float(jnp.abs(idle_s - state).max()) == 0.0 and float(jnp.abs(idle_y).max()) == 0.0
    every_s, _ = ops.ssm_state_update(state, jnp.int32(1), x, dt, A, B, C, impl="kernel",
                                      interpret=True)
    assert float(jnp.abs(every_s[1, 2] - state[1, 2]).max()) > 0
    # against the recurrence's own arithmetic, one step from that state
    h = jnp.exp(dt[0] * A)[:, None, None] * state[1, 0] + (
        dt[0][:, None] * x[0].astype(jnp.float32))[:, :, None] * B[0].astype(jnp.float32)
    assert _close(got_s[1, 0], h) < 1e-6
    with pytest.raises(ValueError, match="impl"):
        ops.ssm_state_update(state, jnp.int32(0), x, dt, A, B, C, impl="xla")


def test_the_on_chip_comparison_of_the_update_kernel_runs_here_interpreted():
    """`chip_smoke.compare_state_update`, what a chip call runs at a row's
    published shape over 96 slots: scattered live rows that change from step
    to step, slots taken again, one row, none, all; the whole state against
    the `jax.numpy` form's after every step."""
    import chip_smoke

    r = chip_smoke.compare_state_update(
        dict(layers=3, slots=12, heads=4, head_dim=8, d_state=128), interpret=True)
    assert r["ok"] and [s["live"] for s in r["steps"]] == [4, 5, 1, 0, 12]
    assert all(s["others_bit_equal"] and s["dead_y_zero"] for s in r["steps"])
    assert r["steps"][1]["taken"] < r["steps"][1]["live"]         # some rows stayed


# ------------------------------------------------------------ what is refused


def _engine(cfg, p, **kw):
    from ray_tpu.llm.engine import TPUEngine

    kw = {**dict(max_slots=3, max_len=MAX_LEN, min_bucket=16, page_size=PAGE,
                 num_pages=40), **kw}
    return TPUEngine(cfg, p, **kw)


@pytest.mark.parametrize("kwargs,what", [
    (dict(max_loras=2), "state-space layers.*max_loras"),
    (dict(mesh="a mesh"), "state-space layers.*tensor-parallel mesh"),
    (dict(enable_prefix_cache=True), "state-space layers.*enable_prefix_cache.*snapshot"),
])
def test_what_the_engine_does_not_carry_is_refused_at_construction(model, kwargs, what):
    cfg, p = model
    with pytest.raises(ValueError, match=what):
        _engine(cfg, p, **kwargs)


def test_what_the_model_code_does_not_carry_is_refused(model):
    cfg, p = model
    with pytest.raises(NotImplementedError, match="state-space layers"):
        decoding.init_lora_bank(cfg, 2, 4)
    with pytest.raises(NotImplementedError, match="state-space layers"):
        decoding.prefill_batch(p, jnp.zeros((2, 16), jnp.int32), jnp.ones((2,), jnp.int32), cfg)
    for kwargs in (dict(n_layers=12), dict(ssm=dataclasses.replace(cfg.ssm, attn_at=10)),
                   dict(act="gelu")):
        with pytest.raises(ValueError, match="whole periods"):
            transformer.init(jax.random.PRNGKey(0), dataclasses.replace(cfg, **kwargs))
    for kwargs in (dict(window=64, window_period=2), dict(n_passes=2), dict(bias=True),
                   dict(kv_lora_rank=32, n_kv_heads=None)):
        with pytest.raises(ValueError, match="state-space layers"):
            transformer.init(jax.random.PRNGKey(0), dataclasses.replace(cfg, **kwargs))
    for kwargs in (dict(d_head=48), dict(n_kv_heads=1, n_heads=4)):   # no whole rows of 128
        with pytest.raises(ValueError, match="kv_packed"):
            transformer.init(jax.random.PRNGKey(0), dataclasses.replace(cfg, **kwargs))
    plain = llama_config("tiny", vocab_size=VOCAB, kv_packed=True, d_head=64, n_kv_heads=2)
    assert plain.kv_row == (1, 128)
    with pytest.raises(ValueError, match="kv_packed.*max_loras"):
        _engine(plain, None, max_loras=2)
    with pytest.raises(ValueError, match="'rope', 'learned' or 'none'"):
        transformer.init(jax.random.PRNGKey(0), dataclasses.replace(cfg, pos="nope"))
    with pytest.raises(ValueError, match="sequence-parallel"):
        transformer.forward(p, jnp.zeros((1, 8), jnp.int32), cfg, sp_axis="sp")


# ------------------------------------------------------------ the engine


def _greedy(cfg, p, prompt, k):
    tokens, out = list(prompt), []
    for _ in range(k):
        logits, _ = reference.forward(p, jnp.asarray(tokens, jnp.int32), _sizes(cfg))
        out.append(int(jnp.argmax(logits[-1])))
        tokens.append(out[-1])
    return out


def _idle(eng, timeout=30.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if not (eng._by_slot or eng._prefilling or eng._backlog or eng._waiting.qsize()
                or eng._unread):
            return
        time.sleep(0.01)
    raise AssertionError("the engine did not come to rest")


@pytest.mark.parametrize("kwargs", [{}, dict(prefill_chunk=32)], ids=["plain", "chunked"])
def test_engine_serves_concurrent_rows_and_counts_the_state(model, kwargs):
    """Through TPUEngine, greedy: five requests at once over three slots (so
    slots are released and taken again while other rows live) return the
    reference's own greedy tokens, prefilled whole or in chunks; the state's
    counters move as the rows do."""
    from ray_tpu.llm.engine import SamplingParams, _iter_request

    cfg, p = model
    eng = _engine(cfg, p, **kwargs)
    try:
        with pytest.raises(NotImplementedError, match="state-space layers"):
            eng.submit_prefilled(length=4)
        lengths, answers = [23, 70, 9, 41, 100], [6, 4, 7, 5, 3]
        prompts = [_tokens(n, seed=10 + i).tolist() for i, n in enumerate(lengths)]
        reqs = [eng.submit(prompt, SamplingParams(max_tokens=k, temperature=0.0))
                for prompt, k in zip(prompts, answers)]
        outs = [list(_iter_request(r)) for r in reqs]
        _idle(eng)
        assert outs == [_greedy(cfg, p, prompt, k) for prompt, k in zip(prompts, answers)]
        cache = eng.stats()["cache"]
        row_bytes = LM * (8 * 16 * 16 * 4 + 3 * 160 * 4)        # float32 model: tail too
        assert cache["state_bytes_per_row"] == row_bytes
        assert cache["bytes_per_token"] == LA * 2 * 2 * 64 * 4
        # a row takes part in one decode step a token after its first
        assert eng.stats()["decode_slot_steps"] == sum(answers) - len(answers)
        if kwargs:
            assert eng.stats()["prefill_chunks_run"] >= 3 + 2 + 4
        assert eng.stats()["loops"]["planes"] == LA
        assert sorted(eng._free_pages) == list(range(1, eng.num_pages))
        assert sorted(eng._free) == [0, 1, 2]
    finally:
        eng.shutdown()


def test_the_family_is_built_through_llm_config():
    from ray_tpu.llm import LLMConfig, ModelLoadingConfig

    cfg, params = LLMConfig(
        model_family="granite", model_loading_config=ModelLoadingConfig(model_id="tiny"),
        model_kwargs=dict(vocab_size=VOCAB, max_seq_len=64), accelerator_type=None,
    ).build_model()
    assert cfg.ssm is not None and "ssm_layers" in params and cfg.pos == "none"


# ------------------------------------------------------------ the other families


@pytest.mark.parametrize("family", ["llama", "mixtral", "mellum", "ouro"])
def test_the_new_fields_are_inert_at_their_defaults(family):
    """A family that sets none of the new fields has the parent's parameter
    tree and traces no operation of theirs: no multiply by a multiplier, no
    state-space parameter, and its softmax scale is qk_dim ** -0.5."""
    cfg = {"llama": llama_config, "mixtral": mixtral_config, "mellum": mellum_config,
           "ouro": ouro_config}[family]("tiny", vocab_size=VOCAB, dtype=jnp.float32)
    assert (cfg.ssm, cfg.embedding_multiplier, cfg.residual_multiplier, cfg.logits_scaling,
            cfg.attention_multiplier) == (None, 1.0, 1.0, 1.0, None)
    assert cfg.softmax_scale == cfg.qk_dim ** -0.5 and cfg.n_attn_layers == cfg.n_layers
    assert (cfg.init_out_std, cfg.init_attn_std) == (None, None)
    assert cfg.n_planes == cfg.n_passes * cfg.n_layers and cfg.n_ssm_layers == 0
    p = jax.eval_shape(lambda: transformer.init(jax.random.PRNGKey(0), cfg))
    assert "ssm_layers" not in p
    state = jax.eval_shape(lambda: dp.init_paged_state(cfg, 2, 128, 9, 16))
    assert "ssm" not in state and "conv" not in state
    tokens = jnp.zeros((1, 16), jnp.int32)
    text = str(jax.make_jaxpr(lambda q: transformer.forward(q, tokens, cfg)[0])(p))
    want = str(jax.make_jaxpr(lambda q: transformer.forward(
        q, tokens, dataclasses.replace(cfg, attention_multiplier=cfg.qk_dim ** -0.5))[0])(p))
    assert text == want                                 # the scale is the same constant
    assert "softplus" not in text and "log1p" not in text and "expm1" not in text
