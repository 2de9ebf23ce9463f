"""Ouro (a stack of layers run several times over shared weights, a cache
plane for every layer APPLICATION, sandwich norms, the final norm closing
every pass, the exit gate) against its plain reference
(`chipbench/reference/ouro.py`) at a tiny size on the CPU, seeded weights:
6 layers, 3 passes, so that passes and layers cannot stand in for each other.

Tolerances: everything runs in float32 here, so program and reference differ
by summation order only: 1e-4 of the largest logit (measured 2e-7 to 2e-6);
gradients 1e-3 of a leaf's norm (measured under 1e-5). The kernel
(interpreted) against its mirror is the ragged launch other files compare
bitwise; here both go against the reference.
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

from chipbench import loop_faults  # noqa: E402
from chipbench.reference import ouro as reference  # noqa: E402
from ray_tpu.models import decoding, llama_config, ouro_config, transformer  # noqa: E402
from ray_tpu.models import decoding_paged as dp  # noqa: E402

VOCAB, PAGE, MAX_LEN = 300, 16, 512
TOL = 1e-4


def _cfg(**kw):
    return ouro_config("tiny", vocab_size=VOCAB, max_seq_len=1024, dtype=jnp.float32, **kw)


T, L = _cfg().n_passes, _cfg().n_layers            # 3 passes over 6 layers


def _sizes(cfg):
    return dict(n_layers=cfg.n_layers, n_passes=cfg.n_passes, rope_theta=cfg.rope_theta,
                norm_eps=cfg.norm_eps)


def _params(cfg, seed=3):
    p = transformer.init(jax.random.PRNGKey(seed), cfg)
    # norm weights and the gate's bias away from one and zero, so that a norm
    # left out would show; a gate an order larger, so that the passes' exit
    # probabilities differ by more than rounding
    p = jax.tree.map(lambda x: x + 0.05 * jax.random.normal(
        jax.random.PRNGKey(7), x.shape, x.dtype), p)
    return {**p, "exit_gate": {"w": 10 * p["exit_gate"]["w"], "b": p["exit_gate"]["b"] - 1.0}}


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


# --------------------------------------------------- the stack, whole forward


@pytest.mark.parametrize("n_tokens", [1, 23, 150])
def test_forward_agrees_with_the_reference(model, n_tokens):
    cfg, p = model
    tokens = _tokens(n_tokens)
    logits, aux = transformer.forward(p, tokens[None], cfg)
    want, margin = reference.forward(p, jnp.asarray(tokens), _sizes(cfg))
    assert _close(logits[0], want) < TOL
    # a dense model has no router: margins no `router_tie` reaches, finite
    assert margin.shape == (L, n_tokens, 2) and float(margin.min()) > 1e6
    assert np.isfinite(np.asarray(margin)).all() and float(aux) == 0.0


@pytest.mark.parametrize("broken,what", [
    (dict(n_passes=1), "the stack run once"),
    (dict(n_passes=2), "one pass short"),
    (dict(sandwich_norms=False), "the norms on the sublayers' outputs skipped"),
    (dict(rope_theta=1e4), "another rope"),
    ("pass_norm_left_out", "the final norm after the last pass only"),
])
def test_the_reference_tells_a_wrong_stack(model, broken, what):
    cfg, p = model
    tokens = _tokens(40)
    want, _ = reference.forward(p, jnp.asarray(tokens), _sizes(cfg))
    if isinstance(broken, str):
        # `forward` is not jitted: it finds the planted close_pass as it runs
        wrong = loop_faults._last_norm_only(transformer.close_pass)
        try:
            transformer.close_pass, sound = wrong, transformer.close_pass
            got, _ = transformer.forward(p, tokens[None], cfg)
        finally:
            transformer.close_pass = sound
    else:
        got, _ = transformer.forward(p, tokens[None], dataclasses.replace(cfg, **broken))
    assert _close(got[0], want) > 100 * TOL, what


def test_the_weights_are_counted_and_held_once(model):
    """48 layers applied four times are 48 layers of weights: `init`,
    `logical_axes` and `num_params` know nothing of the passes."""
    cfg, p = model
    once = dataclasses.replace(cfg, n_passes=1)
    assert cfg.num_params() == once.num_params()
    assert cfg.n_planes == T * L == 18
    assert all(x.shape[0] == L for x in jax.tree.leaves(p["layers"]))
    axes = transformer.logical_axes(cfg)
    assert jax.tree.structure(jax.tree.map(lambda x: 0, p)) == jax.tree.structure(
        jax.tree.map(lambda t: 0, axes, is_leaf=lambda x: isinstance(x, tuple)))
    big = ouro_config("2.6b")
    layer = 4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048
    assert big.num_params() == 48 * layer + 2 * 49152 * 2048 + 2048 + 2048 + 1
    assert (big.n_passes, big.n_planes, big.kv_heads, big.head_dim) == (4, 192, 16, 128)


def test_the_new_fields_are_inert_at_their_defaults():
    """A configuration that sets none of them has no parameter, no cache
    state and no scope of the loop: its programs are what they were."""
    cfg = llama_config("tiny", vocab_size=VOCAB, max_seq_len=256, dtype=jnp.float32)
    assert (cfg.n_passes, cfg.sandwich_norms, cfg.exit_gate) == (1, False, False)
    assert cfg.n_planes == cfg.n_layers
    p = transformer.init(jax.random.PRNGKey(0), cfg)
    assert set(p) == {"embed", "layers", "final_norm", "lm_head"}
    assert set(p["layers"]) == {"norm1", "attn", "norm2", "mlp"}
    state = dp.init_paged_state(cfg, 2, 64, 8, PAGE)
    assert "exit_cdf" not in state and state["kp"].shape[0] == cfg.n_layers
    text = dp.decode_step_paged_ragged.lower(p, state, cfg, 2, False).as_text(debug_info=True)
    assert "loop_pass" not in text
    looped = _cfg()
    text = dp.decode_step_paged_ragged.lower(
        transformer.init(jax.random.PRNGKey(0), looped),
        dp.init_paged_state(looped, 2, 64, 8, PAGE), looped, 2, False).as_text(debug_info=True)
    assert "ray_tpu:loop_pass" in text


def test_the_output_norms_start_where_the_family_says():
    """The scale a randomly initialised block's output norms start at is the
    family's (`ouro.OUTPUT_NORM_INIT`), not the shared initialiser's: a
    configuration that only asks for sandwich norms gets every norm at 1."""
    from ray_tpu.models import ouro

    p = transformer.init(jax.random.PRNGKey(0), _cfg())
    for name in ("post_attn_norm", "post_mlp_norm"):
        np.testing.assert_allclose(np.asarray(p["layers"][name]["w"], np.float32),
                                   ouro.OUTPUT_NORM_INIT, rtol=1e-6)
    for name in ("norm1", "norm2"):
        assert np.all(np.asarray(p["layers"][name]["w"]) == 1)
    plain = llama_config("tiny", vocab_size=VOCAB, dtype=jnp.float32, sandwich_norms=True)
    assert plain.sandwich_norm_init == 1.0
    q = transformer.init(jax.random.PRNGKey(0), plain)
    assert np.all(np.asarray(q["layers"]["post_attn_norm"]["w"]) == 1)


def test_a_looped_stack_without_its_closing_norm_is_refused(model):
    """`scan_layers` over several passes needs `close` (the final norm ends
    every pass); one pass runs without it as it always did."""
    cfg, p = model
    h = jnp.zeros((1, 4, cfg.d_model), jnp.float32)
    block = lambda c, layer_p: (c, None)  # noqa: E731
    with pytest.raises(ValueError, match="needs `close`"):
        transformer.scan_layers(block, h, p, cfg)
    once = dataclasses.replace(cfg, n_passes=1)
    out, _ = transformer.scan_layers(block, h, p, once)
    assert out.shape == h.shape


# ------------------------------------------------------------ the exit gate


def test_the_gates_exit_distribution_agrees_with_the_reference(model):
    """p_t a position: lambda_t times the probability of having stayed, the
    last pass taking what is left; it sums to one at every position."""
    cfg, p = model
    tokens = _tokens(31)
    _, _, leave = transformer.forward(p, tokens[None], cfg, return_exit=True)
    want = reference.exit_probabilities(p, jnp.asarray(tokens), _sizes(cfg))
    assert leave.shape == (T, 1, 31) and want.shape == (T, 31)
    assert float(jnp.abs(leave[:, 0] - want).max()) < 1e-5
    assert float(jnp.abs(leave.sum(0) - 1.0).max()) < 1e-6
    # the passes differ: a gate that read one pass's output thrice would not
    assert float(jnp.abs(want[0] - want[1]).max()) > 1e-3
    lam = jnp.asarray([[0.25], [0.5], [0.9]])
    assert np.allclose(transformer.exit_distribution(lam)[:, 0], [0.25, 0.375, 0.375])


# ------------------------------------------------------------- the gradients


def test_gradients_agree_with_the_reference(model):
    """`loss_fn` against the reference's loss by `jax.grad`, every leaf: a
    layer's weights are used in every pass and accumulate T contributions."""
    cfg, p = model
    tokens = _tokens(25)
    loss, grads = jax.value_and_grad(
        lambda q: transformer.loss_fn(q, tokens[None], cfg))(p)
    want_loss, want = jax.value_and_grad(
        lambda q: reference.loss(q, jnp.asarray(tokens), _sizes(cfg)))(p)
    assert abs(float(loss) - float(want_loss)) < 1e-5
    # the gate decides nothing about the logits: no gradient from this loss
    assert float(jnp.abs(grads["exit_gate"]["w"]).max()) == 0.0
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        if "exit_gate" in jax.tree_util.keystr(path):
            continue
        w = want
        for key in path:
            w = w[key.key]
        rel = float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
        assert rel < 1e-3, (jax.tree_util.keystr(path), rel)
    # one pass's share alone is not the gradient: the contributions add up
    once = jax.grad(lambda q: transformer.loss_fn(
        q, tokens[None], dataclasses.replace(cfg, n_passes=1)))(p)
    wq, w1 = grads["layers"]["attn"]["wq"], once["layers"]["attn"]["wq"]
    assert float(jnp.linalg.norm(wq - w1) / jnp.linalg.norm(wq)) > 0.1


# --------------------------------------------- the cache: a plane a (pass, layer)


def _prefilled(cfg, p, tokens, n, bucket, slot=1, slots=3):
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :n] = tokens[:n]
    logits, kv = decoding.prefill(p, jnp.asarray(padded), jnp.int32(n), cfg)
    state = dp.init_paged_state(cfg, slots, MAX_LEN, 40, PAGE)
    row = np.zeros((MAX_LEN // PAGE,), np.int32)
    need = max(bucket // PAGE, (n + 40) // PAGE + 1)
    row[:need] = 1 + np.random.default_rng(1).permutation(39)[:need]
    state = dp.insert_sequence_paged(state, slot, kv, jnp.int32(n), jnp.int32(tokens[n]),
                                     jnp.asarray(row), cfg)
    return logits, kv, state, row


def test_planes_are_indexed_by_pass_and_layer(model):
    """Pass 2's keys differ from pass 1's at the same layer and position; the
    pools hold T * L planes, plane t * L + l what the reference's layer l
    computes in pass t; the state carries the exit CDF a row."""
    cfg, p = model
    n = 37
    tokens = _tokens(n + 1)
    _, kv, state, row = _prefilled(cfg, p, tokens, n, 48)
    assert kv["k"].shape == kv["v"].shape == (T * L, 48, 4, 16)
    assert state["kp"].shape == state["vp"].shape == (T * L, 40, PAGE, 4, 16)
    assert state["exit_cdf"].shape == (3, T)
    by_pass = kv["k"].reshape(T, L, 48, 4, 16)[:, :, :n]
    for t in range(1, T):
        for l in range(L):
            assert float(jnp.abs(by_pass[t, l] - by_pass[t - 1, l]).max()) > 1e-2, (t, l)
    # the first pass's first layer sees the embedding: the same keys as a
    # stack run once; every later plane does not
    _, once = decoding.prefill(p, jnp.asarray(np.pad(tokens[:n], (0, 11))[None]),
                               jnp.int32(n), dataclasses.replace(cfg, n_passes=1))
    assert float(jnp.abs(once["k"][:, :n] - by_pass[0]).max()) < 1e-5
    # the pages of plane t * L + l hold that plane's rows
    pages = state["kp"][:, row[:3]].reshape(T * L, 48, 4, 16)
    assert float(jnp.abs(pages[:, :n] - kv["k"][:, :n]).max()) == 0.0


@pytest.mark.parametrize("kernel", [False, True], ids=["mirror", "kernel"])
def test_prefill_then_paged_decode_agrees_with_the_full_forward(model, kernel_interpreted,
                                                                kernel):
    """A prompt, then decode steps across page boundaries through the paged
    cache, against the reference's full forward at EVERY position: logits,
    and the exit CDF of each step against the reference's gate."""
    cfg, p = model
    n, steps = 41, 24
    tokens = _tokens(n + steps + 1)
    want, _ = reference.forward(p, jnp.asarray(tokens[:-1]), _sizes(cfg))
    leave = reference.exit_probabilities(p, jnp.asarray(tokens[:-1]), _sizes(cfg))
    logits, _, state, _ = _prefilled(cfg, p, tokens, n, 64)
    assert _close(logits, want[n - 1]) < TOL
    for i in range(steps):
        state, step = dp.decode_step_paged_ragged(p, state, cfg, 8, kernel)
        assert _close(step[1], want[n + i]) < TOL, i
        cdf = np.asarray(state["exit_cdf"][1])
        assert np.abs(cdf - np.cumsum(np.asarray(leave[:, n + i]))).max() < 1e-5, i
        assert abs(cdf[-1] - 1.0) < 1e-6
        state = decoding.commit_tokens(state, jnp.full((3,), tokens[n + i + 1], jnp.int32))
    assert int(state["length"][1]) == n + steps


@pytest.mark.parametrize("fault", ["planes_shared", "pass_norm_left_out", "one_pass",
                                   "sandwich_left_out"])
def test_a_planted_fault_of_the_loop_shows_in_the_decode_steps(model, fault):
    """`chipbench/loop_faults.py`'s faults at the tiny size, by hand: the
    cache indexed by layer alone (every pass reads and writes pass 0's
    planes) and its three siblings each move the decode steps' logits far
    from the reference's; the sound program, before and after, does not."""
    cfg, p = model
    n, steps = 30, 4
    tokens = _tokens(n + steps + 1)
    want, _ = reference.forward(p, jnp.asarray(tokens[:-1]), _sizes(cfg))

    def worst(cfg):
        _, _, state, _ = _prefilled(cfg, p, tokens, n, 32)
        errs = []
        for i in range(steps):
            state, step = dp.decode_step_paged_ragged(p, state, cfg, 4, False)
            errs.append(_close(step[1], want[n + i]))
            state = decoding.commit_tokens(state, jnp.full((3,), tokens[n + i + 1], jnp.int32))
        return max(errs)

    assert worst(cfg) < TOL
    if fault == "one_pass":
        assert worst(loop_faults.broken_config(cfg, fault)) > 100 * TOL
    else:
        with loop_faults.planted(fault):
            assert worst(cfg) > 100 * TOL
    assert worst(cfg) < TOL          # the planted code has gone with its traces


def _chunked(cfg, p, tokens, n, chunk):
    """The engine's staged prefill by hand: chunks of `chunk` (the tail
    padded to it), every plane's prefix gathered out of the pool and every
    plane's suffix written back. Returns (last logits, state, row)."""
    state = dp.init_paged_state(cfg, 2, MAX_LEN, 40, PAGE)
    span = -(-n // chunk) * chunk
    row = np.zeros((MAX_LEN // PAGE,), np.int32)
    need = min(span // PAGE + 2, MAX_LEN // PAGE)
    row[:need] = 1 + np.random.default_rng(2).permutation(39)[:need]
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
            assert pk.shape == (T * L, npad * PAGE, 4, 16)
            logits, kv = dp.prefill_with_prefix(
                p, jnp.asarray(padded), pk, pv, jnp.int32(done), jnp.int32(live), cfg)
        assert kv["k"].shape == (T * L, chunk, 4, 16)
        pages = range(done // PAGE, (done + chunk) // PAGE)
        state = dp.write_kv_pages(state, kv, jnp.asarray(row[list(pages)]))
    return logits, state, row


@pytest.mark.parametrize("chunks,chunk", [(2, 32), (3, 32), (5, 32), (5, 64)])
def test_chunked_prefill_agrees_with_one_shot_prefill(model, chunks, chunk):
    """2, 3 and 5 chunks with a padded tail chunk against one-shot prefill and
    the reference, then decode steps from the chunked state: what the chunks
    left in every plane is what a decode step needs."""
    cfg, p = model
    n, steps = chunk * chunks - 11, 5
    tokens = _tokens(n + steps + 1)
    padded = np.zeros((1, 512), np.int32)
    padded[0, :n] = tokens[:n]
    one_shot, _ = decoding.prefill(p, jnp.asarray(padded), jnp.int32(n), cfg)
    want, _ = reference.forward(p, jnp.asarray(tokens[:-1]), _sizes(cfg))
    logits, state, row = _chunked(cfg, p, tokens, n, chunk)
    assert _close(logits, one_shot) < TOL and _close(logits, want[n - 1]) < TOL
    state = dp.activate_slot(state, 0, jnp.asarray(row), jnp.int32(n), jnp.int32(tokens[n]))
    for i in range(steps):
        state, step = dp.decode_step_paged_ragged(p, state, cfg, 32, False)
        assert _close(step[0], want[n + i]) < TOL, i
        state = decoding.commit_tokens(state, jnp.full((2,), tokens[n + i + 1], jnp.int32))


# ------------------------------------------------------------ what is refused


def test_a_threshold_other_than_one_is_refused_at_construction():
    with pytest.raises(ValueError, match="leave the loop before its last pass"):
        _cfg(early_exit_threshold=0.9)
    assert _cfg(early_exit_threshold=1.0).n_passes == T


@pytest.mark.parametrize("kwargs", [
    dict(kv_lora_rank=32, n_kv_heads=None), dict(window=64, window_period=2),
    dict(n_dense_layers=1, d_ff_dense=64)])
@pytest.mark.parametrize("field", [dict(n_passes=2), dict(sandwich_norms=True),
                                   dict(exit_gate=True)])
def test_what_a_looped_stack_does_not_carry_is_refused(kwargs, field):
    cfg = llama_config("tiny", vocab_size=VOCAB, dtype=jnp.float32, **kwargs, **field)
    if "sandwich_norms" in field and "kv_lora_rank" not in kwargs:
        # built since PR 44: sandwich norms go with window layers and with
        # leading dense layers (tests/test_trinity.py runs such stacks
        # against their own forward); a looped stack and the gate do not
        assert "post_attn_norm" in transformer.init(jax.random.PRNGKey(0), cfg)["layers"]
        return
    with pytest.raises(ValueError, match="looped stack|latent attention"):
        transformer.init(jax.random.PRNGKey(0), cfg)


def _engine(cfg, p, **kw):
    from ray_tpu.llm.engine import TPUEngine

    kw = {**dict(max_slots=3, max_len=MAX_LEN, min_bucket=16, page_size=PAGE,
                 num_pages=60), **kw}
    return TPUEngine(cfg, p, **kw)


@pytest.mark.parametrize("kwargs,what", [
    (dict(max_loras=2), "max_loras"),
    (dict(mesh="a mesh"), "tensor-parallel mesh"),
])
def test_what_the_engine_does_not_carry_is_refused_at_construction(model, kwargs, what):
    cfg, p = model
    with pytest.raises(ValueError, match=f"looped stack.*{what}"):
        _engine(cfg, p, **kwargs)
    with pytest.raises(NotImplementedError, match="looped stack"):
        decoding.init_lora_bank(cfg, 2, 4)
    with pytest.raises(NotImplementedError, match="looped stack"):
        decoding.prefill_batch(p, jnp.zeros((2, 16), jnp.int32), jnp.ones((2,), jnp.int32), cfg)
    with pytest.raises(ValueError, match="at least once"):
        transformer.init(jax.random.PRNGKey(0), dataclasses.replace(cfg, n_passes=0))


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
        if not (eng._by_slot or eng._prefilling or eng._backlog or eng._waiting.qsize()):
            return
        time.sleep(0.01)
    raise AssertionError("the engine did not come to rest")


@pytest.mark.parametrize("kwargs", [
    {}, dict(prefill_chunk=32), dict(enable_prefix_cache=True, prefill_chunk=32)],
    ids=["plain", "chunked", "prefix_cache"])
def test_engine_serves_the_looped_stack_and_counts_its_passes(model, kwargs):
    """Through TPUEngine, greedy: the reference's own greedy tokens, whether
    the prompt is prefilled whole, in chunks (a padded tail), or partly out of
    the prefix cache (the second request shares 64 tokens with the first);
    the counters of the loop move as the rows do; nothing assumes n_layers
    planes."""
    from ray_tpu.llm.engine import SamplingParams, _iter_request

    cfg, p = model
    eng = _engine(cfg, p, **kwargs)
    try:
        with pytest.raises(NotImplementedError, match="looped stack"):
            eng.submit_prefilled(length=4)
        shared = _tokens(64, seed=5).tolist()
        prompts = [shared + _tokens(23, seed=6).tolist(), shared + _tokens(9, seed=7).tolist()]
        outs = []
        for prompt in prompts:
            req = eng.submit(prompt, SamplingParams(max_tokens=5, temperature=0.0))
            outs.append(list(_iter_request(req)))
            _idle(eng)
        assert outs == [_greedy(cfg, p, prompt, 5) for prompt in prompts]
        stats = eng.stats()
        assert stats["cache"]["bytes_per_token"] == T * L * 2 * 4 * 16 * 4   # float32
        loops = stats["loops"]
        assert loops["passes"] == T and loops["planes"] == T * L
        assert stats["decode_steps"] == 8 and loops["stack_passes"] == T * 8
        assert len(loops["exit_rows"]) == T and sum(loops["exit_rows"]) == 8   # a row a step
        if kwargs.get("enable_prefix_cache"):
            assert stats["prefix_cache"]["hits"] == 1
            assert stats["prefix_cache"]["tokens_reused"] == 64
        if kwargs.get("prefill_chunk"):
            assert stats["prefill_chunks_run"] >= 3
        assert sorted(eng._free_pages + list(eng._prefix_cache.values())) == list(
            range(1, eng.num_pages))
    finally:
        eng.shutdown()


def test_exit_rows_count_the_pass_at_which_the_cdf_reaches_a_half(model):
    """With a gate that is nearly shut until its bias opens it, every row
    leaves at the last pass; wide open, at the first."""
    from ray_tpu.llm.engine import SamplingParams, _iter_request

    cfg, p = model
    for bias, at in ((-30.0, T - 1), (30.0, 0)):
        q = {**p, "exit_gate": {"w": 0 * p["exit_gate"]["w"], "b": jnp.float32(bias)}}
        eng = _engine(cfg, q)
        try:
            req = eng.submit(_tokens(20).tolist(), SamplingParams(max_tokens=4, temperature=0.0))
            assert len(list(_iter_request(req))) == 4
            _idle(eng)
            rows = eng.stats()["loops"]["exit_rows"]
            assert rows[at] == 3 and sum(rows) == 3
        finally:
            eng.shutdown()
