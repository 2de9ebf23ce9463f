"""Kimi-VL's language model (latent attention, a leading dense layer, sigmoid
routing with a selection bias, shared experts) against its plain reference
(`chipbench/reference/kimi_vl.py`) at a tiny size on the CPU, seeded weights.

Tolerances: everything runs in float32 here, so program and reference differ
by summation order only: 1e-4 of the largest logit (measured 1e-7 to 2e-6).
The interpreted kernel multiplies the operands as stored, which at float32
is the reference's arithmetic too.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench.reference import kimi_vl as reference  # noqa: E402
from ray_tpu import ops  # noqa: E402
from ray_tpu.models import decoding, kimi_vl_config, mixtral_config, transformer  # noqa: E402
from ray_tpu.models import decoding_paged as dp  # noqa: E402
from ray_tpu.models.transformer import MoEConfig  # noqa: E402

VOCAB, PAGE, MAX_LEN = 300, 16, 256
TOL = 1e-4
MANY = ops.moe.SORTED_MIN_TOKENS + 8     # a call of this many tokens sorts its slots


def _cfg(n_dense=1, **kw):
    cfg = kimi_vl_config("tiny", vocab_size=VOCAB, max_seq_len=1024, dtype=jnp.float32,
                         n_layers=n_dense + 2, n_dense_layers=n_dense, **kw)
    return dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, select_bias_init_std=0.05))


def _sizes(cfg):
    return dict(n_layers=cfg.n_layers, n_dense_layers=cfg.n_dense_layers,
                qk_nope_head_dim=cfg.qk_nope_head_dim, kv_lora_rank=cfg.kv_lora_rank,
                rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps,
                kv_norm_eps=cfg.kv_norm_eps, top_k=cfg.moe.top_k,
                num_experts=cfg.moe.num_experts,
                routed_scaling_factor=cfg.moe.routed_scaling_factor)


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


@pytest.mark.parametrize("n_dense,n_tokens", [(1, 48), (2, 48), (1, MANY)])
def test_forward_agrees_with_the_reference(n_dense, n_tokens):
    """A few tokens go through the one-hot dispatch at capacity N, many
    through the sorted one over the stack's experts where they lie."""
    cfg = _cfg(n_dense)
    p, tokens = _params(cfg), _tokens(n_tokens)
    assert ops.sorted_pays(n_tokens) == (n_tokens == MANY)
    logits, aux = transformer.forward(p, tokens[None], cfg)
    want, margin = reference.forward(p, jnp.asarray(tokens), _sizes(cfg))
    assert _close(logits[0], want) < TOL and float(aux) == 0.0
    if n_tokens == MANY:  # and through prefill, which hands back the rows to cache
        last, kv = decoding.prefill(p, jnp.asarray(tokens[None]), jnp.int32(n_tokens), cfg)
        assert _close(last, want[-1]) < TOL and kv["k"].shape == (cfg.n_layers, MANY, 128)
    assert bool(jnp.isinf(margin[:n_dense]).all()) and bool(jnp.isfinite(margin[n_dense:]).all())
    assert jax.tree.structure(jax.tree.map(lambda x: 0, p)) == jax.tree.structure(
        jax.tree.map(lambda x: 0, transformer.logical_axes(cfg),
                     is_leaf=lambda x: isinstance(x, tuple)))


def test_the_train_step_traces_and_differentiates(model):
    cfg, p = model
    g = jax.grad(lambda q: transformer.loss_fn(q, _tokens(33)[None], cfg))(p)
    assert all(bool(jnp.isfinite(x).all()) for x in jax.tree.leaves(g))
    # the selection bias takes part in no product: balance moves it, not the loss
    assert float(jnp.abs(g["layers"]["mlp"]["router_bias"]).max()) == 0.0
    assert float(jnp.abs(g["layers"]["mlp"]["router"]).max()) > 0.0


def test_norm_eps_is_the_configurations():
    cfg = _cfg()
    assert cfg.norm_eps == 1e-5 and mixtral_config("tiny").norm_eps == 1e-6
    p, tokens = _params(cfg), _tokens(16)[None]
    a, _ = transformer.forward(p, tokens, cfg)
    b, _ = transformer.forward(p, tokens, dataclasses.replace(cfg, norm_eps=1e-2))
    assert float(jnp.abs(a - b).max()) > 1e-4


@pytest.fixture
def kernel_interpreted(monkeypatch):
    import ray_tpu.ops.ragged_paged_attention as rpa

    real = rpa._latent_kernel_call
    monkeypatch.setattr(rpa, "_latent_kernel_call",
                        lambda *a, interpret, **kw: real(*a, interpret=True, **kw))


def _prefilled(cfg, p, tokens, n):
    padded = np.zeros((1, 64), np.int32)
    padded[0, :n] = tokens[:n]
    logits, kv = decoding.prefill(p, jnp.asarray(padded), jnp.int32(n), cfg)
    state = dp.init_paged_state(cfg, 3, MAX_LEN, 24, PAGE)
    row = np.zeros((MAX_LEN // PAGE,), np.int32)
    row[:6] = [5, 2, 9, 4, 7, 11]
    state = dp.insert_sequence_paged(state, 1, kv, jnp.int32(n), jnp.int32(tokens[n]),
                                     jnp.asarray(row), cfg)
    return logits, kv, state, row


@pytest.mark.parametrize("kernel", [False, True], ids=["ragged-reference", "ragged-kernel"])
def test_prefill_then_paged_decode_agrees_with_the_full_forward(model, kernel_interpreted, kernel):
    """Expanded attention in prefill, absorbed attention over the latent pages
    in every decode step (across a page boundary), against the reference's
    full forward at every decoded position."""
    cfg, p = model
    n, steps = 40, 12
    tokens = _tokens(n + steps + 1)
    want, _ = reference.forward(p, jnp.asarray(tokens[:-1]), _sizes(cfg))
    logits, _, state, _ = _prefilled(cfg, p, tokens, n)
    assert _close(logits, want[n - 1]) < TOL
    for i in range(steps):
        state, step = dp.decode_step_paged_ragged(p, state, cfg, 8, kernel)
        assert _close(step[1], want[n + i]) < TOL, i
        state = decoding.commit_tokens(state, jnp.full((3,), tokens[n + i + 1], jnp.int32))
    assert "vp" not in state and int(state["length"][1]) == n + steps


def test_the_pages_hold_the_references_latent_rows(model):
    """A cached row is (c | k_rope | 0): RMSNorm of the down-projection with
    its own weight, the shared rope key rotated at its position; nothing per
    head."""
    cfg, p = model
    n = 40
    tokens = _tokens(n + 2)
    _, kv, state, row = _prefilled(cfg, p, tokens, n)
    state, _ = dp.decode_step_paged_ragged(p, state, cfg, 8, False)
    r, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    assert state["kp"].shape == (cfg.n_layers, 24, PAGE, 128) and kv["k"].shape[-1] == 128
    x = p["embed"][tokens[:n + 1]]
    # layer 0 reads the embeddings: its rows can be written down directly
    a = jax.tree.map(lambda t: t[0], p["dense_layers"])
    h = reference._rms_norm(x, a["norm1"]["w"], cfg.norm_eps)
    ckr = h @ a["attn"]["w_dkv"]
    c = reference._rms_norm(ckr[:, :r], a["attn"]["kv_norm"], cfg.kv_norm_eps)
    k_rope = reference._rope(ckr[:, None, r:], cfg.rope_theta)[:, 0]
    rows = state["kp"][0][jnp.asarray(row[:3])].reshape(3 * PAGE, -1)[:n + 1]
    assert float(jnp.abs(rows[:, :r] - c).max()) < 1e-5
    assert float(jnp.abs(rows[:, r:r + dr] - k_rope).max()) < 1e-5
    assert float(jnp.abs(rows[:, r + dr:]).max()) == 0.0


@pytest.mark.parametrize("chunks", [2, 3])
def test_prefill_with_prefix_agrees_with_one_shot_prefill(model, chunks):
    """Every chunk after the first expands the latent rows of the chunks
    before it; the last chunk is partly padding."""
    cfg, p = model
    n = 32 * chunks - 11
    tokens = _tokens(n)
    padded = np.zeros((1, 32 * chunks), np.int32)
    padded[0, :n] = tokens
    want, kv_want = decoding.prefill(p, jnp.asarray(padded), jnp.int32(n), cfg)
    logits, kv = decoding.prefill(p, jnp.asarray(padded[:, :32]), jnp.int32(32), cfg)
    rows = kv["k"]
    for c in range(1, chunks):
        live = min(32, n - 32 * c)
        prefix = jnp.pad(rows, ((0, 0), (0, 64 - rows.shape[1] % 64), (0, 0)))  # a bucket
        logits, kv = dp.prefill_with_prefix(
            p, jnp.asarray(padded[:, 32 * c:32 * c + 32]), prefix, None,
            jnp.int32(32 * c), jnp.int32(live), cfg)
        rows = jnp.concatenate([rows, kv["k"]], axis=1)
    assert _close(logits, want) < TOL
    assert float(jnp.abs(rows[:, :n] - kv_want["k"][:, :n]).max()) < 1e-5


def test_absorbed_attention_equals_expanded_attention_on_the_same_rows(model):
    cfg, p = model
    a = jax.tree.map(lambda t: t[0], p["layers"]["attn"])
    S = 37
    x = jax.random.normal(jax.random.PRNGKey(1), (1, S, cfg.d_model))
    cos, sin = ops.rope_frequencies(cfg.rope_dim, MAX_LEN, theta=cfg.rope_theta)
    q, rows = transformer._mla_project(x, a, cfg, cos, sin)
    k, v = transformer._mla_expand(rows, a, cfg)
    scale = cfg.qk_dim ** -0.5
    s = jnp.einsum("hd,shd->hs", q[0, -1], k[0]) * scale           # the last query
    expanded = jnp.einsum("hs,shd->hd", jax.nn.softmax(s, -1), v[0])
    q_abs = transformer._mla_absorb_q(q[:, -1], a, cfg)             # [1, H, lanes]
    s_abs = jnp.einsum("hw,sw->hs", q_abs[0], rows[0]) * scale
    assert float(jnp.abs(s_abs - s).max()) < 1e-5
    o_lat = jnp.einsum("hs,sw->hw", jax.nn.softmax(s_abs, -1), rows[0])
    absorbed = transformer._mla_absorb_out(o_lat[None], a, cfg)[0]
    assert float(jnp.abs(absorbed - expanded).max()) < 1e-5


# ---------------------------------------------------------------- routing


def test_selection_uses_the_bias_and_the_weights_do_not():
    logits = jnp.log(jnp.asarray([[0.9, 0.8, 0.7, 0.6, 0.5, 0.4]]) /
                     (1 - jnp.asarray([[0.9, 0.8, 0.7, 0.6, 0.5, 0.4]])))  # scores 0.9 .. 0.4
    bias = jnp.asarray([0.0, 0.0, -0.5, 0.0, 0.0, 0.25])
    idx, w, aux = ops.sigmoid_topk(logits, bias, k=3, scale=2.0)
    # 0.7 - 0.5 falls out, 0.4 + 0.25 comes in: experts 0, 1, 5
    assert sorted(np.asarray(idx[0]).tolist()) == [0, 1, 5] and float(aux) == 0.0
    got = dict(zip(np.asarray(idx[0]).tolist(), np.asarray(w[0]).tolist()))
    for e, score in ((0, 0.9), (1, 0.8), (5, 0.4)):       # scores WITHOUT the bias
        assert got[e] == pytest.approx(2.0 * score / (0.9 + 0.8 + 0.4), rel=1e-5)
    assert float(w.sum()) == pytest.approx(2.0, rel=1e-5)   # renormalised, then scaled


def test_softmax_topk_is_mixtrals_routing():
    logits = jax.random.normal(jax.random.PRNGKey(0), (32, 8))
    idx, w, aux = ops.softmax_topk(logits, k=2)
    probs = jax.nn.softmax(logits, -1)
    top, want = jax.lax.top_k(probs, 2)
    assert bool((idx == want).all()) and float(aux) > 0
    np.testing.assert_allclose(np.asarray(w), np.asarray(top / top.sum(-1, keepdims=True)),
                               rtol=1e-6)


def _dense_moe(x, idx, w, gate, up, down):
    """Every token through every expert it chose, one by one."""
    y = jnp.zeros_like(x)
    for t in range(x.shape[0]):
        for e, we in zip(np.asarray(idx[t]), np.asarray(w[t])):
            y = y.at[t].add(we * ((jax.nn.silu(x[t] @ gate[e]) * (x[t] @ up[e])) @ down[e]))
    return y


def _experts(E=8, D=16, F=12):
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    return (jax.random.normal(ks[0], (E, D, F)) * 0.3, jax.random.normal(ks[1], (E, D, F)) * 0.3,
            jax.random.normal(ks[2], (E, F, D)) * 0.3)


@pytest.mark.parametrize("case", ["every_token_to_one_expert", "uneven", "padded_rows"])
def test_sorted_dispatch_is_dropless_and_equals_the_dense_reference(case):
    N, k, E = 24, 3, 8
    x = jax.random.normal(jax.random.PRNGKey(2), (N, 16))
    rng = np.random.default_rng(4)
    if case == "every_token_to_one_expert":     # the worst imbalance: N slots on expert 5
        idx = np.tile(np.asarray([[5, 0, 7]]), (N, 1))
    else:
        idx = np.stack([rng.choice(E, k, replace=False, p=np.asarray(
            [.4, .3, .1, .1, .05, .03, .01, .01])) for _ in range(N)])
    if case == "padded_rows":                   # a bucket's padding: zero rows are routed too
        x = x.at[N // 2:].set(0.0)
    w = jnp.asarray(rng.uniform(0.1, 1.0, (N, k)), jnp.float32)
    gate, up, down = _experts()
    got = ops.moe_sorted(x, jnp.asarray(idx, jnp.int32), w, gate, up, down)
    want = _dense_moe(x, idx, w, gate, up, down)
    assert float(jnp.abs(got - want).max()) < 1e-5
    assert float(jnp.abs(want).sum(-1).min()) > 0 or case == "padded_rows"
    if case == "padded_rows":
        assert float(jnp.abs(got[N // 2:]).max()) == 0.0


def test_shared_experts_are_counted_once(model):
    """The layer's output is the routed sum plus ONE pass of the shared MLP
    (width 2 x d_ff), for every token, ungated and unscaled."""
    cfg, p = model
    mlp = jax.tree.map(lambda t: t[0], p["layers"]["mlp"])
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 20, cfg.d_model))
    y, _ = transformer._moe_mlp(x, mlp, cfg)
    logits = x[0] @ mlp["router"]
    idx, w, _ = ops.sigmoid_topk(logits, mlp["router_bias"], k=cfg.moe.top_k,
                                 scale=cfg.moe.routed_scaling_factor)
    routed = _dense_moe(x[0], idx, w, mlp["gate"], mlp["up"], mlp["down"])
    shared = transformer._dense_mlp(x[0], mlp["shared"], cfg)
    assert mlp["shared"]["wi_gate"].shape == (cfg.d_model, 2 * cfg.d_ff)
    assert float(jnp.abs(y[0] - (routed + shared)).max()) < 1e-5
    assert float(jnp.abs(shared).max()) > 1e-3
    assert float(w.sum(-1)[0]) == pytest.approx(2.446, rel=1e-5)


@pytest.mark.parametrize("n_tokens", [64, MANY])
def test_mixtral_dropless_equals_the_one_hot_form_at_full_capacity(n_tokens):
    """capacity_factor E/k is dropless: few tokens take the one-hot form at
    capacity N, many the sorted one. Just under E/k the one-hot form runs at
    a capacity of N - 1, which drops nothing on these tokens: all agree."""
    dropless = mixtral_config("tiny", vocab_size=VOCAB, max_seq_len=1024, dtype=jnp.float32,
                              moe=MoEConfig(num_experts=8, top_k=2, capacity_factor=4.0))
    onehot = dataclasses.replace(
        dropless, moe=MoEConfig(num_experts=8, top_k=2, capacity_factor=3.999))
    assert dropless.moe.dropless and not onehot.moe.dropless
    p = transformer.init(jax.random.PRNGKey(0), dropless)
    tokens = _tokens(n_tokens)[None]
    (a, aux_a), (b, aux_b) = (transformer.forward(p, tokens, c) for c in (dropless, onehot))
    assert float(aux_a) == pytest.approx(float(aux_b), rel=1e-6)
    assert _close(a, b) < 1e-5
    (a, _), (b, _) = (decoding.prefill(p, jnp.asarray(tokens), jnp.int32(n_tokens - 14), c)
                      for c in (dropless, onehot))
    assert _close(a, b) < 1e-5


# ------------------------------------------------------------ the engine


@pytest.mark.parametrize("kwargs,what", [
    (dict(max_loras=2), "max_loras"),
    (dict(mesh="a mesh"), "tensor-parallel mesh"),
])
def test_what_is_not_carried_to_the_latent_cache_raises_at_construction(model, kwargs, what):
    from ray_tpu.llm.engine import TPUEngine

    cfg, p = model
    with pytest.raises(ValueError, match=what):
        TPUEngine(cfg, p, max_len=MAX_LEN, **kwargs)
    with pytest.raises(NotImplementedError, match="latent attention"):
        decoding.init_lora_bank(cfg, 2, 4)


def test_engine_serves_the_latent_cache_and_counts_it(model):
    """Through TPUEngine: chunked prefill over a latent prefix, greedy decode,
    the same tokens as the model's own steps; the cache counters move."""
    from ray_tpu.llm.engine import SamplingParams, TPUEngine

    cfg, p = model
    eng = TPUEngine(cfg, p, max_slots=2, max_len=MAX_LEN, min_bucket=32,
                    page_size=PAGE, num_pages=40, prefill_chunk=32, enable_prefix_cache=True)
    try:
        with pytest.raises(NotImplementedError, match="latent"):
            eng.submit_prefilled(length=4)
        prompt = _tokens(75).tolist()
        out = eng.generate(prompt, SamplingParams(max_tokens=6, temperature=0.0))
        tokens, want = list(prompt), []
        for _ in range(6):
            logits, _ = transformer.forward(p, jnp.asarray(tokens)[None], cfg)
            want.append(int(jnp.argmax(logits[0, -1])))
            tokens.append(want[-1])
        assert list(out) == want
        cache = eng.stats()["cache"]
        assert cache["bytes_per_token"] == cfg.n_layers * 128 * 4
        assert cache["prefix_tokens_gathered"] == 32 + 64      # chunks 2 and 3
        assert cache["context_tokens"] == sum(75 + i + 1 for i in range(5))
        assert 0 < cache["page_steps_used"] <= cache["page_steps_total"]
    finally:
        eng.shutdown()


def test_reference_offers_the_two_nearest_other_routings(model):
    """depth 1: the k-th and k+1-th by score + b change places; depth 2: the
    nearer of (k-th out, k+2-th in) and (k-1-th out, k+1-th in); `margin`
    holds the gaps rounding has to bridge for each. Weights from the scores
    without b, renormalised and scaled, whatever the depth."""
    cfg, p = model
    layers, k, T = p["layers"], cfg.moe.top_k, 64
    x = jax.random.normal(jax.random.PRNGKey(11), (T, cfg.d_model))
    kw = dict(top_k=k, scale=cfg.moe.routed_scaling_factor, eps=cfg.norm_eps)
    h, g0, margin = reference._route(layers, 0, x, jnp.zeros((T,), jnp.int32), **kw)
    _, g1, _ = reference._route(layers, 0, x, jnp.ones((T,), jnp.int32), **kw)
    _, g2, _ = reference._route(layers, 0, x, jnp.full((T,), 2, jnp.int32), **kw)
    scores = jax.nn.sigmoid(h @ layers["mlp"]["router"][0])
    v = np.asarray(scores + layers["mlp"]["router_bias"][0])
    order = np.argsort(-v, axis=1)
    both = set()
    for t in range(T):
        r, sets = order[t], [set(np.nonzero(np.asarray(g[t]))[0]) for g in (g0, g1, g2)]
        assert sets[0] == set(r[:k]) and sets[1] == set(r[:k - 1]) | {r[k]}
        last_out = v[t, r[k - 1]] - v[t, r[k + 1]]
        last_but_one_out = v[t, r[k - 2]] - v[t, r[k]]
        want = (set(r[:k - 1]) | {r[k + 1]} if last_out <= last_but_one_out
                else set(r[:k]) - {r[k - 2]} | {r[k]})
        both.add(bool(last_out <= last_but_one_out))
        assert sets[2] == want
        np.testing.assert_allclose(margin[t], [v[t, r[k - 1]] - v[t, r[k]],
                                               min(last_out, last_but_one_out)], atol=1e-6)
    assert both == {True, False}
    for g in (g0, g1, g2):
        np.testing.assert_allclose(np.asarray(g.sum(-1)), 2.446, rtol=1e-5)
        taken = np.asarray(g) > 0
        np.testing.assert_allclose(np.asarray(g)[taken] / np.asarray(g.sum(-1, keepdims=True)).repeat(8, 1)[taken],
                                   (np.asarray(scores) / (np.asarray(scores) * taken).sum(-1, keepdims=True))[taken],
                                   rtol=1e-5)
