"""Solar Open 2 (three gated delta-rule linear-attention layers, KDA, to one
gated grouped-query attention layer without positions, routed experts and a
shared one in EVERY layer, a layer holding a SHARE of the experts) against its
plain reference (`chipbench/reference/solar_open2.py`: the recurrence token by
token) at a tiny size on the CPU, seeded weights: two whole periods of four
layers, the scan's chunk 32 in sub-blocks of 16.

Tolerances: everything runs in float32 here, so program and reference differ
by summation order and by the chunked form's triangular solve: 1e-4 of the
largest logit (measured 1e-6 to 6e-6).
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench.reference import solar_open2 as reference  # noqa: E402
from ray_tpu import ops  # noqa: E402
from ray_tpu.models import (decoding, granite_config, solar_open2_config,  # noqa: E402
                            transformer)
from ray_tpu.models import decoding_paged as dp  # noqa: E402

VOCAB, PAGE, MAX_LEN = 300, 16, 256
TOL = 1e-4
SHARE = dict(experts_held=8, first_expert=16)


def _cfg(**kw):
    return solar_open2_config("tiny", vocab_size=VOCAB, max_seq_len=1024, dtype=jnp.float32,
                              select_bias_init_std=0.02, **kw)


CHUNK = _cfg().ssm.chunk                             # 32
LK, LA = _cfg().n_ssm_layers, _cfg().n_attn_layers   # 6 and 2


def _sizes(cfg):
    s = dict(n_layers=cfg.n_layers, norm_eps=cfg.norm_eps, top_k=cfg.moe.top_k,
             gqa_layers=[l for l in range(cfg.n_layers) if transformer.is_attn_layer(cfg, l)],
             kda_heads=cfg.ssm.n_heads,
             routed_scaling_factor=cfg.moe.routed_scaling_factor)
    if cfg.moe.share:
        s["experts_held"] = list(range(cfg.moe.first_expert,
                                       cfg.moe.first_expert + cfg.moe.held))
    return s


def _params(cfg, seed=3):
    p = transformer.init(jax.random.PRNGKey(seed), cfg)
    # norm weights and the gate's bias away from one and zero, so that one
    # left out would show
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


@pytest.fixture(scope="module")
def share():
    cfg = _cfg(**SHARE)
    return cfg, _params(cfg)


@pytest.fixture
def kernels_interpreted(monkeypatch):
    import ray_tpu.ops.ragged_paged_attention as rpa
    import ray_tpu.ops.ssm as ssm

    for module, name in ((rpa, "_ragged_kernel_call"), (ssm, "_kda_update_kernel_call")):
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
    kinds = ["gqa" if transformer.is_attn_layer(cfg, l) else "kda" for l in range(cfg.n_layers)]
    assert kinds == ["gqa", "kda", "kda", "kda"] * 2
    assert "attn" in p["layers"] and "mixer" not in p["layers"]
    assert "mixer" in p["ssm_layers"] and "attn" not in p["ssm_layers"]
    for stack, n in ((p["layers"], LA), (p["ssm_layers"], LK)):
        assert stack["norm1"]["w"].shape[0] == n
        assert stack["mlp"]["router"].shape == (n, 64, 64)       # experts in EVERY layer
        assert stack["mlp"]["gate"].shape == (n, 64, 64, 32)
        assert stack["mlp"]["shared"]["wi_gate"].shape == (n, 64, 32)
    assert p["layers"]["attn"]["wg"].shape == (LA, 64, 4, 16)     # the attention gate
    assert "pos_embed" not in p and "lm_head" in p
    axes = transformer.logical_axes(cfg)
    assert jax.tree.structure(jax.tree.map(lambda a: 0, p)) == jax.tree.structure(
        jax.tree.map(lambda a: 0, axes, is_leaf=lambda x: isinstance(x, tuple)))


def test_the_published_size_counts_what_the_issue_reckoned():
    big = solar_open2_config()
    assert [l for l in range(48) if transformer.is_attn_layer(big, l)] == list(range(0, 48, 4))
    assert (big.ssm.d_inner, big.ssm.conv_dim, big.ssm.state_shape) == (
        8192, 24576, (64, 128, 128))
    cut = solar_open2_config(n_layers=4, vocab_size=24576, experts_held=40)
    shapes = jax.eval_shape(lambda: transformer.init(jax.random.PRNGKey(0), cut))
    count = lambda t: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(t))  # noqa: E731
    assert count(shapes["ssm_layers"]["mixer"]) == 3 * 137_740_480
    assert count(shapes["layers"]["attn"]) == 109_051_904
    assert count(shapes["layers"]["mlp"]) == 646_185_280
    assert cut.num_params() == 3_308_377_920
    # a row's recurrent state and tails, as the engine's stats() reads them
    state = jax.eval_shape(lambda: dp.init_paged_state(
        dataclasses.replace(cut, dtype=jnp.bfloat16), 2, 128, 4, 64))
    per_row = sum(int(np.prod(state[k].shape)) * state[k].dtype.itemsize // 2
                  for k in ("ssm", "conv"))
    assert per_row == 13_025_280 and state["ssm"].dtype == jnp.float32
    assert state["kp"].shape[0] == 1                                # ONE layer's pages


@pytest.mark.parametrize("n_tokens", [1, 7, 33, 100])
def test_forward_agrees_with_the_reference(model, n_tokens):
    cfg, p = model
    tokens = _tokens(n_tokens)
    want, _ = reference.forward(p, jnp.asarray(tokens), _sizes(cfg))
    got, _ = transformer.forward(p, jnp.asarray(tokens)[None], cfg)
    assert _close(got[0], want) < TOL


@pytest.mark.parametrize("what", ["delta_left_out", "decay_per_head", "beta_not_doubled",
                                  "out_gate_left_out", "qk_l2norm_left_out",
                                  "gqa_gate_left_out"])
def test_the_reference_tells_a_part_left_out(model, what):
    """The faults the chip's check is given (`chipbench/solar_faults.py`),
    planted in the forward: the reference reads each."""
    from chipbench import solar_faults

    cfg, p = model
    tokens = _tokens(40)
    want, _ = reference.forward(p, jnp.asarray(tokens), _sizes(cfg))
    with solar_faults.planted(what, {"sizes": _sizes(cfg)}):
        got, _ = transformer.forward(p, jnp.asarray(tokens)[None], cfg)
    assert _close(got[0], want) > 5 * TOL


# ------------------------------------------------------ the scan and the state


def _kda_inputs(T, seed=1, H=3, D=8, strong=True):
    """q and k of unit length, log decays down to -5 a step and some beta
    within 1e-3 of 2: the hard end of what the layer can produce."""
    rng = np.random.default_rng(seed)
    q, k = (rng.normal(size=(T, H, D)) for _ in range(2))
    q, k = (a / np.linalg.norm(a, axis=-1, keepdims=True) for a in (q, k))
    g = -rng.uniform(0.0, 5.0 if strong else 0.1, (T, H, D))
    beta = np.where(rng.random((T, H)) < 0.3, 2.0 - 1e-3 * rng.random((T, H)),
                    rng.uniform(0.0, 2.0, (T, H)))
    v = rng.normal(size=(T, H, D + 4))
    return tuple(jnp.asarray(a, jnp.float32) for a in (q / np.sqrt(D), k, v, g, beta))


@pytest.mark.parametrize("strong", [True, False], ids=["strong_gates", "weak_gates"])
@pytest.mark.parametrize("T,chunk,sub", [(1, 8, 4), (5, 8, 4), (8, 8, 4), (9, 8, 4), (21, 8, 8),
                                         (64, 16, 4), (70, 32, 16), (100, 64, 16)])
def test_the_chunked_scan_is_the_recurrence(T, chunk, sub, strong):
    """Chunk sizes that do and do not divide T, one and several sub-blocks a
    chunk, against the token-by-token recurrence; then carried: the second
    part run on from the first part's state."""
    q, k, v, g, beta = _kda_inputs(T, strong=strong)
    want_o, want_s = reference.delta_rule(q, k, v, g, beta)
    o, s = ops.kda_chunk_scan(q, k, v, g, beta, chunk=chunk, sub=sub)
    assert o.shape == want_o.shape and s.shape == want_s.shape == (3, 8, 12)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(s).all())
    assert _close(o, want_o) < 2e-5 and _close(s, want_s) < 2e-5
    cut = T // 2
    if cut:
        a, b = (tuple(t[part] for t in (q, k, v, g, beta))
                for part in (slice(None, cut), slice(cut, None)))
        _, s1 = ops.kda_chunk_scan(*a, chunk=chunk, sub=sub)
        o2, s2 = ops.kda_chunk_scan(*b, chunk=chunk, sub=sub, state=s1)
        assert _close(o2, want_o[cut:]) < 2e-5 and _close(s2, want_s) < 2e-5


def test_the_scan_takes_no_exponent_of_a_positive_sum():
    """Decays of e^-5 a step for a whole chunk of 64: exp(-G_j) would be
    e^320. Every output is finite and the recurrence's."""
    T = 128
    q, k, v, _, beta = _kda_inputs(T)
    g = jnp.full((T, 3, 8), -5.0)
    want_o, want_s = reference.delta_rule(q, k, v, g, beta)
    o, s = ops.kda_chunk_scan(q, k, v, g, beta, chunk=64)
    assert bool(jnp.isfinite(o).all()) and _close(o, want_o) < 2e-5 and _close(s, want_s) < 2e-5
    with pytest.raises(ValueError, match="sub-block"):
        ops.kda_chunk_scan(q, k, v, g, beta, chunk=20)


def test_padding_is_g_zero_and_beta_zero():
    q, k, v, g, beta = _kda_inputs(20)
    _, want = ops.kda_chunk_scan(q[:13], k[:13], v[:13], g[:13], beta[:13], chunk=8, sub=4)
    real = jnp.arange(20) < 13
    _, got = ops.kda_chunk_scan(q, k, v, jnp.where(real[:, None, None], g, 0.0),
                                jnp.where(real[:, None], beta, 0.0), chunk=8, sub=4)
    assert _close(got, want) < 1e-6


# ---- the Pallas launch, interpreted on the CPU, at heads whose keys and values
# ---- are whole 128-lane tiles (the tests above run dk 8 / dv 12: the XLA form)


def _launch_inputs(T, seed=1, strong=True, H=2, D=128):
    q, k, v, g, beta = _kda_inputs(T, seed=seed, H=H, D=D, strong=strong)
    return q, k, v[..., :D], g, beta


def _launch(*a, **kw):
    return ops.kda_chunk_scan(*a, interpret=True, **kw)


@pytest.mark.parametrize("strong", [True, False], ids=["strong_gates", "weak_gates"])
@pytest.mark.parametrize("T,chunk", [(64, 64), (100, 64), (256, 64), (40, 32), (8, 8)])
def test_the_launch_is_the_recurrence_and_the_xla_form(T, chunk, strong):
    """One chunk, a last chunk that is not full, the four chunks of a grid
    step, smaller chunks (shallower hierarchies): against the token-by-token
    recurrence at the XLA form's bound and against the XLA form itself, from
    a state that is not zero."""
    a = _launch_inputs(T, strong=strong)
    s0 = jnp.asarray(np.random.default_rng(5).normal(size=(2, 128, 128)), jnp.float32)
    want_o, want_s = reference.delta_rule(*a, state=s0)
    o, s = _launch(*a, chunk=chunk, state=s0)
    assert o.shape == want_o.shape and s.shape == want_s.shape == (2, 128, 128)
    assert _close(o, want_o) < 2e-5 and _close(s, want_s) < 2e-5
    xla_o, xla_s = ops.kda_chunk_scan(*a, chunk=chunk, state=s0)
    assert _close(o, xla_o) < 2e-5 and _close(s, xla_s) < 2e-5


def test_the_launch_carries_its_state_and_starts_from_zeros():
    a = _launch_inputs(150, seed=3)
    want_o, want_s = reference.delta_rule(*a)
    first, second = (tuple(t[part] for t in a) for part in (slice(None, 70), slice(70, None)))
    o1, s1 = _launch(*first, chunk=64)
    o2, s2 = _launch(*second, chunk=64, state=s1)
    assert _close(jnp.concatenate([o1, o2]), want_o) < 2e-5 and _close(s2, want_s) < 2e-5


def test_the_launch_leaves_the_state_as_it_was_under_padding():
    """g = 0 and beta = 0 past position 77: the state after a bucket of 128
    is the state after 77, and a whole chunk of padding changes nothing."""
    q, k, v, g, beta = _launch_inputs(128, seed=4)
    _, want = _launch(q[:77], k[:77], v[:77], g[:77], beta[:77], chunk=64)
    real = jnp.arange(128) < 77
    g, beta = jnp.where(real[:, None, None], g, 0.0), jnp.where(real[:, None], beta, 0.0)
    _, got = _launch(q, k, v, g, beta, chunk=64)
    assert _close(got, want) < 1e-6
    _, after = _launch(q[:64], k[:64], v[:64], 0 * g[:64], 0 * beta[:64], chunk=64, state=got)
    assert bool((after == got).all())


def test_the_launch_takes_no_exponent_of_a_positive_sum():
    """Decays of e^-5 a step for whole chunks of 64 (exp(-G_j) would be
    e^320) and beta within 1e-3 of 2 at every position: finite, and the
    recurrence's."""
    T = 128
    q, k, v, _, beta = _launch_inputs(T, seed=6)
    g = jnp.full((T, 2, 128), -5.0)
    beta = 2.0 - 1e-3 * beta / 2
    want_o, want_s = reference.delta_rule(q, k, v, g, beta)
    o, s = _launch(q, k, v, g, beta, chunk=64)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(s).all())
    assert _close(o, want_o) < 2e-5 and _close(s, want_s) < 2e-5
    # ... and without any decay, the keys of a chunk nearly one direction:
    # (I + A)^-1 has entries of alternating sign that do not die away
    k = k[:1] + 0.05 * k
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    want_o, want_s = reference.delta_rule(q, k, v, 0 * g, beta)
    o, s = _launch(q, k, v, 0 * g, beta, chunk=64)
    assert _close(o, want_o) < 2e-5 and _close(s, want_s) < 2e-5


def test_the_launch_is_chosen_from_backend_and_shape(monkeypatch):
    """On the CPU the XLA form runs whatever the shape; the launch takes
    keys of one 128-lane tile and values of whole ones, heads in pairs and a
    chunk that is a power of two from 8 to 64; outside them `interpret` runs
    the XLA form too."""
    from ray_tpu.ops import ssm

    assert ssm.kda_scan_tiles(64, 128, 128, 64) and not ssm.kda_scan_in_kernel(64, 128, 128, 64)
    assert ssm.kda_scan_tiles(2, 128, 256, 8)
    for heads, dk, dv, chunk in [(64, 64, 128, 64), (64, 256, 128, 64), (64, 128, 96, 64),
                                 (3, 128, 128, 64), (64, 128, 128, 48), (64, 128, 128, 4),
                                 (64, 128, 128, 128)]:
        assert not ssm.kda_scan_tiles(heads, dk, dv, chunk)
    launches = []
    monkeypatch.setattr(ssm, "_kda_scan_launch", lambda *a, **kw: launches.append(kw) or 1 / 0)
    a = _launch_inputs(16)
    ops.kda_chunk_scan(*a, chunk=8)                          # the CPU: no launch
    small = _kda_inputs(16)                                  # dk 8, dv 12
    ops.kda_chunk_scan(*small, chunk=8, interpret=True)
    ops.kda_chunk_scan(*_launch_inputs(16, H=3), chunk=8, interpret=True)
    assert not launches
    with pytest.raises(ZeroDivisionError):
        ops.kda_chunk_scan(*a, chunk=8, interpret=True)
    assert launches == [dict(chunk=8, interpret=True)]
    with pytest.raises(ValueError, match="sub-block"):
        ops.kda_chunk_scan(*a, chunk=20, interpret=True)


def test_the_gradient_through_the_mixer_is_the_xla_forms(monkeypatch):
    """`transformer.forward` differentiates the mixer in a train step: the
    launch's `custom_vjp` hands back the XLA form's own gradient."""
    import functools

    cfg = _cfg(ssm=transformer.KDAConfig(n_heads=2, d_head=128, gate_rank=8, chunk=16))
    p = jax.tree.map(lambda x: x.astype(jnp.float32),
                     transformer._kda_params(cfg, jax.random.PRNGKey(0)))
    x = jax.random.normal(jax.random.PRNGKey(1), (40, cfg.d_model), jnp.float32)

    def loss(p, x):
        y, state, _ = transformer.kda_mixer(x, p, cfg, length=jnp.int32(33))
        return (y ** 2).sum() + (state ** 2).sum()

    want_value, want = jax.value_and_grad(loss, argnums=(0, 1))(p, x)
    launches = []
    real = ops.kda_chunk_scan

    def launch(*a, **kw):
        launches.append(kw)
        return real(*a, interpret=True, **kw)

    monkeypatch.setattr(ops, "kda_chunk_scan", launch)
    got_value, got = jax.value_and_grad(loss, argnums=(0, 1))(p, x)
    assert len(launches) == 1 and float(abs(got_value - want_value)) < 1e-5 * float(want_value)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert _close(g, w) < 1e-4


# ---- the mixer's elementwise work on either side of the scan: three launches
# ---- (`kda_conv`, `kda_split`, `kda_gate_norm`), interpreted on the CPU, and
# ---- the XLA forms they stand for (`ops.causal_conv`, and the bodies of
# ---- `kda_split` and `kda_out` that run wherever the launches do not)


def _wide(H=2, D=128, dtype=jnp.float32):
    """Heads of one 128-lane tile, which the launches take."""
    return dataclasses.replace(_cfg(ssm=transformer.KDAConfig(
        n_heads=H, d_head=D, gate_rank=8, chunk=16)), dtype=dtype)


def _normal(seed, *shape, dtype=jnp.float32):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape), dtype)


@pytest.fixture
def launches(monkeypatch):
    """Counts the launches traced, by name."""
    from ray_tpu.ops import ssm

    seen = []
    for name in ("kda_conv_launch", "kda_split_launch", "kda_gate_norm_launch"):
        real = getattr(ssm, name)
        monkeypatch.setattr(ssm, name, lambda *a, _real=real, _name=name, **kw: (
            seen.append(_name[:-len("_launch")]), _real(*a, **kw))[1])
    return seen


@pytest.fixture
def mixer_as_on_chip(monkeypatch, launches):
    """`kda_mixer` as a TPU's backend traces it, its launches interpreted."""
    from ray_tpu.ops import ssm

    monkeypatch.setattr(ssm, "kda_mixer_in_kernel", ssm.kda_mixer_tiles)
    for name in ("kda_conv_launch", "kda_split_launch", "kda_gate_norm_launch"):
        counted = getattr(ssm, name)
        monkeypatch.setattr(ssm, name, lambda *a, interpret, _counted=counted, **kw: _counted(
            *a, interpret=True, **kw))
    return launches


@pytest.fixture
def predicates_as_on_chip(monkeypatch):
    """The predicates of `ops/ssm.py` see a TPU; nothing else does, and no
    kernel runs."""
    from ray_tpu.ops import ssm

    monkeypatch.setattr(ssm, "jax", type("jax", (), {
        "__getattr__": lambda _, name: getattr(jax, name),
        "default_backend": staticmethod(lambda: "tpu")})())


# (T, H, D): D = 128 in whole row blocks is a launch's; the rest (the tests'
# own heads of 16, a bucket under a block, a bucket that is not whole blocks)
# run the XLA form whatever `interpret` says
MIXER_SHAPES = [(128, 2, 128), (256, 4, 128), (384, 3, 128), (1024, 1, 128),
                (64, 4, 16), (64, 2, 128), (192, 2, 128)]


def _launched(T, D):
    return D == 128 and T % 128 == 0


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("T,H,D", MIXER_SHAPES)
def test_the_convolutions_launch_is_causal_conv(launches, T, H, D, dtype):
    """The projection as projected (either dtype), a tail that is not zero:
    float32 sums, float32 out, `ops.causal_conv` of a float32 copy."""
    cfg = _wide(H, D, dtype)
    C = 3 * H * D
    x, tail, w = _normal(1, T, C, dtype=dtype), _normal(2, 3, C, dtype=dtype), _normal(3, 4, C)
    got = transformer.kda_conv(x, tail, w, cfg, interpret=True)
    want = ops.causal_conv(x.astype(jnp.float32), tail, w)
    assert got.dtype == want.dtype == jnp.float32 and got.shape == (T, C)
    assert _close(got, want) < 1e-6
    assert launches == ["kda_conv"] * _launched(T, D)
    assert _close(transformer.kda_conv(x, tail, w, cfg), want) == 0.0   # the CPU: no launch
    assert len(launches) == _launched(T, D)


@pytest.mark.parametrize("T,H,D", MIXER_SHAPES)
def test_the_split_launch_is_the_xla_form(launches, T, H, D):
    cfg = _wide(H, D)
    conved = 2.0 * _normal(4, T, 3 * H * D)
    got = transformer.kda_split(conved, cfg, interpret=True)
    want = transformer.kda_split(conved, cfg)
    assert launches == ["kda_split"] * _launched(T, D)
    for g, w in zip(got, want):
        assert g.shape == (T, H, D) and g.dtype == w.dtype == jnp.float32
        assert _close(g, w) < 1e-6
    q, k, _ = got                                          # unit length a head, q times D^-1/2
    assert _close(jnp.linalg.norm(k, axis=-1), jnp.ones((T, H))) < 1e-4
    assert _close(jnp.linalg.norm(q, axis=-1) * D ** 0.5, jnp.ones((T, H))) < 1e-4


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("T,H,D", MIXER_SHAPES)
def test_the_gated_norm_launch_is_the_xla_form(launches, T, H, D, dtype):
    """... and what `out_proj` multiplies is in the activations' dtype: to
    1e-6 in float32, to an ulp of the rounding in bfloat16."""
    cfg = _wide(H, D, dtype)
    o, gate = _normal(5, T, H, D), 3.0 * _normal(6, T, H, D)
    w = 1.0 + 0.1 * _normal(7, D, dtype=dtype)
    got = transformer.kda_gated_norm(o, gate, w, cfg, interpret=True)
    want = transformer.kda_gated_norm(o, gate, w, cfg)
    assert launches == ["kda_gate_norm"] * _launched(T, D)
    assert got.dtype == want.dtype == dtype and got.shape == (T, H * D)
    assert _close(got.astype(jnp.float32), want.astype(jnp.float32)) < (
        1e-6 if dtype == jnp.float32 else 2 ** -8)


def test_a_tail_carried_across_two_launches_is_one_launch(launches):
    """The convolutions of a sequence in two spans, the second after the
    first's last three inputs (`ops.conv_tail`), are those of the sequence in
    one: across blocks of a launch (three of 128 rows), across launches, and
    from a tail that ends inside the first span's padding."""
    cfg = _wide(H=2, dtype=jnp.bfloat16)
    C = 3 * 2 * 128
    x, tail, w = (_normal(1, 640, C, dtype=jnp.bfloat16), _normal(2, 3, C, dtype=jnp.bfloat16),
                  _normal(3, 4, C))
    conv = lambda x, tail: transformer.kda_conv(x, tail, w, cfg, interpret=True)   # noqa: E731
    whole = conv(x, tail)
    first = conv(x[:384], tail)
    second = conv(x[384:], ops.conv_tail(x[:384], tail, 384))
    assert bool((jnp.concatenate([first, second]) == whole).all())
    # the first span padded: 300 real positions in a bucket of 384
    second = conv(x[300:556], ops.conv_tail(x[:384], tail, 300))
    assert bool((second == whole[300:556]).all())
    assert launches == ["kda_conv"] * 4


def test_the_mixers_launches_are_chosen_from_shape(launches):
    """On the CPU the XLA forms run whatever the shape; the launches take
    heads of one 128-lane tile over whole row blocks of at least 128
    positions; outside them `interpret` runs the XLA forms too, and so does
    a decode step's batch of rows."""
    from ray_tpu.ops import ssm

    assert ssm.kda_mixer_tiles(2048, 128, 4) and ssm.kda_mixer_tiles(128, 128, 2)
    assert not ssm.kda_mixer_in_kernel(2048, 128, 4)                 # the CPU
    for T, D, K in [(32, 128, 4), (64, 128, 4), (192, 128, 4), (2048, 64, 4), (2048, 256, 4),
                    (2048, 128, 1), (2048, 128, 10)]:
        assert not ssm.kda_mixer_tiles(T, D, K)
    cfg = _wide()
    p = jax.tree.map(lambda x: x.astype(jnp.float32),
                     transformer._kda_params(cfg, jax.random.PRNGKey(0)))
    x = _normal(1, 128, cfg.d_model)
    transformer.kda_mixer(x, p, cfg)                                  # the CPU: no launch
    transformer.kda_mixer(x[:64], p, cfg)
    # a decode step's rows [B, .] of a few slots, and a batch of rows [B, T, .]
    transformer.kda_split(_normal(2, 32, 3 * 256), cfg, interpret=True)
    transformer.kda_split(_normal(2, 2, 128, 3 * 256), cfg, interpret=True)
    transformer.kda_gated_norm(_normal(3, 32, 2, 128), _normal(4, 32, 2, 128), p["norm"], cfg,
                               interpret=True)
    assert not launches
    transformer.kda_split(_normal(2, 128, 3 * 256), cfg, interpret=True)
    assert launches == ["kda_split"]


def test_the_mixers_launches_are_chosen_from_backend_and_mesh(predicates_as_on_chip):
    """Where the predicates see a TPU: whole row blocks outside a mesh, and
    nothing inside one (GSPMD cannot partition a Mosaic kernel)."""
    from ray_tpu.ops import ssm

    assert ssm.kda_mixer_in_kernel(2048, 128, 4) and ssm.kda_mixer_in_kernel(128, 128, 4)
    assert not ssm.kda_mixer_in_kernel(32, 128, 4) and not ssm.kda_mixer_in_kernel(2048, 64, 4)
    with jax.set_mesh(jax.make_mesh((2,), ("tp",))):
        assert not ssm.kda_mixer_in_kernel(2048, 128, 4)


def test_length_inside_a_bucket_leaves_state_and_tail_as_at_length(mixer_as_on_chip):
    """The mixer through its launches over a bucket of 128 with 77 real
    positions: the state and the tail are those after 77 positions (the XLA
    forms over exactly 77), the outputs of the real positions agree, and a
    second span carries both on."""
    cfg = _wide()
    p = jax.tree.map(lambda x: x.astype(jnp.float32) + 0.05 * _normal(9, *x.shape),
                     transformer._kda_params(cfg, jax.random.PRNGKey(0)))
    x = _normal(1, 256, cfg.d_model)
    want_y, want_state, want_tail = transformer.kda_mixer(x[:77], p, cfg)
    assert not mixer_as_on_chip                                       # 77: the XLA forms
    y, state, tail = transformer.kda_mixer(x[:128], p, cfg, length=jnp.int32(77))
    assert mixer_as_on_chip == ["kda_conv", "kda_split", "kda_gate_norm"]
    assert _close(y[:77], want_y) < 1e-5 and _close(state, want_state) < 1e-5
    assert bool((tail == want_tail).all())
    whole_y, whole_state, whole_tail = transformer.kda_mixer(x[:205], p, cfg)
    y2, state2, tail2 = transformer.kda_mixer(x[77:205], p, cfg, None, state, tail)
    assert len(mixer_as_on_chip) == 6
    assert _close(y2, whole_y[77:]) < 1e-5 and _close(state2, whole_state) < 1e-5
    assert bool((tail2 == whole_tail).all())


def test_the_gradient_through_the_mixers_launches_is_the_xla_forms(request):
    """As `test_the_gradient_through_the_mixer_is_the_xla_forms` for the three
    launches around the scan (the scan in its XLA form here): each
    `custom_vjp` hands back its XLA form's own gradient, also under the `vmap`
    over a batch's rows that `transformer.forward` takes the mixer in."""
    cfg = _wide()
    p = jax.tree.map(lambda x: x.astype(jnp.float32),
                     transformer._kda_params(cfg, jax.random.PRNGKey(0)))
    x = _normal(1, 2, 128, cfg.d_model)

    def loss(p, x):
        y, state, _ = jax.vmap(lambda row: transformer.kda_mixer(row, p, cfg, jnp.int32(101)))(x)
        return (y ** 2).sum() + (state ** 2).sum()

    want_value, want = jax.value_and_grad(loss, argnums=(0, 1))(p, x)
    launches = request.getfixturevalue("mixer_as_on_chip")
    got_value, got = jax.value_and_grad(loss, argnums=(0, 1))(p, x)
    assert sorted(set(launches)) == ["kda_conv", "kda_gate_norm", "kda_split"]
    assert float(abs(got_value - want_value)) < 1e-5 * float(want_value)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert _close(g, w) < 1e-4


@pytest.mark.parametrize("n", [1, 2, 3, CHUNK - 1, CHUNK, CHUNK + 1, 45])
def test_a_padded_bucket_leaves_state_and_tail_as_at_n(model, n):
    """Padding to a bucket must not advance the recurrent state, and the
    tails are the last three REAL inputs (zeros before the row's start)."""
    cfg, p = model
    tokens = _tokens(70, seed=n)
    _, exact = _bucket_prefill(cfg, p, tokens, n, n)            # no padding at all
    for bucket in (16, 32, 64):
        if bucket < n:
            continue
        _, kv = _bucket_prefill(cfg, p, tokens, n, bucket)
        assert kv["ssm"].shape == (LK, 4, 16, 16) and kv["conv"].shape == (LK, 3, 192)
        assert kv["ssm"].dtype == jnp.float32
        assert _close(kv["ssm"], exact["ssm"]) < 1e-5, bucket
        assert _close(kv["conv"], exact["conv"]) < 1e-5, bucket
    if n < 3:
        assert float(jnp.abs(exact["conv"][:, :3 - n]).max()) == 0.0


def _prefilled(cfg, p, tokens, n, bucket, slot=1, slots=3):
    logits, kv = _bucket_prefill(cfg, p, tokens, n, bucket)
    state = dp.init_paged_state(cfg, slots, MAX_LEN, 40, PAGE)
    row = np.zeros((MAX_LEN // PAGE,), np.int32)
    row[:] = 1 + np.arange(MAX_LEN // PAGE)
    state = dp.insert_sequence_paged(state, slot, kv, jnp.int32(n), jnp.int32(tokens[n]),
                                     jnp.asarray(row), cfg)
    return logits, kv, state


@pytest.mark.parametrize("which", ["whole", "share"])
@pytest.mark.parametrize("kernel", [False, True], ids=["mirror", "kernel"])
def test_prefill_then_paged_decode_agrees_with_the_full_forward(
        model, share, kernels_interpreted, kernel, which):
    """A prompt in a padded bucket, then decode steps across page boundaries
    through the ONE attention kind's pages and the slot's recurrent state,
    against the reference's full forward at EVERY position; with a share, the
    reference given the same share and the counts of the held slots."""
    cfg, p = model if which == "whole" else share
    n, steps = 41, 20
    tokens = _tokens(n + steps + 1)
    want, _ = reference.forward(p, jnp.asarray(tokens[:-1]), _sizes(cfg))
    logits, kv, state = _prefilled(cfg, p, tokens, n, 64)
    assert state["kp"].shape == (LA, 40, PAGE, 2, 16)
    assert state["ssm"].shape == (LK, 3, 4, 16, 16) and state["ssm"].dtype == jnp.float32
    assert state["conv"].shape == (LK, 3, 3, 192)
    assert ("expert_counts" in kv) == cfg.moe.share
    assert _close(logits, want[n - 1]) < TOL
    for i in range(steps):
        state, step = dp.decode_step_paged_ragged(p, state, cfg, 8, kernel)
        assert _close(step[1], want[n + i]) < TOL, i
        # the rows that hold nothing keep what they had: zeros
        assert float(jnp.abs(state["ssm"][:, 0]).max()) == 0.0
        if cfg.moe.share:  # 3 rows x 4 slots x 8 layers routed; some held here
            held, groups = (int(c) for c in state.pop("expert_counts"))
            assert 0 < held < 3 * 4 * 8 and 0 < groups <= min(held, 8 * 8)
        state = decoding.commit_tokens(state, jnp.full((3,), tokens[n + i + 1], jnp.int32))
    assert int(state["length"][1]) == n + steps


def _chunked(cfg, p, tokens, n, chunk, spoil=None):
    """The engine's staged prefill by hand: chunks of `chunk` (the tail padded
    to it), the attention layers' prefix gathered out of the pool, the
    recurrent state and the tails carried from chunk to chunk (`spoil`: what
    a wrong engine would do to them on the way)."""
    state = dp.init_paged_state(cfg, 2, MAX_LEN, 40, PAGE)
    row = np.zeros((MAX_LEN // PAGE,), np.int32)
    row[:] = 1 + np.random.default_rng(2).permutation(39)[:MAX_LEN // PAGE]
    carried, counted = None, 0
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
            logits, kv = dp.prefill_with_prefix(
                p, jnp.asarray(padded), pk, pv, jnp.int32(done), jnp.int32(live), cfg,
                row_state=carried if spoil is None else spoil(carried))
        if cfg.moe.share:
            counted += int(kv["expert_counts"][0])
        carried = {name: kv[name] for name in ("ssm", "conv")}
        pages = range(done // PAGE, (done + chunk) // PAGE)
        state = dp.write_kv_pages(state, kv, jnp.asarray(row[list(pages)]))
    return logits, state, row, carried, counted


@pytest.mark.parametrize("chunks,chunk,which", [(2, 32, "whole"), (3, 32, "whole"),
                                                (5, 32, "share"), (3, 64, "share"),
                                                (2, 16, "whole")])
def test_chunked_prefill_agrees_with_one_shot_prefill(model, share, chunks, chunk, which):
    """2, 3 and 5 chunks with a padded tail chunk (chunks of the scan's own
    size, of two of them, and of half of one) against one-shot prefill and the
    reference, then decode steps from the state the chunks carried."""
    cfg, p = model if which == "whole" else share
    n, steps = chunk * chunks - 11, 5
    tokens = _tokens(n + steps + 1)
    one_shot, whole = _bucket_prefill(cfg, p, tokens, n, 256)
    want, _ = reference.forward(p, jnp.asarray(tokens[:-1]), _sizes(cfg))
    logits, state, row, carried, counted = _chunked(cfg, p, tokens, n, chunk)
    assert _close(logits, one_shot) < TOL and _close(logits, want[n - 1]) < TOL
    assert _close(carried["ssm"], whole["ssm"]) < TOL
    assert _close(carried["conv"], whole["conv"]) < TOL
    assert (counted > 0) == cfg.moe.share
    assert float(jnp.abs(state["ssm"]).max()) == 0.0   # not in a slot before the row is live
    state = dp.activate_slot(state, 0, jnp.asarray(row), jnp.int32(n), jnp.int32(tokens[n]),
                             None, carried)
    for i in range(steps):
        state, step = dp.decode_step_paged_ragged(p, state, cfg, 16, False)
        assert _close(step[0], want[n + i]) < TOL, i
        state.pop("expert_counts", None)
        state = decoding.commit_tokens(state, jnp.full((2,), tokens[n + i + 1], jnp.int32))


@pytest.mark.parametrize("dropped", ["ssm", "conv"])
def test_a_state_or_a_tail_not_carried_between_chunks_shows(model, dropped):
    cfg, p = model
    n = 70
    tokens = _tokens(n + 1)
    want, _ = reference.forward(p, jnp.asarray(tokens[:-1]), _sizes(cfg))
    logits = _chunked(cfg, p, tokens, n, 32, lambda carried: {
        **carried, dropped: jnp.zeros_like(carried[dropped])})[0]
    assert _close(logits, want[n - 1]) > 5 * TOL


# ------------------------------------------------------------------ the kernel


def _step_inputs(R=4, H=8, D=16, Dv=128, L=3, seed=5):
    rng = np.random.default_rng(seed)
    state = jnp.asarray(rng.normal(size=(L, R, H, D, Dv)), jnp.float32)
    q, k = (rng.normal(size=(R, H, D)) for _ in range(2))
    q, k = (jnp.asarray(a / np.linalg.norm(a, axis=-1, keepdims=True), jnp.float32)
            for a in (q, k))
    v = jnp.asarray(rng.normal(size=(R, H, Dv)), jnp.float32)
    g = -jnp.asarray(rng.uniform(0.0, 5.0, (R, H, D)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.0, 2.0, (R, H)), jnp.float32)
    return state, q, k, v, g, beta


def test_the_update_kernel_is_its_reference_bit_for_bit_and_updates_in_place():
    state, q, k, v, g, beta = _step_inputs()
    live = jnp.asarray([True, True, False, True])
    want_s, want_o = ops.kda_state_update(state, jnp.int32(1), q, k, v, g, beta, live=live)
    got_s, got_o = ops.kda_state_update(state, jnp.int32(1), q, k, v, g, beta, live=live,
                                        impl="kernel", interpret=True)
    assert float(jnp.abs(got_s - want_s).max()) == 0.0          # bit for bit
    assert float(jnp.abs(got_o - want_o).max()) == 0.0
    for s, o in ((got_s, got_o), (want_s, want_o)):
        assert float(jnp.abs(s[0] - state[0]).max()) == 0.0    # the other layers
        assert float(jnp.abs(s[2] - state[2]).max()) == 0.0
        assert bool((s[1, 2] == state[1, 2]).all())             # not live: bit for bit
        assert float(jnp.abs(o[2]).max()) == 0.0
        assert float(jnp.abs(s[1, 0] - state[1, 0]).max()) > 0
    # no row live (the engine never steps then): nothing moves
    idle_s, idle_o = ops.kda_state_update(state, jnp.int32(1), q, k, v, g, beta,
                                          live=jnp.zeros((4,), bool), impl="kernel",
                                          interpret=True)
    assert float(jnp.abs(idle_s - state).max()) == 0.0 and float(jnp.abs(idle_o).max()) == 0.0
    every_s, _ = ops.kda_state_update(state, jnp.int32(1), q, k, v, g, beta, impl="kernel",
                                      interpret=True)
    assert float(jnp.abs(every_s[1, 2] - state[1, 2]).max()) > 0
    # against the recurrence's own arithmetic, one step from that state
    o1, s1 = reference.delta_rule(q[:1], k[:1], v[:1], g[:1], beta[:1], state[1, 0])
    assert _close(got_s[1, 0], s1) < 1e-6 and _close(got_o[0], o1[0]) < 1e-5
    with pytest.raises(ValueError, match="impl"):
        ops.kda_state_update(state, jnp.int32(0), q, k, v, g, beta, impl="xla")


def test_steps_of_the_kernel_follow_the_scan():
    """A prefix through the chunked scan, then the rest a step at a time
    through the kernel (interpreted): the recurrence's outputs and state."""
    T, cut = 40, 27
    q, k, v, g, beta = _kda_inputs(T, H=8, D=16)
    v = jnp.pad(v, [(0, 0), (0, 0), (0, 128 - v.shape[-1])])
    want_o, want_s = reference.delta_rule(q, k, v, g, beta)
    _, s = ops.kda_chunk_scan(q[:cut], k[:cut], v[:cut], g[:cut], beta[:cut], chunk=16,
                              sub=4)
    state = jnp.zeros((2, 1, 8, 16, 128), jnp.float32).at[1, 0].set(s)
    for t in range(cut, T):
        state, o = ops.kda_state_update(state, jnp.int32(1), q[t][None], k[t][None],
                                        v[t][None], g[t][None], beta[t][None],
                                        impl="kernel", interpret=True)
        assert _close(o[0], want_o[t]) < 2e-5, t
    assert _close(state[1, 0], want_s) < 2e-5


def test_the_on_chip_comparison_of_the_update_kernel_runs_here_interpreted():
    """`chip_smoke.compare_kda_update`, what a chip call runs at a row's
    published shape over the configuration's slots: scattered live rows that
    change from step to step, slots taken again, one row, none, all."""
    import chip_smoke

    r = chip_smoke.compare_kda_update(dict(layers=3, slots=12, heads=2, head_dim=128),
                                      interpret=True)
    assert r["ok"] and [s["live"] for s in r["steps"]] == [4, 5, 1, 0, 12]
    assert all(s["others_bit_equal"] and s["dead_o_zero"] for s in r["steps"])
    assert r["max_abs_err"] == 0.0                                # interpreted: the same bits


# ---------------------------------------------------------- the experts' share


@pytest.mark.parametrize("n_tokens", [100, ops.moe.SORTED_MIN_TOKENS + 8],
                         ids=["onehot", "sorted"])
def test_eight_shares_add_up_to_the_uncut_layer(model, n_tokens):
    """One KDA layer's expert layer of 64 experts as 8 shares of 8: what each
    share's held experts give, the shared expert (which every chip computes
    alike) counted once, sums to the uncut reference's layer; and the
    program's counts of one share are the count by hand."""
    from chipbench.reference import kimi_vl

    cfg, p = model
    layer = jax.tree.map(lambda a: a[2], p["ssm_layers"])
    x = jax.random.normal(jax.random.PRNGKey(n_tokens), (1, n_tokens, cfg.d_model))
    # the uncut reference's layer: all 64 experts, one after another
    stacked = jax.tree.map(lambda a: a[None], layer)
    h, gates, _ = kimi_vl._route(
        {**stacked, "norm2": {"w": jnp.ones((1, cfg.d_model))}}, 0, x[0],
        jnp.zeros((n_tokens,), jnp.int32), top_k=cfg.moe.top_k, scale=1.0, eps=0.0)
    x = h[None]                     # the layer's input as the router normed it
    shared = kimi_vl._swiglu(stacked["mlp"]["shared"], 0, h)
    routed = kimi_vl._experts(stacked["mlp"], 0, h, gates, cap=n_tokens)
    total = jnp.zeros_like(h)
    for c in range(8):
        part = _cfg(experts_held=8, first_expert=8 * c)
        mlp = {**layer["mlp"], **{k: layer["mlp"][k][8 * c:8 * c + 8]
                                  for k in ("gate", "up", "down")}}
        y, _, counts = transformer._moe_mlp(x, mlp, part)
        total = total + (y[0] - shared)
        mine = np.asarray(gates[:, 8 * c:8 * c + 8] > 0)
        assert counts.tolist() == [int(mine.sum()), int(mine.any(axis=0).sum())]
    assert float(jnp.abs(total - routed).max() / jnp.abs(routed).max()) < TOL
    y, _ = transformer._moe_mlp(x, layer["mlp"], cfg)       # and the program's own uncut
    assert float(jnp.abs(y[0] - (routed + shared)).max() / jnp.abs(routed).max()) < TOL


# ------------------------------------------------ what is built, what is refused


def test_what_is_not_built_beside_recurrent_layers_is_refused(model):
    cfg, p = model
    init = lambda c: transformer.init(jax.random.PRNGKey(0), c)  # noqa: E731
    for kwargs in (dict(window=64, window_period=2), dict(n_passes=2), dict(bias=True),
                   dict(qk_norm=True), dict(sandwich_norms=True)):
        with pytest.raises(ValueError, match="state-space layers"):
            init(dataclasses.replace(cfg, **kwargs))
    # experts and the gate go with the gated delta rule's layers: not with
    # Mamba-2's, not with a capacity, not with the scalar multipliers
    mamba = granite_config("tiny", vocab_size=VOCAB)
    for wrong in (dataclasses.replace(mamba, moe=cfg.moe),
                  dataclasses.replace(mamba, attn_gate=True),
                  dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, score_func="softmax",
                                                                   capacity_factor=1.25)),
                  dataclasses.replace(cfg, logits_scaling=8.0)):
        with pytest.raises(ValueError, match="gated delta rule"):
            init(wrong)
    with pytest.raises(ValueError, match="whole periods"):
        init(dataclasses.replace(cfg, n_layers=6))
    with pytest.raises(ValueError, match="multiple of 8"):
        init(dataclasses.replace(cfg, ssm=dataclasses.replace(cfg.ssm, chunk=20)))
    with pytest.raises(NotImplementedError, match="state-space layers"):
        decoding.init_lora_bank(cfg, 2, 4)
    with pytest.raises(ValueError, match="sequence-parallel"):
        transformer.forward(p, jnp.zeros((1, 8), jnp.int32), cfg, sp_axis="sp")


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
def test_what_the_engine_does_not_carry_is_refused_at_construction(share, kwargs, what):
    cfg, p = share
    with pytest.raises(ValueError, match=what):
        _engine(cfg, p, **kwargs)


# ------------------------------------------------------------------ the engine


def test_the_engine_serves_it_in_chunks_and_counts_what_it_ran(share):
    """Two prompts through `TPUEngine` with chunked prefill (three chunks and
    one), greedy: every token is the reference's best at its position; the
    state a row, the held slots, the live rows' steps and the scan's
    positions are on `stats()`."""
    from ray_tpu.llm.engine import SamplingParams

    cfg, p = share
    eng = _engine(cfg, p, prefill_chunk=32)
    try:
        prompts = [_tokens(75, seed=1).tolist(), _tokens(20, seed=2).tolist()]
        reqs = [eng.submit(t, SamplingParams(max_tokens=6)) for t in prompts]
        outs = [list(r) for r in reqs]
        stats = eng.stats()
    finally:
        eng.shutdown()
    for prompt, out in zip(prompts, outs):
        assert len(out) == 6
        want, _ = reference.forward(p, jnp.asarray(prompt + out[:-1], jnp.int32), _sizes(cfg))
        assert [int(jnp.argmax(want[len(prompt) - 1 + i])) for i in range(6)] == out
    assert stats["cache"]["state_bytes_per_row"] == LK * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    assert stats["prefill_chunks_run"] == 3
    # a bucket rounded up to whole chunks of the scan, a KDA layer: 32 + 32 +
    # 32 (11 real) for the long prompt, 32 (20 real) for the short one
    assert stats["prefill"]["scan_positions"] == LK * 128
    assert stats["prefill"]["scan_padded"] == LK * (128 - 95)
    assert stats["prefill"]["scan_kernel_positions"] == 0      # the CPU: the XLA form
    experts = stats["experts"]
    assert 0 < experts["slots_held"] < experts["slots_routed"]
    assert experts["groups_with_rows"] > 0 and experts["calls"] % cfg.n_layers == 0
    assert stats["decode_slot_steps"] >= 10 and stats["decode_steps"] >= 5


# ---- what a TPU's backend would choose, asked on the CPU: the predicates of
# ---- `ops/ssm.py` see a TPU, nothing else does (no kernel runs)


@pytest.mark.parametrize("family", ["dense", "mixtral_style", "state_space", "kda"])
def test_engines_of_other_families_build_where_the_predicates_see_a_tpu(
        predicates_as_on_chip, family):
    """What a replica of every configuration runs in `TPUEngine.__init__` on
    the chip and no CPU test saw (ledger, PR 52: the first serve cell's
    replica, Mixtral's, did not come up): with the backend test of the KDA
    launches' predicates forced true, a dense, a Mixtral-style and a
    state-space (not KDA) configuration build their engines, their counters
    of the KDA launches stay 0 and off, and a KDA configuration of heads that
    tile turns both on."""
    from ray_tpu.models import llama_config
    from ray_tpu.ops import ssm

    sizes = dict(vocab_size=VOCAB, max_seq_len=1024, dtype=jnp.float32)
    cfg = {
        "dense": lambda: llama_config("tiny", **sizes),
        "mixtral_style": lambda: dataclasses.replace(
            llama_config("tiny", **sizes),
            moe=transformer.MoEConfig(num_experts=4, top_k=2, capacity_factor=None)),
        "state_space": lambda: granite_config("tiny", **sizes),
        "kda": lambda: _wide(H=2),
    }[family]()
    assert ssm.kda_mixer_in_kernel(128, 128, 4) and ssm.kda_scan_in_kernel(2, 128, 128, 64)
    eng = _engine(cfg, transformer.init(jax.random.PRNGKey(0), cfg))
    try:
        stats = eng.stats()
        assert (eng._scan_kernel, eng._mixer_kernel) == ((family == "kda"),) * 2
        assert eng.mixer_kernel_positions == eng.scan_kernel_positions == 0
        assert stats["prefill"].get("mixer_kernel_positions", 0) == 0
        assert ("mixer_kernel_positions" in stats["prefill"]) == (cfg.ssm is not None)
    finally:
        eng.shutdown()


def test_importing_the_ops_initialises_no_backend():
    """Nothing of `ray_tpu.ops` runs at import: a replica's process asks for
    its chip when its engine is built, not when a module is loaded."""
    import subprocess

    code = ("import ray_tpu.ops, ray_tpu.models.transformer; from jax._src import xla_bridge; "
            "assert not xla_bridge.backends_are_initialized(), 'a backend was initialised'")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, "-c", code], cwd=root, capture_output=True, text=True,
                       timeout=120, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-2000:]
