"""Ops tests: numerics vs plain-jax references; flash kernel via interpret."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu import ops
from ray_tpu.ops.flash_attention import _reference_bhtd, flash_attention_forward


def test_rms_norm():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 16))
    w = jnp.ones(16) * 2.0
    y = ops.rms_norm(x, w)
    ref = x / np.sqrt((np.asarray(x) ** 2).mean(-1, keepdims=True) + 1e-6) * 2.0
    np.testing.assert_allclose(np.asarray(y), ref, atol=1e-5)


def test_layer_norm_matches_flax():
    import flax.linen as nn

    x = jax.random.normal(jax.random.PRNGKey(1), (3, 7, 32))
    w = jax.random.normal(jax.random.PRNGKey(2), (32,))
    b = jax.random.normal(jax.random.PRNGKey(3), (32,))
    y = ops.layer_norm(x, w, b)
    ln = nn.LayerNorm(epsilon=1e-5)
    ref = ln.apply({"params": {"scale": w, "bias": b}}, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-4)


def test_rope_rotation_preserves_norm():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 10, 4, 32))
    cos, sin = ops.rope_frequencies(32, 64)
    y = ops.apply_rope(x, cos, sin)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(x), axis=-1),
        np.linalg.norm(np.asarray(y), axis=-1),
        atol=1e-4,
    )
    # position 0 is identity
    np.testing.assert_allclose(np.asarray(y[:, 0]), np.asarray(x[:, 0]), atol=1e-5)


def test_cross_entropy_matches_optax():
    import optax

    logits = jax.random.normal(jax.random.PRNGKey(0), (6, 11))
    labels = jnp.array([0, 5, 10, 3, 2, 7])
    loss, n = ops.softmax_cross_entropy(logits, labels)
    ref = optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()
    assert n == 6
    np.testing.assert_allclose(float(loss), float(ref), atol=1e-5)


def test_cross_entropy_ignore_index():
    logits = jax.random.normal(jax.random.PRNGKey(0), (4, 5))
    labels = jnp.array([1, -100, 2, -100])
    loss, n = ops.softmax_cross_entropy(logits, labels)
    assert n == 2
    assert np.isfinite(float(loss))


def _flash_qkv(seed, shape, dtype):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return [jax.random.normal(k, shape).astype(dtype) for k in ks]


def _reference_f32(q, k, v, *, causal):
    """`_reference_bhtd` on the same values, computed and returned in float32."""
    return _reference_bhtd(*(x.astype(jnp.float32) for x in (q, k, v)),
                           causal=causal, scale=q.shape[-1] ** -0.5)


def _assert_flash_close(got, ref, dtype, f32_tol, err_msg=""):
    """float32 operands: the kernel's products are float32 products, elementwise
    tolerance. bfloat16 operands: the kernel rounds p / ds to bfloat16 before
    their second product and its result to bfloat16 (2^-8 each), so 2e-2 of
    the result's scale; a dropped term or a wrong mask on a block is O(1)."""
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, ref, atol=f32_tol, rtol=f32_tol,
                                   err_msg=err_msg)
    else:
        np.testing.assert_allclose(got, ref, atol=2e-2 * np.abs(ref).max(),
                                   rtol=0, err_msg=err_msg)
        # and in the norm, where a handful of wrong rows cannot hide
        assert np.linalg.norm(got - ref) <= 1e-2 * np.linalg.norm(ref), err_msg


_DTYPES = pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                                  ids=["float32", "bfloat16"])


@_DTYPES
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_interpret_matches_reference(causal, dtype):
    B, H, T, D = 2, 2, 512, 64
    q, k, v = _flash_qkv(0, (B, H, T, D), dtype)
    out = flash_attention_forward(q, k, v, causal=causal, interpret=True,
                                  block_q=128, block_k=128)
    assert out.dtype == dtype
    ref = _reference_f32(q, k, v, causal=causal)
    _assert_flash_close(out, ref, dtype, 2e-5)


def _flash_grads_vs_reference(shape, dtype, *, causal, block_q, block_k, seed,
                              loss):
    from ray_tpu.ops.flash_attention import flash_attention

    q, k, v = _flash_qkv(seed, shape, dtype)

    def f_flash(q, k, v):
        out = flash_attention(q, k, v, causal, None, block_q, block_k, True)
        return loss(out.astype(jnp.float32))

    def f_ref(q, k, v):
        return loss(_reference_f32(q, k, v, causal=causal))

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(
        *(x.astype(jnp.float32) for x in (q, k, v)))
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        assert gf.dtype == dtype
        _assert_flash_close(gf, gr, dtype, 5e-4, err_msg=f"d{name}")


@_DTYPES
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_backward_interpret_matches_reference(causal, dtype):
    _flash_grads_vs_reference((1, 2, 256, 64), dtype, causal=causal,
                              block_q=128, block_k=128, seed=7,
                              loss=lambda o: (o ** 2).sum())


@_DTYPES
def test_flash_attention_backward_uneven_blocks(dtype):
    # block_q != block_k exercises the causal liveness predicates on both
    # backward kernels
    _flash_grads_vs_reference((1, 1, 256, 32), dtype, causal=True,
                              block_q=128, block_k=64, seed=3,
                              loss=lambda o: (o * 0.5).sum())


@_DTYPES
def test_flash_attention_backward_four_blocks(dtype):
    # T = 4 blocks: blocks wholly under the diagonal (no mask built), blocks
    # the diagonal crosses and skipped blocks above it all occur, in the
    # forward and in both backward kernels
    _flash_grads_vs_reference((1, 2, 512, 64), dtype, causal=True,
                              block_q=128, block_k=128, seed=11,
                              loss=lambda o: (o ** 2).sum())


@_DTYPES
def test_flash_attention_diagonal_strips(dtype):
    # blocks of 512 at T = 1024: the two blocks on the diagonal are computed in
    # strips of 256 with what lies above each strip left out, the block under
    # the diagonal whole, in the forward and in both backward kernels
    _flash_grads_vs_reference((1, 1, 1024, 64), dtype, causal=True,
                              block_q=512, block_k=512, seed=5,
                              loss=lambda o: (o ** 2).sum())


# d_head, T, the block asked for (both kernels' axes; None: the kernels' own
# choice), causal: one block at T = 256 by choice, 2 x 2 to 16 x 16 blocks
# elsewhere, so a q block's log-sum-exp is written after several kv steps and
# the dq kernel's once-a-q-block turn of its rows is read across kv steps
_ROWS_GRID = [
    pytest.param(d, T, block, causal,
                 id=f"d{d}-t{T}-{'auto' if block is None else block}-"
                    f"{'causal' if causal else 'full'}")
    for d in (64, 128) for T in (256, 1024, 2048) for block in (None, 128, 256)
    for causal in (True, False)]


@pytest.mark.parametrize("d_head,T,block,causal", _ROWS_GRID)
def test_flash_forward_hands_over_its_log_sum_exp_as_rows(d_head, T, block, causal):
    """The forward kernel's second result is [B, H, 1, T] (PR 58: rows at
    every kernel boundary, never the column [B, H, T, 1]) and is the float32
    reference's log-sum-exp of the scaled, masked scores."""
    from ray_tpu.ops.flash_attention import _NEG_INF, _fwd_call

    B, H = 1, 2
    q, k, v = _flash_qkv(T + d_head, (B, H, T, d_head), jnp.float32)
    scale = d_head ** -0.5
    out, lse = _fwd_call(q, k, v, causal=causal, scale=scale, block_q=block,
                         block_k=block, interpret=True)
    assert (lse.shape, str(lse.dtype)) == ((B, H, 1, T), "float32")
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, _NEG_INF)
    np.testing.assert_allclose(np.asarray(lse[:, :, 0]),
                               np.asarray(jax.nn.logsumexp(s, axis=-1)),
                               atol=1e-5, rtol=1e-5)
    _assert_flash_close(out, _reference_f32(q, k, v, causal=causal), jnp.float32, 2e-5)


@_DTYPES
@pytest.mark.parametrize("d_head,T,block,causal", _ROWS_GRID)
def test_flash_attention_gradients_over_rows_match_reference(d_head, T, block, causal, dtype):
    """`jax.grad` through `flash_attention`, whose residual is the row form:
    both backward kernels read the forward's [B, H, 1, T] and one `delta`."""
    _flash_grads_vs_reference((1, 1, T, d_head), dtype, causal=causal,
                              block_q=block, block_k=block, seed=T + d_head,
                              loss=lambda o: (o ** 2).sum())


@_DTYPES
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("T", [256, 1024, 2048])
@pytest.mark.parametrize("d_head", [64, 128])
def test_flash_rule_gradients_over_rows_match_reference(d_head, T, causal, dtype):
    """The same through `ops.attention._flash`, the train step's rule on
    [B, T, H, D], which names the rows and hands them back (blocks: the
    kernels' own choice, 2 x 2 of 1,024 at T = 2,048)."""
    import sys

    _flash = sys.modules["ray_tpu.ops.attention"]._flash
    q, k, v = _flash_qkv(T + d_head, (1, T, 2, d_head), dtype)

    def heads_major(x):
        return x.transpose(0, 2, 1, 3)

    def f_rule(q, k, v):
        return (_flash(q, k, v, causal, None, True).astype(jnp.float32) ** 2).sum()

    def f_ref(q, k, v):
        return (_reference_f32(*map(heads_major, (q, k, v)), causal=causal) ** 2).sum()

    g_rule = jax.grad(f_rule, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(*(x.astype(jnp.float32) for x in (q, k, v)))
    for gf, gr, name in zip(g_rule, g_ref, "qkv"):
        assert gf.dtype == dtype and gf.shape == gr.shape
        _assert_flash_close(gf, gr, dtype, 5e-4, err_msg=f"d{name}")


def test_flash_attention_blocks_chosen_from_t():
    # no block size named: the kernels' best, cut to what tiles T
    from ray_tpu.ops.flash_attention import _blocks

    assert _blocks(1024, None, None) == (1024, 1024)
    assert _blocks(1280, None, None) == (256, 256)
    assert _blocks(4096, 512, None) == (512, 1024)
    assert _blocks(192, None, None) == (192, 192)
    with pytest.raises(ValueError):
        _blocks(1024, 384, None)
    _flash_grads_vs_reference((1, 1, 256, 64), jnp.bfloat16, causal=True,
                              block_q=None, block_k=None, seed=2,
                              loss=lambda o: o.sum())


def _walk_eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs in its parameters."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (tuple, list)) else (value,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _walk_eqns(sub)


def test_flash_kernels_multiply_bf16_operands_as_stored():
    """The mechanism of PR 31 engaged: given bfloat16, no product inside the
    three kernels runs on float32 operands (a `.astype(float32)` before a
    `dot` costs the MXU its bf16 rate), and each accumulates in float32."""
    from ray_tpu.ops.flash_attention import flash_attention

    q, k, v = _flash_qkv(0, (1, 1, 256, 64), jnp.bfloat16)

    def loss(q, k, v):
        out = flash_attention(q, k, v, True, None, 128, 128, True)
        return out.astype(jnp.float32).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v).jaxpr
    dots = {}
    for eqn in _walk_eqns(jaxpr):
        if eqn.primitive.name == "pallas_call":
            dots[eqn.params["name"]] = [
                (tuple(str(x.aval.dtype) for x in d.invars),
                 str(d.outvars[0].aval.dtype))
                for d in _walk_eqns(eqn.params["jaxpr"])
                if d.primitive.name == "dot_general"]
    # forward: q k^T, p v; dk/dv: q k^T, p^T dO, dO v^T, ds^T q; dq: q k^T,
    # dO v^T, ds k -- each traced twice, for a block the diagonal crosses and
    # for a block under it
    assert {n: len(d) for n, d in dots.items()} == {
        "flash_fwd": 4, "flash_bwd_dkv": 8, "flash_bwd_dq": 6}
    for name, found in dots.items():
        for operands, result in found:
            assert operands == ("bfloat16", "bfloat16"), (name, operands)
            assert result == "float32", (name, result)


def _model_layout_qkv(dtype):
    """[B, T, H, D] operands of the dispatcher's `_flash` and the cotangent."""
    B, T, H, D = 2, 256, 2, 64
    return [jax.random.normal(k, (B, T, H, D), jnp.float32).astype(dtype)
            for k in jax.random.split(jax.random.PRNGKey(7), 4)]


@_DTYPES
@pytest.mark.parametrize("causal", [True, False])
def test_flash_rule_in_the_models_layout_equals_flash_attention(causal, dtype):
    """`ops.attention._flash` is the kernels' differentiation boundary on
    [B, T, H, D]: the same three launches as `flash_attention`'s own rule on
    the transposed operands, so output and gradients are equal bit for bit,
    differentiated or not."""
    import sys

    from ray_tpu.ops.flash_attention import flash_attention

    _flash = sys.modules["ray_tpu.ops.attention"]._flash
    q, k, v, g = _model_layout_qkv(dtype)

    def in_heads_major(q, k, v):
        qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
        return flash_attention(qt, kt, vt, causal, None, None, None, True).transpose(0, 2, 1, 3)

    def rule(q, k, v):
        return _flash(q, k, v, causal, None, True)

    want, want_vjp = jax.vjp(in_heads_major, q, k, v)
    got, got_vjp = jax.vjp(rule, q, k, v)
    assert got.dtype == dtype and got.shape == q.shape
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
    np.testing.assert_array_equal(np.asarray(rule(q, k, v), np.float32),
                                  np.asarray(want, np.float32))
    for a, b, name in zip(got_vjp(g), want_vjp(g), "qkv"):
        assert a.dtype == dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32),
                                      err_msg=f"d{name}")


def test_flash_rule_names_what_the_backward_keeps_lane_dense():
    """The forward rule names the output as [B, T, H * D] and the log-sum-exp
    as the rows [B, H, 1, T] the kernel writes (PR 58): what a layer's
    checkpoint keeps, in shapes whose last dimension fills a 128-lane tile."""
    import sys

    _flash = sys.modules["ray_tpu.ops.attention"]._flash
    q, k, v, _ = _model_layout_qkv(jnp.bfloat16)
    B, T, H, D = q.shape
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: _flash(q, k, v, True, None, True).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)))(q, k, v).jaxpr
    named = {e.params["name"]: e.outvars[0].aval for e in _walk_eqns(jaxpr)
             if e.primitive.name == "name"}
    assert set(named) == set(ops.FLASH_KEPT) == {"flash_out", "flash_lse"}
    assert (named["flash_out"].shape, str(named["flash_out"].dtype)) == (
        (B, T, H * D), "bfloat16")
    assert (named["flash_lse"].shape, str(named["flash_lse"].dtype)) == ((B, H, 1, T), "float32")
    assert sorted(e.params["name"] for e in _walk_eqns(jaxpr)
                  if e.primitive.name == "pallas_call") == [
        "flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]


def test_attention_dispatcher_gqa():
    B, T, H, Hkv, D = 2, 32, 8, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, T, H, D))
    k = jax.random.normal(ks[1], (B, T, Hkv, D))
    v = jax.random.normal(ks[2], (B, T, Hkv, D))
    out = ops.attention(q, k, v, causal=True)
    # manual GQA reference
    kr = jnp.repeat(k, H // Hkv, axis=2)
    vr = jnp.repeat(v, H // Hkv, axis=2)
    from ray_tpu.parallel import reference_attention

    ref = reference_attention(q, kr, vr, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_moe_routing_full_capacity_identity():
    # with generous capacity and k=1, each token goes to its argmax expert
    N, E, D = 16, 4, 8
    logits = jax.random.normal(jax.random.PRNGKey(0), (N, E)) * 5
    routing = ops.topk_routing(logits, num_experts=E, k=1, capacity_factor=4.0)
    x = jax.random.normal(jax.random.PRNGKey(1), (N, D))

    def expert_fn(params, xe):
        return xe * params  # scale by expert-specific constant

    params = jnp.arange(1.0, E + 1.0)[:, None, None]  # broadcastable [E,1,1]
    y = ops.moe_apply(x, routing, expert_fn, params)
    top1 = np.argmax(np.asarray(logits), -1)
    expected = np.asarray(x) * (top1[:, None] + 1.0)
    np.testing.assert_allclose(np.asarray(y), expected, atol=1e-5)


def test_moe_capacity_drops():
    # all tokens prefer expert 0; capacity forces drops → combine weight 0
    N, E = 8, 4
    logits = jnp.tile(jnp.array([[10.0, 0.0, 0.0, 0.0]]), (N, 1))
    routing = ops.topk_routing(logits, num_experts=E, k=1, capacity_factor=1.0)
    # capacity = ceil(1*8/4*1.0) = 2 → only 2 tokens kept
    kept = np.asarray(routing.combine.sum(axis=(1, 2)))
    assert (kept > 0.5).sum() == 2
    assert routing.aux_loss > 1.0  # heavily imbalanced → large aux loss
