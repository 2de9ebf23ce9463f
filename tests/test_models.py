"""Model family tests: shapes, grads, determinism, sharded equivalence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.models import gpt2_config, llama_config, mixtral_config, transformer, vit, vit_config
from ray_tpu.parallel import MeshSpec, param_shardings, shard_map
from tests.test_ops import _walk_eqns


def tiny_gpt2():
    return gpt2_config("124m", vocab_size=128, max_seq_len=64,
                       d_model=64, n_layers=2, n_heads=4, d_ff=128, dtype=jnp.float32)


def tiny_llama():
    return llama_config("tiny", vocab_size=128, max_seq_len=64,
                        d_model=64, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=96,
                        dtype=jnp.float32)


def tiny_mixtral():
    return mixtral_config("tiny", vocab_size=128, max_seq_len=64,
                          d_model=64, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=96,
                          num_experts=4, top_k=2, dtype=jnp.float32)


@pytest.mark.parametrize("cfg_fn", [tiny_gpt2, tiny_llama, tiny_mixtral])
def test_forward_and_loss(cfg_fn):
    cfg = cfg_fn()
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    logits, aux = transformer.forward(params, tokens, cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)
    loss = transformer.loss_fn(params, tokens, cfg)
    assert np.isfinite(float(loss))
    # grads flow to every leaf
    grads = jax.grad(transformer.loss_fn)(params, tokens, cfg)
    norms = [float(jnp.abs(g).sum()) for g in jax.tree.leaves(grads)]
    assert all(np.isfinite(n) for n in norms)
    assert sum(1 for n in norms if n > 0) >= len(norms) - 2  # biases may be 0-grad at init


def test_logical_axes_tree_matches_params():
    for cfg in (tiny_gpt2(), tiny_llama(), tiny_mixtral()):
        params = transformer.init(jax.random.PRNGKey(0), cfg)
        axes = transformer.logical_axes(cfg)
        p_struct = jax.tree.structure(params)
        a_struct = jax.tree.structure(axes, is_leaf=lambda x: isinstance(x, tuple))
        assert p_struct == a_struct
        # rank of every logical tuple matches param rank
        flat_p = jax.tree.leaves(params)
        flat_a = jax.tree.leaves(axes, is_leaf=lambda x: isinstance(x, tuple))
        for p, a in zip(flat_p, flat_a):
            assert p.ndim == len(a), f"{p.shape} vs {a}"


def test_sharded_forward_matches_single_device():
    cfg = tiny_llama()
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab_size)
    expected = transformer.loss_fn(params, tokens, cfg)

    mesh = MeshSpec(dp=2, fsdp=2, tp=2).build()
    shardings = param_shardings(mesh, transformer.logical_axes(cfg))
    sharded_params = jax.device_put(params, shardings)
    tok_sharding = NamedSharding(mesh, P(("dp", "fsdp")))
    sharded_tokens = jax.device_put(tokens, tok_sharding)
    loss = jax.jit(lambda p, t: transformer.loss_fn(p, t, cfg))(sharded_params, sharded_tokens)
    np.testing.assert_allclose(float(loss), float(expected), rtol=2e-5)


def test_vit_forward_and_grad():
    cfg = vit_config("s16", image_size=32, patch_size=8, num_classes=10,
                     d_model=64, n_layers=2, n_heads=4, d_ff=128, dtype=jnp.float32)
    params = vit.init(jax.random.PRNGKey(0), cfg)
    images = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
    logits = vit.forward(params, images, cfg)
    assert logits.shape == (2, 10)
    labels = jnp.array([1, 7])
    g = jax.grad(vit.loss_fn)(params, (images, labels), cfg)
    assert all(np.isfinite(float(jnp.abs(x).sum())) for x in jax.tree.leaves(g))
    # axes tree matches
    axes = vit.logical_axes(cfg)
    assert jax.tree.structure(params) == jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple))


def test_param_counts_sane():
    cfg = gpt2_config("124m")
    n = cfg.num_params()
    assert 120e6 < n < 130e6, n


@pytest.fixture
def flash_layers(monkeypatch):
    """A two-layer GPT-2 whose attention runs the flash kernels, interpreted
    (`attn_impl="flash"`, the dispatcher's `_flash` with its trailing flag
    set), as a TPU's layers do from 1,024 tokens up."""
    import sys

    attn = sys.modules["ray_tpu.ops.attention"]
    real = attn._flash
    monkeypatch.setattr(attn, "_flash", lambda q, k, v, causal, scale: real(
        q, k, v, causal, scale, True))
    cfg = gpt2_config("124m", vocab_size=256, max_seq_len=256, d_model=128,
                      n_layers=2, n_heads=2, d_ff=256, dtype=jnp.float32)
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 257), 0, cfg.vocab_size)
    return cfg, params, tokens[:, :-1].shape, lambda p, c=cfg: transformer.loss_fn(
        p, tokens, c, attn_impl="flash")


def test_layer_checkpoint_keeps_the_flash_output_and_runs_the_forward_kernel_once(
        flash_layers):
    """What the layer's checkpoint keeps: its carry and, of a layer whose
    attention ran the flash kernels, their output and log-sum-exp. The
    gradient program holds the forward kernel once (in the forward scan, which
    hands both stacks to the backward scan) and not again under remat."""
    cfg, params, (B, T), loss = flash_layers
    L, E, H = cfg.n_layers, cfg.d_model, cfg.n_heads
    jaxpr = jax.make_jaxpr(jax.grad(loss))(params).jaxpr
    calls = [e.params["name"] for e in _walk_eqns(jaxpr) if e.primitive.name == "pallas_call"]
    assert sorted(calls) == ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]
    forward, = [e for e in jaxpr.eqns if e.primitive.name == "scan" and any(
        i.primitive.name == "pallas_call" and i.params["name"] == "flash_fwd"
        for i in _walk_eqns(e.params["jaxpr"].jaxpr))]
    stacked = [(v.aval.shape, str(v.aval.dtype)) for v in forward.outvars]
    # the layers' carries and flash_out, lane-dense (H * D is E here), and
    # flash_lse, the kernel's rows: nothing else of a layer's size is handed to
    # the backward scan
    assert sorted(shape for shape, _ in stacked if len(shape) >= 4) == [
        (L, B, H, 1, T), (L, B, T, E), (L, B, T, E)]


def test_layer_checkpoint_gradients_equal_those_without_it(flash_layers):
    import dataclasses

    cfg, params, _, loss = flash_layers
    kept = jax.grad(loss)(params)
    plain = jax.grad(loss)(params, dataclasses.replace(cfg, remat=False))
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(kept), jax.tree.leaves(plain)):
        scale = float(jnp.abs(b).max()) or 1.0
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5 * scale, rtol=1e-5,
                                   err_msg=jax.tree_util.keystr(path))
