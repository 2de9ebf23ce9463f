"""The plain references agree with the program at tiny widths on the CPU,
and the test that pins why mixtral-8x7b sets capacity_factor = E / k."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import program
from chipbench.reference import gpt2, mixtral
from ray_tpu.models import transformer

import tiny


def _params(cfg, seed, scale=1.0):
    p = program.init_params(cfg, seed)
    # biases and norms away from their zero/one initial values
    return jax.tree.map(lambda x: x * scale + 0.01 * jax.random.normal(
        jax.random.PRNGKey(7), x.shape, x.dtype), p)


def test_gpt2_reference_agrees_with_the_program():
    conf = tiny.gpt2_cell()["config_file"]
    cfg = program.transformer_config(conf["program"])
    p = _params(cfg, 2**31 + 5)
    tokens = np.random.default_rng(0).integers(0, 211, 33, dtype=np.int32)
    logits, _ = transformer.forward(p, tokens[None, :-1], cfg)
    want = gpt2.forward(p, tokens[:-1], conf["sizes"])
    assert float(jnp.abs(logits[0] - want).max()) < 1e-5
    loss = transformer.loss_fn(p, tokens[None], cfg)
    assert float(abs(loss - gpt2.loss(p, tokens, conf["sizes"], remat=True))) < 1e-5
    g = jax.grad(lambda q: transformer.loss_fn(q, tokens[None], cfg))(p)
    r = jax.grad(lambda q: gpt2.loss(q, tokens, conf["sizes"], remat=True))(p)
    for path in conf["check"]["grad_leaves"]:
        a, b = g, r
        for key in path.split("/"):
            a, b = a[key], b[key]
        assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < 1e-4, path


@pytest.mark.parametrize("capacity_factor,agrees", [(4.0, True), (1.25, False)])
def test_mixtral_reference_is_dropless(capacity_factor, agrees):
    """At capacity_factor = E / k = 4 the program drops nothing and agrees
    with the published routing; at its default 1.25 an expert that more than
    capacity tokens choose drops the rest, and it does not."""
    conf = tiny.mixtral_cell(capacity_factor)["config_file"]
    cfg = program.transformer_config(conf["program"])
    p = _params(cfg, 11, scale=20.0)  # sharp routers: experts overflow
    tokens = np.random.default_rng(1).integers(0, 300, 48, dtype=np.int32)
    logits, _ = transformer.forward(p, tokens[None], cfg)
    want, margin = mixtral.forward(p, jnp.asarray(tokens), conf["sizes"])
    err = float(jnp.abs(logits[0] - want).max() / jnp.abs(want).max())
    assert margin.shape == (2, 48, 2)    # to the first, the second expert left out
    assert (err < 1e-3) == agrees, err
