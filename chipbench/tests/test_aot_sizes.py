"""Each cell's step compiled for a described v5e (no chip attached): it has
to fit, and the bytes the configuration files record have to be what the
compiler says. One file, topology in a fixture (one process may load libtpu)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from chipbench import harness, program

USABLE = 15.49e9  # 15.75 GB of HBM less 0.26 GB the runtime reserves


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(autouse=True)
def flash_as_on_the_chip(monkeypatch):
    """`ops.attention._flash_ok` asks jax.default_backend(), which is the CPU
    here: steer it in the test, as the kernel runs in the step on the chip."""
    import sys

    import ray_tpu.ops.attention  # noqa: F401
    monkeypatch.setattr(sys.modules["ray_tpu.ops.attention"], "_flash_ok",
                        lambda q: q.shape[1] % 256 == 0 and q.shape[1] >= 1024)


def _total(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def _abstract(tree, shardings):
    return jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                        tree, shardings)


@pytest.mark.parametrize("workload", [w["name"] for w in harness.load_json(
    harness.ROOT, "BENCHMARK.json")["workloads"] if "train" in w["traffic"]])
def test_train_step_fits_and_matches_the_file(topo, workload):
    import optax

    from ray_tpu.models import transformer
    from ray_tpu.parallel import DEFAULT_RULES, MeshSpec, param_shardings
    from ray_tpu.train.spmd import make_train_step

    cell = harness.resolve_cell(workload)
    conf, mix = cell["config_file"], cell["traffic_file"]
    cfg = program.transformer_config(conf["program"])
    mesh = MeshSpec(**conf["mesh"]).build(list(topo.devices[:cell["chips"]]))
    axes, opt = transformer.logical_axes(cfg), optax.adamw(mix["lr"])
    step, _, batch_sharding = make_train_step(
        lambda p, t: transformer.loss_fn(p, t, cfg), axes, mesh, opt)
    p_sh = param_shardings(mesh, axes, DEFAULT_RULES)
    params = _abstract(jax.eval_shape(lambda k: transformer.init(k, cfg),
                                      jax.random.PRNGKey(0)), p_sh)
    o_shape = jax.eval_shape(opt.init, params)
    o_sh = optax.tree_map_params(opt, lambda _, s: s, o_shape, p_sh,
                                 transform_non_params=lambda _: NamedSharding(mesh, P()))
    batch = jax.ShapeDtypeStruct((mix["batch"], mix["seq"] + 1), jnp.int32,
                                 sharding=batch_sharding)
    compiled = step.lower(params, _abstract(o_shape, o_sh), batch).compile()
    assert "tpu_custom_call" in compiled.as_text()
    total = _total(compiled)
    assert total < USABLE
    recorded = conf["aot"]["step_b4_s1024_bytes"]
    assert total == pytest.approx(recorded, rel=0.01)


def test_mixtral_decode_step_fits_beside_the_weights(topo):
    from ray_tpu.models import decoding_paged as dp
    from ray_tpu.models import transformer

    conf = harness.resolve_cell("mixtral-8x7b.chat-steady")["config_file"]
    cfg, eng = program.transformer_config(conf["program"]), conf["engine"]
    one = SingleDeviceSharding(topo.devices[0])
    ab = lambda t: jax.tree.map(  # noqa: E731
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), t)
    params = ab(jax.eval_shape(lambda k: transformer.init(k, cfg), jax.random.PRNGKey(0)))
    weights = sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert weights == conf["aot"]["weights_bytes"]
    state = ab(jax.eval_shape(lambda: dp.init_paged_state(
        cfg, eng["max_slots"], eng["max_len"], eng["num_pages"], eng["page_size"])))
    compiled = dp.decode_step_paged_ragged.lower(params, state, cfg, 32, True).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _total(compiled) < USABLE


def test_the_controls_rounding_fits_in_place_beside_the_weights(topo):
    """check.coarse(params, donate=True) on the served weights: float8
    conversion compiles for the chip and needs no second copy."""
    from chipbench import check
    from ray_tpu.models import transformer

    conf = harness.resolve_cell("mixtral-8x7b.chat-steady")["config_file"]
    cfg = program.transformer_config(conf["program"])
    one = SingleDeviceSharding(topo.devices[0])
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
        jax.eval_shape(lambda k: transformer.init(k, cfg), jax.random.PRNGKey(0)))
    compiled = check.coarse_program(True).lower(params).compile()
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes == conf["aot"]["weights_bytes"]
    assert m.temp_size_in_bytes < 1e9
