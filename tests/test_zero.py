"""ZeRO-1 sharded optimizer update (train/zero.py) — both planes.

Acceptance contract (ISSUE 12): per-replica optimizer-state bytes drop
~W x with loss parity against the unsharded baseline over the same
batches, in the spmd/pjit plane (8-device virtual mesh) and the
host-collective plane (actor workers over the ring).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import ray_tpu
from ray_tpu.parallel import MeshSpec
from ray_tpu.train import zero
from ray_tpu.train.optim import adamw_int8, optimizer_state_bytes
from ray_tpu.train.spmd import init_sharded, make_train_step


# ------------------------------------------------------------- rules plane


def test_match_partition_rules_params_and_opt_state():
    params = {"layers": {"wq": jnp.zeros((4, 8)), "nw": jnp.ones((8,))},
              "head": jnp.zeros((8, 16)), "count": jnp.zeros(())}
    rules = [("layers/wq", P("dp", "tp")), ("head", P(None, "tp")),
             ("nw", P())]
    specs = zero.match_partition_rules(rules, params)
    assert specs["layers"]["wq"] == P("dp", "tp")
    assert specs["head"] == P(None, "tp")
    assert specs["count"] == P()  # scalars never partitioned
    # optax state paths embed the param names -> the same rules match
    opt = optax.adam(1e-3)
    state_shape = jax.eval_shape(opt.init, params)
    sspecs = zero.match_partition_rules(rules, state_shape, strict=False)
    mus = [s for s in jax.tree.leaves(
        sspecs, is_leaf=lambda x: isinstance(x, P)) if s == P("dp", "tp")]
    assert len(mus) == 2  # mu and nu of layers/wq both matched


def test_match_partition_rules_strict_raises():
    with pytest.raises(ValueError, match="no partition rule"):
        zero.match_partition_rules([("x", P())], {"y": jnp.zeros((4, 4))})


def test_zero_shard_spec_folds_dp_into_first_free_divisible_dim():
    mesh = MeshSpec(dp=4, tp=2).build()
    assert zero.zero_shard_spec(P(), (8, 6), mesh) == P("dp", None)
    assert zero.zero_shard_spec(P(None, "tp"), (8, 6), mesh) == P("dp", "tp")
    # first dim not divisible -> falls to the second
    assert zero.zero_shard_spec(P(), (6, 8), mesh) == P(None, "dp")
    # already dp-sharded or nothing divisible -> unchanged
    assert zero.zero_shard_spec(P("dp"), (8,), mesh) == P("dp")
    assert zero.zero_shard_spec(P(), (3, 5), mesh) == P()
    assert zero.zero_shard_spec(P(), (), mesh) == P()


# --------------------------------------------------------------- spmd plane


def _toy_problem():
    key = jax.random.PRNGKey(0)
    params = {"w": jax.random.normal(key, (64, 16)) * 0.1,
              "b": jnp.zeros((16,))}
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 64))
    y = jax.random.normal(jax.random.PRNGKey(2), (64, 16))

    def loss_fn(p, batch):
        xb, yb = batch
        return jnp.mean((jnp.tanh(xb @ p["w"]) + p["b"] - yb) ** 2)

    return params, (x, y), loss_fn


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs the 8-device virtual CPU mesh")
def test_spmd_zero_state_bytes_drop_w_times_with_loss_parity():
    W = 8
    mesh = MeshSpec(dp=W).build()
    params, batch, loss_fn = _toy_problem()
    rules = [("w", P()), ("b", P())]
    opt = optax.adamw(1e-2)

    # unsharded baseline over the same batches
    bstep = jax.jit(lambda p, s, b: _plain_step(loss_fn, opt, p, s, b))
    bp, bs = params, opt.init(params)
    for _ in range(10):
        bp, bs, bloss = bstep(bp, bs, batch)

    step, shard_params, batch_sharding = make_train_step(
        loss_fn, None, mesh, opt, partition_rules=rules,
        params_template=params, zero_axis="dp", donate=False)
    sp = shard_params(params)
    sstate = opt.init(sp)
    sbatch = jax.device_put(batch, batch_sharding)
    for _ in range(10):
        sp, sstate, sloss = step(sp, sstate, sbatch)

    # loss parity: same math, only sharded
    np.testing.assert_allclose(float(sloss), float(bloss), rtol=1e-4)
    # per-replica optimizer state drops ~W x (count scalar is replicated,
    # so slightly under exactly W)
    total = optimizer_state_bytes(sstate)
    per_device = zero.sharded_state_bytes(sstate)
    assert total / per_device > 0.9 * W
    # moments really carry the dp axis
    mu_w = sstate[0].mu["w"]
    assert "dp" in str(mu_w.sharding.spec)


def _plain_step(loss_fn, opt, p, s, b):
    loss, grads = jax.value_and_grad(loss_fn)(p, b)
    updates, s = opt.update(grads, s, p)
    return optax.apply_updates(p, updates), s, loss


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs the 8-device virtual CPU mesh")
def test_make_zero_train_step_init_opt_state_is_sharded():
    mesh = MeshSpec(dp=8).build()
    params, batch, loss_fn = _toy_problem()
    rules = [("w", P()), ("b", P())]
    opt = optax.adamw(1e-2)
    step, init_opt_state, shard_params, batch_sharding = \
        zero.make_zero_train_step(loss_fn, params, mesh, opt, rules,
                                  donate=False)
    sp = shard_params(params)
    state = init_opt_state(sp)  # initialized straight into its shards
    assert optimizer_state_bytes(state) / zero.sharded_state_bytes(state) > 7
    sp, state, loss = step(sp, state, jax.device_put(batch, batch_sharding))
    assert np.isfinite(float(loss))


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs the 8-device virtual CPU mesh")
def test_init_sharded_with_partition_rules():
    mesh = MeshSpec(dp=2, tp=4).build()

    def init_fn(key):
        return {"emb": jax.random.normal(key, (16, 8)),
                "head": jax.random.normal(key, (8, 16))}

    rules = [("emb", P(None, "tp")), ("head", P(None, "tp"))]
    params = init_sharded(init_fn, None, mesh, jax.random.PRNGKey(0),
                          partition_rules=rules)
    assert "tp" in str(params["emb"].sharding.spec)


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs the 8-device virtual CPU mesh")
def test_init_opt_state_follows_the_parameter_shardings():
    from ray_tpu.train.spmd import init_opt_state

    mesh = MeshSpec(fsdp=4, tp=2).build()
    params = init_sharded(
        lambda key: {"emb": jax.random.normal(key, (16, 8)),
                     "bias": jnp.zeros((8,))}, None, mesh,
        jax.random.PRNGKey(0),
        partition_rules=[("emb", P("fsdp", "tp")), ("bias", P())])
    adam = init_opt_state(optax.adamw(1e-3), params)[0]
    for moment in (adam.mu, adam.nu):
        assert moment["emb"].sharding == params["emb"].sharding
        assert len({s.device for s in moment["emb"].addressable_shards}) == 8
    assert adam.count.sharding.is_fully_replicated
    # the trap it exists for: a bare jit leaves every moment on one device
    bare = jax.jit(optax.adamw(1e-3).init)(params)[0]
    assert len(bare.mu["emb"].sharding.device_set) == 1


def test_make_train_step_zero_axis_requires_rules():
    mesh = MeshSpec(dp=1).build(jax.devices()[:1])
    params, batch, loss_fn = _toy_problem()
    with pytest.raises(ValueError, match="zero_axis needs partition_rules"):
        make_train_step(loss_fn, None, mesh, optax.adam(1e-3),
                        zero_axis="dp")
    with pytest.raises(ValueError, match="needs params_template"):
        make_train_step(loss_fn, None, mesh, optax.adam(1e-3),
                        partition_rules=[(".*", P())])


# --------------------------------------------------------- host-ring plane


@ray_tpu.remote
class ZeroWorker:
    def init_collective_group(self, world_size, rank, backend, group_name):
        from ray_tpu.util import collective as col

        col.init_collective_group(world_size, rank, backend=backend,
                                  group_name=group_name)
        self.rank = rank
        self.g = group_name

    def train(self, steps, opt_kind, grad_compression):
        params, x, loss_fn = _worker_problem(self.rank)
        opt = (adamw_int8(1e-2, weight_decay=0.01) if opt_kind == "int8"
               else optax.adamw(1e-2, weight_decay=0.01))
        zopt = zero.ZeroShardedOptimizer(
            opt, group_name=self.g, grad_compression=grad_compression)
        state = zopt.init(params)
        for _ in range(steps):
            loss, grads = jax.value_and_grad(loss_fn)(params, x)
            params, state = zopt.step(params, grads, state)
        return (float(loss), zopt.state_bytes(state),
                float(np.asarray(params["w"]).sum()),
                np.asarray(params["w"]))

    def opt_state_gauge(self):
        from ray_tpu.util import metrics as met

        snap = met.snapshot()
        rec = [m for m in snap
               if m["name"] == "ray_tpu_train_opt_state_bytes"]
        return rec[0]["series"] if rec else []


def _worker_problem(rank):
    key = jax.random.PRNGKey(0)
    params = {"w": jax.random.normal(key, (37, 19)) * 0.5,
              "b": jnp.zeros((19,))}
    x = jax.random.normal(jax.random.PRNGKey(10 + rank), (32, 37))

    def loss_fn(p, xb):
        return jnp.mean(jnp.tanh(xb @ p["w"] + p["b"]) ** 2)

    return params, x, loss_fn


def _baseline(steps, opt_fn, W=2):
    """Unsharded dp baseline: every rank updates with the mean gradient."""
    params, _, loss_fn = _worker_problem(0)
    xs = [_worker_problem(r)[1] for r in range(W)]
    opt = opt_fn()
    state = opt.init(params)
    for _ in range(steps):
        pairs = [jax.value_and_grad(loss_fn)(params, x) for x in xs]
        grads = jax.tree.map(lambda *g: sum(g) / W,
                             *[g for _, g in pairs])
        updates, state = opt.update(grads, state, params)
        params = optax.apply_updates(params, updates)
    return (float(pairs[0][0]), optimizer_state_bytes(state),
            np.asarray(params["w"]))


@pytest.fixture
def prim_cluster():
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=32, num_workers=2, max_workers=16)
    yield
    ray_tpu.shutdown()


def _run_group(steps, opt_kind, compression, name):
    ws = [ZeroWorker.remote() for _ in range(2)]
    from ray_tpu.util import collective as col_mod

    col_mod.create_collective_group(ws, 2, [0, 1], group_name=name)
    out = ray_tpu.get([w.train.remote(steps, opt_kind, compression)
                       for w in ws], timeout=300)
    return ws, out


def test_host_zero_exact_parity_fp32(prim_cluster):
    """f32 AdamW + uncompressed ring: the sharded update IS the baseline
    update, just partitioned — parity to float tolerance, state ~1/2."""
    ws, out = _run_group(8, "fp32", None, "zfp")
    base_loss, base_bytes, base_w = _baseline(
        8, lambda: optax.adamw(1e-2, weight_decay=0.01))
    (l0, bytes0, sum0, w0), (l1, bytes1, sum1, w1) = out
    np.testing.assert_array_equal(w0, w1)  # ranks stay in lockstep
    np.testing.assert_allclose(w0, base_w, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(l0, base_loss, rtol=1e-4)
    assert bytes0 < 0.62 * base_bytes  # ~W x drop (W=2, plus padding slack)


def test_host_zero_int8_grads_int8_state_loss_parity(prim_cluster):
    """The full composition: quantized (error-feedback) reduce-scatter
    feeding a dp-sharded int8-AdamW update — loss stays within tolerance
    of the unsharded exact-gradient baseline over the same batches."""
    ws, out = _run_group(12, "int8", "int8_block", "zq")
    base_loss, base_bytes, base_w = _baseline(
        12, lambda: adamw_int8(1e-2, weight_decay=0.01))
    (l0, bytes0, _, w0), (l1, bytes1, _, w1) = out
    np.testing.assert_array_equal(w0, w1)
    # loss parity, not weight parity: the sharded flat vector quantizes
    # int8 moments over different block boundaries than the per-leaf
    # baseline, so trajectories differ by quantization noise — but both
    # must land at the same loss
    np.testing.assert_allclose(l0, base_loss, rtol=0.1)
    assert bytes0 < 0.62 * base_bytes
    # the worker emitted its optimizer-state footprint as a gauge
    series = ray_tpu.get(ws[0].opt_state_gauge.remote())
    assert series and series[0][1] == bytes0
