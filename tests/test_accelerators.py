"""Chip-granular TPU scheduling: per-worker visible-chips isolation.

(reference test strategy: python/ray/tests/accelerators/test_tpu.py — TPU
topologies are env-simulated, no hardware needed; here RAY_TPU_CHIPS fakes a
4-chip host. A chip worker's env pins the TPU platform, so these tests read
the binding from the environment and never touch jax inside one.)
"""

from __future__ import annotations

import os

import pytest

import ray_tpu
from ray_tpu._private import accelerators


@pytest.fixture
def tpu4_session(monkeypatch):
    monkeypatch.setenv("RAY_TPU_CHIPS", "4")
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8, num_tpus=4, num_workers=0, max_workers=8)
    yield
    ray_tpu.shutdown()


def _visible_chips():
    raw = os.environ.get("TPU_VISIBLE_CHIPS", "")
    return sorted(int(c) for c in raw.split(",") if c != "")


@ray_tpu.remote(num_tpus=1)
class ChipActor:
    def chips(self):
        return _visible_chips()


def test_one_chip_actors_get_disjoint_chips(tpu4_session):
    actors = [ChipActor.remote() for _ in range(4)]
    seen = ray_tpu.get([a.chips.remote() for a in actors])
    assert all(len(c) == 1 for c in seen), seen
    assert sorted(c[0] for c in seen) == [0, 1, 2, 3]
    for a in actors:
        ray_tpu.kill(a)


def test_task_gets_multiple_chips(tpu4_session):
    @ray_tpu.remote(num_tpus=2)
    def chips():
        import os
        return sorted(int(c) for c in os.environ.get("TPU_VISIBLE_CHIPS", "").split(",") if c)

    got = ray_tpu.get(chips.remote())
    assert len(got) == 2
    assert set(got) <= {0, 1, 2, 3}


def test_chips_released_on_actor_death(tpu4_session):
    # Saturate the chip pool, kill one holder: its chip must come back and
    # satisfy a new 1-chip actor.
    actors = [ChipActor.remote() for _ in range(4)]
    first = ray_tpu.get([a.chips.remote() for a in actors])
    ray_tpu.kill(actors[0])
    fresh = ChipActor.remote()
    chips = ray_tpu.get(fresh.chips.remote(), timeout=60.0)
    assert chips == first[0]  # the freed chip, rebound
    for a in actors[1:] + [fresh]:
        ray_tpu.kill(a)


def test_idle_chip_workers_reclaimed_for_bigger_demand(tpu4_session):
    # A finished 1-chip task leaves an idle 1-chip worker; a 4-chip actor
    # needs the whole pool, so the idle binding must be reclaimed.
    @ray_tpu.remote(num_tpus=1)
    def one():
        import os
        return sorted(int(c) for c in os.environ.get("TPU_VISIBLE_CHIPS", "").split(",") if c)

    assert len(ray_tpu.get(one.remote())) == 1

    big = ChipActor.options(num_tpus=4).remote()
    chips = ray_tpu.get(big.chips.remote(), timeout=60.0)
    assert chips == [0, 1, 2, 3]
    ray_tpu.kill(big)


def test_cpu_tasks_keep_running_alongside_chip_tasks(tpu4_session):
    @ray_tpu.remote
    def cpu_only():
        import os
        return sorted(int(c) for c in os.environ.get("TPU_VISIBLE_CHIPS", "").split(",") if c)

    assert ray_tpu.get(cpu_only.remote()) == []


@pytest.mark.parametrize("num_tpus", [0.5, 1.5])
def test_num_tpus_must_be_whole_chips(num_tpus):
    # a fraction of a chip would bind none and compute on the host CPU
    # while its resources say "TPU": rejected at the API
    with pytest.raises(ValueError, match="whole number of chips"):
        @ray_tpu.remote(num_tpus=num_tpus)
        def bad():
            pass


def test_tpu_labels_and_head_resource(monkeypatch):
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5e-8")
    monkeypatch.setenv("TPU_TOPOLOGY", "2x4")
    monkeypatch.setenv("TPU_NAME", "slice-a")
    monkeypatch.setenv("TPU_WORKER_ID", "0")
    labels = accelerators.detect_tpu_labels()
    assert labels["ray_tpu.io/accelerator-type"] == "v5e-8"
    assert labels["ray_tpu.io/tpu-topology"] == "2x4"
    assert labels["ray_tpu.io/tpu-pod-name"] == "slice-a"
    assert accelerators.head_resources() == {"TPU-v5e-8-head": 1.0}
    # non-head workers contribute no head resource
    monkeypatch.setenv("TPU_WORKER_ID", "1")
    assert accelerators.head_resources() == {}


def test_pod_utilities(monkeypatch):
    from ray_tpu.util.accelerators import tpu

    monkeypatch.setenv("TPU_NAME", "slice-b")
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "h0,h1,h2,h3")
    monkeypatch.setenv("RAY_TPU_CHIPS", "4")
    assert tpu.get_current_pod_name() == "slice-b"
    assert tpu.get_current_pod_worker_count() == 4
    assert tpu.get_num_tpu_chips_on_node() == 4
    assert tpu.slice_head_resource("v5e-8") == "TPU-v5e-8-head"


# ---- what a worker's spawn env says about the chip (no hardware needed) ----


@pytest.mark.parametrize("chips,bounds", [((2,), "1,1,1"), ((0, 1, 2, 3), "2,2,1")])
def test_chip_worker_env(monkeypatch, chips, bounds):
    monkeypatch.delenv(accelerators.NOSET_VISIBLE_CHIPS_ENV, raising=False)
    env = {"JAX_PLATFORMS": "tpu,cpu"}  # as a TPU host presets it
    accelerators.apply_chip_env(env, chips)
    ids = ",".join(map(str, chips))
    # pinned: a chip worker that cannot reach its chip dies at backend init,
    # it does not carry on on the CPU backend
    assert env["JAX_PLATFORMS"] == "tpu"
    assert env["TPU_VISIBLE_CHIPS"] == env["RAY_TPU_WORKER_CHIPS"] == ids
    # a sub-host process describes its own share of the host to libtpu
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == bounds
    assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
    assert env["JAX_COMPILATION_CACHE_DIR"] == accelerators.DEFAULT_COMPILE_CACHE_DIR


def test_compile_cache_is_placed_from_outside_when_set():
    env = {"JAX_COMPILATION_CACHE_DIR": "/some/dir"}
    accelerators.apply_chip_env(env, (0,))
    assert env["JAX_COMPILATION_CACHE_DIR"] == "/some/dir"
    # the default is one fixed path in the checkout: the path is part of
    # the cache key, so no tempdir, pid or clock may enter it
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert accelerators.DEFAULT_COMPILE_CACHE_DIR == os.path.join(repo, ".jax_cache")


def test_worker_without_a_chip_is_held_to_the_cpu():
    env = {"JAX_PLATFORMS": "tpu,cpu"}
    accelerators.apply_host_env(env)
    assert env["JAX_PLATFORMS"] == "cpu"


@pytest.mark.parametrize("listing,chips", [
    (["/dev/vfio/0", "/dev/vfio/vfio"], 1),  # the v5e machine: one group + the control node
    (["/dev/vfio/0", "/dev/vfio/1", "/dev/vfio/2", "/dev/vfio/3",
      "/dev/vfio/vfio", "/dev/vfio/devices"], 4),
    (["/dev/accel0", "/dev/accel1", "/dev/accel2", "/dev/accel3"], 4),
    ([], 0),
])
def test_chip_count_ignores_the_vfio_control_node(listing, chips):
    assert accelerators.count_chip_nodes(listing) == chips


def test_tpu_llm_config_resolves_to_a_chip_requesting_deployment():
    from ray_tpu.llm import LLMConfig, build_openai_app

    def actor_options(cfg):
        return build_openai_app(cfg).deployment.config.ray_actor_options

    # accelerator_type defaults to "TPU": one chip unless told how many
    assert actor_options(LLMConfig())["num_tpus"] == 1
    four = LLMConfig(deployment_config={"ray_actor_options": {"num_tpus": 4}})
    assert actor_options(four)["num_tpus"] == 4
    assert "num_tpus" not in actor_options(LLMConfig(accelerator_type=None))
    with pytest.raises(ValueError, match="needs a chip"):
        build_openai_app(LLMConfig(
            deployment_config={"ray_actor_options": {"num_tpus": 0}}))
    with pytest.raises(ValueError, match="'TPU' or None"):
        build_openai_app(LLMConfig(accelerator_type="GPU"))


def test_tpu_llm_config_refuses_to_serve_from_the_cpu():
    from ray_tpu.llm import LLMConfig, TPUEngine

    with pytest.raises(RuntimeError, match="bound no chip"):
        TPUEngine.from_config(LLMConfig())
