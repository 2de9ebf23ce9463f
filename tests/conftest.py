"""Test harness: force JAX onto a virtual 8-device CPU mesh BEFORE jax import.

(reference test strategy: SURVEY.md §4 — accelerators are tested by env
simulation without hardware; multi-chip sharding is validated on a virtual
device mesh the same way the driver's dryrun does.)
"""

import os

# hard-set: tests never use a chip, whatever platform the host env presets
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("XLA_PYTHON_CLIENT_PREALLOCATE", "false")

import faulthandler  # noqa: E402
import signal  # noqa: E402

# debugging aid: `kill -USR1 <pytest pid>` dumps all thread stacks
faulthandler.register(signal.SIGUSR1, all_threads=True)

import pytest  # noqa: E402

# Modules dominated by multi-process orchestration / sleeps; marked slow so a
# driver-timeout-bounded run can use `-m "not slow"` or shard (SURVEY §4.2:
# the reference shards its suite via bazel size/shard_count).
_SLOW_MODULES = {
    "test_multihost", "test_chaos", "test_gcs_fault_tolerance", "test_tune",
    "test_tune_search_elastic", "test_serve_streaming", "test_rllib",
    "test_rllib_dqn", "test_train", "test_data_shuffle", "test_spilling",
    "test_object_lifecycle", "test_autoscaler",
}


def pytest_addoption(parser):
    parser.addoption(
        "--shard", default=None,
        help="i/n: run only the i-th of n deterministic test-file shards")


def pytest_collection_modifyitems(config, items):
    for item in items:
        mod = item.module.__name__.rsplit(".", 1)[-1]
        if mod in _SLOW_MODULES:
            item.add_marker(pytest.mark.slow)
    shard = config.getoption("--shard") or os.environ.get("RAY_TPU_TEST_SHARD")
    if shard:
        idx, n = (int(x) for x in shard.split("/"))
        import zlib

        keep = [it for it in items
                if zlib.crc32(it.module.__name__.encode()) % n == idx]
        deselect = [it for it in items
                    if zlib.crc32(it.module.__name__.encode()) % n != idx]
        config.hook.pytest_deselected(items=deselect)
        items[:] = keep


@pytest.fixture
def ray_start_local():
    import ray_tpu

    ray_tpu.shutdown()
    ray_tpu.init(local_mode=True)
    yield
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_regular():
    """A real multiprocess session with a small worker pool."""
    import ray_tpu

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, num_workers=2, max_workers=4)
    yield
    ray_tpu.shutdown()


@pytest.fixture
def flash_interpreted(monkeypatch):
    """The continuation's flash launch at a test model's tiny shapes, in
    interpret mode: blocks of 16 or 8 whatever the head size, for the calls of
    `prefill_with_prefix` that pass `kernel=True`. Yields the `window` of
    every launch traced."""
    from ray_tpu import ops
    from ray_tpu.models import decoding_paged as dp

    real, windows = ops.flash_prefix_attention, []

    def launch(*a, window, **kw):
        windows.append(window)
        return real(*a, window=window, interpret=True, **kw)

    def blocks(chunk, span, head_dim):
        fit = [next((b for b in (16, 8) if n % b == 0), None) for n in (chunk, span, chunk)]
        return None if None in fit else tuple(fit)

    monkeypatch.setattr(ops, "prefix_blocks", blocks)
    monkeypatch.setattr(ops, "flash_prefix_attention", launch)
    dp.prefill_with_prefix.clear_cache()   # traced anew: the launches are this test's
    yield windows
    dp.prefill_with_prefix.clear_cache()


@pytest.fixture
def continuations_as_on_chip(monkeypatch):
    """An engine on the CPU chooses a continuation's form as one that sees an
    unsharded TPU does (its decode step keeps the reference)."""
    from ray_tpu.models import decoding_paged as dp

    real = dp.continuation_blocks
    monkeypatch.setattr(dp, "continuation_blocks",
                        lambda cfg, chunk, span, kernel: real(cfg, chunk, span, True))
    dp.prefill_with_prefix.clear_cache()
    yield
    dp.prefill_with_prefix.clear_cache()
