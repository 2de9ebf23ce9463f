"""Async actors and concurrency groups.

(reference capability: async actors on fibers — core_worker
task_execution/fiber.h; concurrency groups — concurrency_group_manager.h;
@ray.method — python/ray/actor.py.)
"""

from __future__ import annotations

import os
import time

import pytest

import ray_tpu


@pytest.fixture(scope="module")
def session():
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, num_workers=1, max_workers=8)
    yield
    ray_tpu.shutdown()


def test_async_actor_methods_interleave(session):
    @ray_tpu.remote(max_concurrency=8)
    class AsyncActor:
        async def slow(self, t):
            import asyncio

            await asyncio.sleep(t)
            return t

        async def fast(self):
            return "fast"

    a = AsyncActor.remote()
    t0 = time.monotonic()
    refs = [a.slow.remote(0.5) for _ in range(6)]
    assert ray_tpu.get(a.fast.remote(), timeout=30) == "fast"
    assert ray_tpu.get(refs, timeout=30) == [0.5] * 6
    elapsed = time.monotonic() - t0
    # 6 x 0.5s sleeps overlapped on one event loop: far below serial 3s
    assert elapsed < 2.5, f"async methods did not interleave ({elapsed:.2f}s)"


def test_async_actor_state_is_shared(session):
    @ray_tpu.remote(max_concurrency=4)
    class Counter:
        def __init__(self):
            self.n = 0

        async def incr(self):
            self.n += 1
            return self.n

        async def total(self):
            return self.n

    c = Counter.remote()
    ray_tpu.get([c.incr.remote() for _ in range(10)], timeout=30)
    assert ray_tpu.get(c.total.remote(), timeout=30) == 10


def test_concurrency_groups_isolate_pools(session, tmp_path):
    """Events, no durations: both io tasks are running at once (each says so
    and neither can end before the gate opens), the other groups answer
    while the io pool is full, and the io tasks end when they are let."""
    @ray_tpu.remote(concurrency_groups={"io": 2, "compute": 1})
    class Grouped:
        @ray_tpu.method(concurrency_group="io")
        def io_task(self, started, gate):
            open(started, "w").close()
            deadline = time.monotonic() + 60.0
            while not os.path.exists(gate) and time.monotonic() < deadline:
                time.sleep(0.01)
            return "io"

        @ray_tpu.method(concurrency_group="compute")
        def compute_task(self):
            return "compute"

        def default_task(self):
            return "default"

    g = Grouped.remote()
    gate = str(tmp_path / "gate")
    started = [str(tmp_path / f"started{i}") for i in range(2)]
    io_refs = [g.io_task.remote(s, gate) for s in started]
    deadline = time.monotonic() + 60.0
    while not all(os.path.exists(s) for s in started):  # the io pool is 2 wide
        assert time.monotonic() < deadline, "io group did not run 2-wide"
        time.sleep(0.01)
    # compute + default groups are NOT blocked behind the held io tasks
    assert ray_tpu.get(g.compute_task.remote(), timeout=30) == "compute"
    assert ray_tpu.get(g.default_task.remote(), timeout=30) == "default"
    ready, _ = ray_tpu.wait(io_refs, num_returns=2, timeout=0)
    assert not ready, "an io task ended before the gate opened"
    open(gate, "w").close()
    assert ray_tpu.get(io_refs, timeout=30) == ["io", "io"]


def test_async_actor_error_propagates(session):
    @ray_tpu.remote(max_concurrency=2)
    class Boom:
        async def fail(self):
            raise ValueError("async-kaboom")

    b = Boom.remote()
    with pytest.raises(Exception, match="async-kaboom"):
        ray_tpu.get(b.fail.remote(), timeout=30)
