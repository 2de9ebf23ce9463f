"""Channel-backed compiled-DAG execution plane tests.

The compiled plane provisions one exec loop per actor over mutable-shm
channels; a step is one channel write + one read, no task submission
(reference: python/ray/dag/compiled_dag_node.py do_exec_tasks +
experimental channel tests). Covers: engagement + correctness, a step
that submits no task where the `.remote()` chain submits one a stage (what
the plane's lower step latency comes from; a latency is a chip-side or
operator's measurement, not a test's), fallback, error
propagation, oversized payloads, teardown with work in flight, actor death
mid-loop, and the /dev/shm leak check.
"""

import asyncio
import os
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.dag import channel_execution
from ray_tpu.exceptions import RayChannelError, RayTaskError

pytestmark = pytest.mark.dag

N_STAGES = 4


def _still_there(created: list) -> list:
    return [path for path in created if os.path.exists(path)]


@pytest.fixture
def dag_cluster(monkeypatch):
    """A session, and the channels its compiled DAGs created (the driver
    creates every edge's): the leak check is of those, by name. A glob of
    /dev/shm before and after would also count the channels that tests in
    other processes hold open meanwhile."""
    ray_tpu.shutdown()
    created, create = [], channel_execution.create_mutable_channel

    def recording(buffer_bytes):
        ch = create(buffer_bytes)
        created.append(ch.path)
        return ch

    monkeypatch.setattr(channel_execution, "create_mutable_channel", recording)
    ray_tpu.init(num_cpus=32, num_workers=2, max_workers=8)
    yield created
    ray_tpu.shutdown()
    leaked = _still_there(created)
    assert not leaked, f"/dev/shm channel leak: {leaked}"


@ray_tpu.remote
class Stage:
    def __init__(self, bias):
        self.bias = bias
        self.calls = 0

    def work(self, x):
        self.calls += 1
        return x + self.bias

    def boom(self, x):
        if x == 13:
            raise RuntimeError("unlucky step")
        return x * 2

    def big(self, x):
        return np.zeros(int(x), np.float64)

    def ncalls(self):
        return self.calls


def _pipeline(actors):
    from ray_tpu.dag import InputNode

    with InputNode() as inp:
        node = inp
        for a in actors:
            node = a.work.bind(node)
    return node


def test_channel_plane_engages_and_matches(dag_cluster):
    actors = [Stage.remote(10 ** i) for i in range(N_STAGES)]
    compiled = _pipeline(actors).experimental_compile()
    assert compiled.uses_channels, compiled.fallback_reason
    assert "plane: channels" in compiled.visualize()
    for i in range(25):
        assert compiled.execute(i).result(timeout=60) == i + 1111
    # ray_tpu.get() resolves channel futures too
    assert ray_tpu.get(compiled.execute(5), timeout=60) == 1116
    compiled.teardown()
    # loops are joined: the actors serve normal calls again, and each ran
    # exactly one method invocation per execute() (no speculative steps)
    assert ray_tpu.get(actors[0].ncalls.remote(), timeout=30) == 26


def test_channel_plane_beats_remote_chain(dag_cluster, monkeypatch):
    """What the compiled plane saves, counted and not timed: a step of the
    `.remote()` chain submits one actor task a stage through the driver's
    worker (and the GCS behind it); a step of the compiled plane submits
    none (one channel write, one read), and every stage still ran once a
    step."""
    from ray_tpu._private.api import _get_worker

    worker = _get_worker()
    submitted = []
    submit = worker.submit_actor_task

    def counting(actor_id, method_name, *a, **kw):
        submitted.append(method_name)
        return submit(actor_id, method_name, *a, **kw)

    monkeypatch.setattr(worker, "submit_actor_task", counting)
    actors = [Stage.remote(1) for _ in range(N_STAGES)]

    def chain_step(x):
        ref = x
        for a in actors:
            ref = a.work.remote(ref)
        return ray_tpu.get(ref, timeout=60)

    n = 30
    for i in range(n):
        assert chain_step(i) == i + N_STAGES
    assert submitted.count("work") == n * N_STAGES

    compiled = _pipeline(actors).experimental_compile()
    assert compiled.uses_channels, compiled.fallback_reason
    del submitted[:]
    for i in range(n):
        assert compiled.execute(i).result(timeout=60) == i + N_STAGES
    assert "work" not in submitted
    compiled.teardown()
    assert ray_tpu.get([a.ncalls.remote() for a in actors], timeout=30) == [2 * n] * N_STAGES


def test_function_node_falls_back(dag_cluster):
    from ray_tpu.dag import InputNode

    @ray_tpu.remote
    def add(a, b):
        return a + b

    a = Stage.remote(100)
    with InputNode() as inp:
        dag = a.work.bind(add.bind(inp, 1))
    compiled = dag.experimental_compile()
    assert not compiled.uses_channels
    assert "submit path" in compiled.fallback_reason
    assert "plane: submit" in compiled.visualize()
    assert ray_tpu.get(compiled.execute(5)) == 106
    compiled.teardown()


def test_multi_output_pipelining_and_await(dag_cluster):
    from ray_tpu.dag import InputNode, MultiOutputNode

    a, b = Stage.remote(1), Stage.remote(2)
    with InputNode() as inp:
        mid = a.work.bind(inp)
        dag = MultiOutputNode([mid, b.work.bind(mid)])
    compiled = dag.experimental_compile(max_inflight_executions=4)
    assert compiled.uses_channels, compiled.fallback_reason
    futs = [compiled.execute_async(i) for i in range(8)]
    assert [f.result(timeout=60) for f in futs] == [
        [i + 1, i + 3] for i in range(8)]
    assert futs[0].done()

    async def run():
        return await compiled.execute_async(41)

    assert asyncio.run(run()) == [42, 44]
    compiled.teardown()


def test_dagfuture_await_without_legacy_event_loop(dag_cluster):
    """DAGFuture.__await__ must use get_running_loop (3.12-safe)."""
    a = Stage.remote(1)

    @ray_tpu.remote
    def ident(x):
        return x

    from ray_tpu.dag import InputNode

    with InputNode() as inp:
        dag = a.work.bind(ident.bind(inp))
    compiled = dag.experimental_compile()
    assert not compiled.uses_channels  # fallback plane → DAGFuture

    async def run():
        return await compiled.execute_async(7)

    assert asyncio.run(run()) == 8
    compiled.teardown()


def test_error_propagates_and_pipeline_recovers(dag_cluster):
    from ray_tpu.dag import InputNode

    a, b = Stage.remote(0), Stage.remote(5)
    with InputNode() as inp:
        dag = b.work.bind(a.boom.bind(inp))
    compiled = dag.experimental_compile()
    assert compiled.uses_channels, compiled.fallback_reason
    assert compiled.execute(3).result(timeout=60) == 11
    with pytest.raises(RayTaskError) as ei:
        compiled.execute(13).result(timeout=60)
    # the faulting node is identified: method + actor
    assert "boom" in str(ei.value) and "unlucky step" in str(ei.value)
    # the plane survives a step error: next steps flow normally
    assert compiled.execute(4).result(timeout=60) == 13
    compiled.teardown()


def test_payload_exceeds_buffer(dag_cluster):
    from ray_tpu.dag import InputNode

    a, b = Stage.remote(0), Stage.remote(0)
    with InputNode() as inp:
        dag = b.work.bind(a.big.bind(inp))
    compiled = dag.experimental_compile(channel_buffer_bytes=8192)
    assert compiled.uses_channels, compiled.fallback_reason
    # intermediate exceeds buffer_bytes → clear in-band error...
    with pytest.raises(RayTaskError) as ei:
        compiled.execute(100_000).result(timeout=60)
    assert "exceed" in str(ei.value)
    # ...and the channel stays usable
    out = compiled.execute(16).result(timeout=60)
    assert out.shape == (16,)
    # oversized DRIVER INPUT is rejected before any channel write, so the
    # loops never desynchronize
    with pytest.raises(ValueError, match="exceed"):
        compiled.execute(np.zeros(100_000))
    assert compiled.execute(8).result(timeout=60).shape == (8,)
    compiled.teardown()


def test_teardown_with_execution_in_flight(dag_cluster):
    actors = [Stage.remote(1) for _ in range(N_STAGES)]
    compiled = _pipeline(actors).experimental_compile(
        max_inflight_executions=4)
    assert compiled.uses_channels, compiled.fallback_reason
    for i in range(3):
        compiled.execute(i)  # never drained
    compiled.teardown()  # must join loops and unlink despite inflight work
    assert dag_cluster and not _still_there(dag_cluster), (
        "teardown leaked /dev/shm channels")
    # idempotent + executes after teardown are refused
    compiled.teardown()
    with pytest.raises(Exception):
        compiled.execute(1)


def test_actor_death_mid_loop(dag_cluster):
    actors = [Stage.remote(1) for _ in range(2)]
    compiled = _pipeline(actors).experimental_compile()
    assert compiled.uses_channels, compiled.fallback_reason
    assert compiled.execute(1).result(timeout=60) == 3
    ray_tpu.kill(actors[1])
    with pytest.raises((RayChannelError, ray_tpu.exceptions.ActorDiedError)):
        for i in range(20):  # a step in the kill window may still complete
            compiled.execute(i).result(timeout=30)
    compiled.teardown()  # still clean: joins what it can, unlinks files
    assert dag_cluster and not _still_there(dag_cluster), (
        "teardown after actor death leaked channels")


def test_teardown_surfaces_inflight_errors(dag_cluster):
    """Satellite: teardown no longer swallows in-flight errors silently."""
    from ray_tpu.dag import InputNode

    @ray_tpu.remote
    def die(x):
        raise RuntimeError("inflight failure")

    with InputNode() as inp:
        dag = die.bind(inp)
    compiled = dag.experimental_compile()
    assert not compiled.uses_channels  # FunctionNode → submit plane
    compiled.execute(1)
    with pytest.raises(RayTaskError):
        compiled.teardown(raise_on_error=True)


def test_async_actor_methods_on_channel_plane(dag_cluster):
    """`async def` methods must resolve on the actor's event loop, not
    leak coroutine objects into the channels."""
    from ray_tpu.dag import InputNode

    @ray_tpu.remote
    class AsyncStage:
        async def work(self, x):
            await asyncio.sleep(0)
            return x + 100

    a = AsyncStage.remote()
    with InputNode() as inp:
        dag = a.work.bind(inp)
    compiled = dag.experimental_compile()
    assert compiled.uses_channels, compiled.fallback_reason
    assert [compiled.execute(i).result(timeout=60) for i in range(5)] == [
        i + 100 for i in range(5)]
    compiled.teardown()


def test_get_on_future_lists(dag_cluster):
    actors = [Stage.remote(1) for _ in range(2)]
    compiled = _pipeline(actors).experimental_compile()
    assert compiled.uses_channels, compiled.fallback_reason
    futs = [compiled.execute(i) for i in range(4)]
    # ray_tpu.wait() polls futures' done() (no ObjectRefs exist)
    ready, not_ready = ray_tpu.wait(futs, num_returns=4, timeout=30)
    assert len(ready) == 4 and not not_ready
    assert ray_tpu.get(futs, timeout=60) == [i + 2 for i in range(4)]
    # mixed future + ObjectRef lists resolve elementwise — but only after
    # teardown frees the actors' exec-loop slots for normal calls
    compiled.teardown()
    mixed = [actors[0].work.remote(10)]
    assert ray_tpu.get(mixed, timeout=60) == [11]


def test_unconsumed_results_are_bounded(dag_cluster):
    """Fire-and-forget executes must not grow driver memory unboundedly:
    drained rows whose future was dropped are evicted beyond the retention
    window — while rows with a live future are always kept."""
    actors = [Stage.remote(1) for _ in range(2)]
    compiled = _pipeline(actors).experimental_compile(
        max_inflight_executions=2)
    assert compiled.uses_channels, compiled.fallback_reason
    ex = compiled._channel
    early = compiled.execute(0)  # held future: must survive eviction
    for i in range(1, 100):
        compiled.execute(i)  # futures discarded immediately
    assert len(ex._results) <= ex._retain + 1  # +1: `early` is pinned
    assert ex._expired_below > 0  # dropped-future rows were evicted
    assert early.result(timeout=60) == 2
    # recent executions still resolve
    assert compiled.execute(7).result(timeout=60) == 9
    compiled.teardown()


def test_double_compile_same_actor_rejected(dag_cluster):
    """A second compiled DAG over a busy actor would queue its exec loop
    behind the first forever — reject at compile time, allow after
    teardown."""
    from ray_tpu.dag import InputNode

    a = Stage.remote(1)
    with InputNode() as inp:
        dag1 = a.work.bind(inp)
    c1 = dag1.experimental_compile()
    assert c1.uses_channels, c1.fallback_reason
    with InputNode() as inp:
        dag2 = a.work.bind(inp)
    with pytest.raises(ValueError, match="compiled DAG"):
        dag2.experimental_compile()
    c1.teardown()
    c2 = dag2.experimental_compile()  # actor released at teardown
    assert c2.uses_channels, c2.fallback_reason
    assert c2.execute(1).result(timeout=60) == 2
    c2.teardown()


def test_teardown_unblocks_stuck_result(dag_cluster):
    """teardown() must abort a result() blocked on a hung step (the
    blocked caller holds the executor lock — teardown must not need it)."""
    import threading

    from ray_tpu.dag import InputNode

    @ray_tpu.remote
    class Slow:
        def work(self, x):
            time.sleep(x)
            return x

    s = Slow.remote()
    with InputNode() as inp:
        dag = s.work.bind(inp)
    compiled = dag.experimental_compile()
    assert compiled.uses_channels, compiled.fallback_reason
    fut = compiled.execute(8)  # step hangs ~8s
    errs = []
    t = threading.Thread(
        target=lambda: errs.append(_expect_raises(fut)), daemon=True)
    t.start()
    time.sleep(0.5)  # let result() block inside the executor lock
    compiled.teardown()  # must not deadlock on the executor lock
    t.join(timeout=15)
    assert not t.is_alive(), "result() never unblocked after teardown"
    assert errs and isinstance(errs[0], RayChannelError)


def _expect_raises(fut):
    try:
        fut.result(timeout=60)
        return None
    except Exception as e:  # noqa: BLE001 — the exception IS the assertion
        return e


def test_mutable_shm_nonblocking_poll():
    """Satellite: timeout=0 is a true non-blocking probe (the old deadline
    check ran only after a sleep cycle)."""
    from ray_tpu.experimental.channel.mutable_shm import \
        create_mutable_channel

    ch = create_mutable_channel(4096)
    try:
        t0 = time.perf_counter()
        with pytest.raises(TimeoutError):
            ch.read(timeout=0)
        assert time.perf_counter() - t0 < 0.05
        assert not ch.poll()
        ch.write({"x": 1})
        assert ch.poll()
        with pytest.raises(TimeoutError):
            ch.write({"x": 2}, timeout=0)  # buffer full, non-blocking
        assert ch.read(timeout=0) == {"x": 1}
    finally:
        ch.close()
        ch.unlink()
