"""ActorPool, distributed Queue, from_huggingface.

(reference: python/ray/util/actor_pool.py:13, python/ray/util/queue.py:21,
data read_api from_huggingface — the small public utility APIs users
reach for first when porting.)
"""

import os
import time

import pytest

import ray_tpu
import ray_tpu.data as rdata
from ray_tpu.util import ActorPool
from ray_tpu.util.queue import Empty, Full, Queue


@pytest.fixture(scope="module", autouse=True)
def cluster():
    ray_tpu.init(num_cpus=32, num_workers=3, max_workers=8)
    yield
    ray_tpu.shutdown()


@ray_tpu.remote
class Doubler:
    def double(self, v):
        return 2 * v

    def held_double(self, v, gate):
        """Value 0 ends when the gate file is there (or after a minute)."""
        deadline = time.monotonic() + 60.0
        while v == 0 and not os.path.exists(gate) and time.monotonic() < deadline:
            time.sleep(0.01)
        return 2 * v


def _kill_all(actors):
    for a in actors:
        try:
            ray_tpu.kill(a)
        except Exception:
            pass


def test_actor_pool_map_ordered():
    actors = [Doubler.remote(), Doubler.remote()]
    pool = ActorPool(actors)
    try:
        assert list(pool.map(lambda a, v: a.double.remote(v),
                             list(range(8)))) == [2 * v for v in range(8)]
        # pool is reusable after a full map
        assert list(pool.map(lambda a, v: a.double.remote(v), [5])) == [10]
    finally:
        _kill_all(actors)


def test_actor_pool_map_unordered_completion_order(tmp_path):
    actors = [Doubler.remote(), Doubler.remote()]
    pool = ActorPool(actors)
    gate = str(tmp_path / "gate")
    try:
        results = pool.map_unordered(
            lambda a, v: a.held_double.remote(v, gate), [0, 1, 2, 3])
        # value 0, submitted first, is held until the gate opens: the other
        # actor's results overtake it, and it is yielded when it completes
        out = [next(results)]
        assert out != [0]
        open(gate, "w").close()
        out += list(results)
        assert sorted(out) == [0, 2, 4, 6]
    finally:
        _kill_all(actors)


def test_actor_pool_streaming_submit():
    actors = [Doubler.remote()]
    pool = ActorPool(actors)
    try:
        pool.submit(lambda a, v: a.double.remote(v), 1)
        pool.submit(lambda a, v: a.double.remote(v), 2)  # queued: pool busy
        assert pool.has_next()
        assert pool.get_next() == 2
        assert pool.get_next() == 4
        assert not pool.has_next()
        with pytest.raises(StopIteration):
            pool.get_next()
    finally:
        _kill_all(actors)


def test_actor_pool_push_pop():
    a1, a2 = Doubler.remote(), Doubler.remote()
    pool = ActorPool([a1])
    try:
        idle = pool.pop_idle()
        assert idle is a1
        pool.push(a1)
        pool.push(a2)
        with pytest.raises(ValueError, match="already belongs"):
            pool.push(a2)
        assert list(pool.map(lambda a, v: a.double.remote(v),
                             [1, 2])) == [2, 4]
    finally:
        _kill_all([a1, a2])


def test_queue_basic_fifo_and_batch():
    q = Queue()
    q.put(1)
    q.put_nowait(2)
    q.put_nowait_batch([3, 4, 5])
    assert len(q) == 5 and not q.empty()
    assert q.get() == 1
    assert q.get_nowait() == 2
    assert q.get_nowait_batch(3) == [3, 4, 5]
    assert q.empty()
    with pytest.raises(Empty):
        q.get_nowait()
    with pytest.raises(Empty):
        q.get_nowait_batch(1)
    with pytest.raises(Empty):
        q.get(timeout=0.1)
    q.shutdown()


def test_queue_maxsize_and_full():
    q = Queue(maxsize=2)
    q.put(1)
    q.put(2)
    assert q.full()
    with pytest.raises(Full):
        q.put_nowait(3)
    with pytest.raises(Full):
        q.put(3, timeout=0.1)
    with pytest.raises(Full):
        q.put_nowait_batch([3, 4])
    assert q.get() == 1
    q.put(3, timeout=5)  # room freed: succeeds
    assert q.get_nowait_batch(2) == [2, 3]
    q.shutdown()


def test_queue_cross_process():
    q = Queue()

    @ray_tpu.remote
    def producer(q, n):
        for i in range(n):
            q.put(i)
        return n

    @ray_tpu.remote
    def consumer(q, n):
        return [q.get(timeout=30) for _ in range(n)]

    p = producer.remote(q, 5)
    c = consumer.remote(q, 5)
    assert ray_tpu.get(p) == 5
    assert ray_tpu.get(c) == [0, 1, 2, 3, 4]
    q.shutdown()


def test_queue_blocking_put_unblocks():
    q = Queue(maxsize=1)
    q.put("a")

    @ray_tpu.remote
    def blocked_put(q):
        q.put("b", timeout=30)
        return "done"

    ref = blocked_put.remote(q)
    time.sleep(0.3)
    assert q.get() == "a"  # frees the slot; the remote put lands
    assert ray_tpu.get(ref) == "done"
    assert q.get(timeout=10) == "b"
    q.shutdown()


def test_from_huggingface():
    datasets = pytest.importorskip("datasets")

    hf = datasets.Dataset.from_dict(
        {"text": ["a", "b", "c", "d"], "label": [0, 1, 0, 1]})
    ds = rdata.from_huggingface(hf)
    rows = ds.take_all()
    assert [r["text"] for r in rows] == ["a", "b", "c", "d"]
    assert [int(r["label"]) for r in rows] == [0, 1, 0, 1]
    # pipeline ops compose on top
    assert ds.filter(lambda r: int(r["label"]) == 1).count() == 2

    with pytest.raises(ValueError, match="DatasetDict"):
        rdata.from_huggingface(
            datasets.DatasetDict({"train": hf}))


def test_actor_pool_ordered_after_unordered():
    # reference semantics: unordered retrieval advances the ordered cursor
    actors = [Doubler.remote()]
    pool = ActorPool(actors)
    try:
        out = sorted(pool.map_unordered(
            lambda a, v: a.double.remote(v), [1, 2]))
        assert out == [2, 4]
        # ordered map after a fully-consumed unordered map must not crash
        assert list(pool.map(lambda a, v: a.double.remote(v), [3])) == [6]
    finally:
        _kill_all(actors)


def test_queue_graceful_shutdown_drains():
    q = Queue()
    q.put_nowait_batch([1, 2, 3])

    @ray_tpu.remote
    def drain(q):
        return [q.get(timeout=10) for _ in range(3)]

    ref = drain.remote(q)
    q.shutdown(force=False, grace_period_s=10)  # waits for the consumer
    assert ray_tpu.get(ref) == [1, 2, 3]
    # closed+killed: later operations fail
    with pytest.raises(Exception):
        q.qsize()


def test_from_huggingface_views():
    datasets = pytest.importorskip("datasets")

    hf = datasets.Dataset.from_dict({"x": list(range(10))})
    picked = hf.select([7, 3, 9])
    rows = rdata.from_huggingface(picked).take_all()
    # the lazy _indices view must be honored: exact rows, exact order
    assert [int(r["x"]) for r in rows] == [7, 3, 9]
