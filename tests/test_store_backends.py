"""Backend matrix: the store-sensitive tier-1 subset runs against BOTH
object-store backends — the native shm arena (the default since the flip in
ray_tpu/_private/object_store.py) and the file-per-object fallback
(RAY_TPU_STORE_BACKEND=file).

Covers, per backend: object lifecycle through a real session (driver put /
worker get / worker put / driver get), spilling past a tight tmpfs budget
with everything staying readable, the cross-host transfer plane serving
chunked reads (pins released after send on the arena), and a compiled-DAG
channel smoke. Each session fixture also asserts no /dev/shm segment of its
session leaks past shutdown — the arena file and spill dir must be torn
down by cleanup_session just like the per-object files.
"""

from __future__ import annotations

import glob
import os
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu._private import api as _api
from ray_tpu._private.constants import SHM_DIR, SHM_SESSION_PREFIX
from ray_tpu._private.object_store import make_object_store
from ray_tpu._private.object_transfer import ObjectFetcher, ObjectPlaneServer

pytestmark = pytest.mark.store_matrix

BACKENDS = ("arena", "file")


def _shm_entries(session_id: str) -> set:
    """This session's segments only: other sessions on the machine (xdist
    workers run theirs at the same time) come and go under the same prefix."""
    return set(glob.glob(os.path.join(
        SHM_DIR, f"{SHM_SESSION_PREFIX}{session_id}_*")))


@pytest.fixture(params=BACKENDS)
def backend(request, monkeypatch):
    """Pin the store backend for this process AND every child it spawns
    (spawn_env forwards explicitly-set RAY_TPU_* flags)."""
    monkeypatch.setenv("RAY_TPU_STORE_BACKEND", request.param)
    yield request.param


@pytest.fixture
def backend_session(backend):
    ray_tpu.shutdown()
    session_id = ray_tpu.init(num_cpus=8, num_workers=1, max_workers=8)["session_id"]
    yield backend
    ray_tpu.shutdown()
    leaked = _shm_entries(session_id)
    assert not leaked, f"/dev/shm leak under backend={backend}: {leaked}"


def test_object_lifecycle(backend_session):
    # big enough to clear the 64 KiB inline tier: these travel via the store
    arr = np.arange(50_000, dtype=np.float64)  # 400 KB
    ref = ray_tpu.put(arr)

    @ray_tpu.remote
    def double(x):
        return x * 2.0

    out = ray_tpu.get(double.remote(ref))  # worker gets, worker puts
    np.testing.assert_array_equal(out, arr * 2.0)
    np.testing.assert_array_equal(ray_tpu.get(ref), arr)  # driver re-get
    # many distinct objects round-trip (exercises index + free-list reuse)
    refs = [ray_tpu.put(np.full(20_000, i, np.float64)) for i in range(20)]
    for i, r in enumerate(refs):
        assert ray_tpu.get(r)[0] == i
    # live objects carry the names the fixture's leak check looks for
    assert _shm_entries(_api._worker.session_id)


def test_spilling_past_budget(backend, monkeypatch):
    """2x the store budget of live objects: everything stays readable, the
    overflow lands in the spill tier (file: GCS spiller; arena: LRU
    evict-to-spill on put)."""
    monkeypatch.setenv("RAY_TPU_OBJECT_STORE_CAPACITY", str(1_600_000))
    monkeypatch.setenv("RAY_TPU_STORE_CAPACITY", str(1_600_000))
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, num_workers=1, max_workers=4)
    try:
        refs = [ray_tpu.put(np.full(100_000, i, np.float64))  # 8 x 0.8 MB
                for i in range(8)]
        time.sleep(0.3)  # let the file-backend spiller drain
        for i, r in enumerate(refs):
            arr = ray_tpu.get(r)
            assert arr[0] == i and arr.shape == (100_000,)
        if backend == "arena":
            store = _api._worker.store
            # the budget bound holds structurally: the arena segment IS the
            # capacity; live bytes inside it never exceed it
            assert store.used() <= store.capacity() <= 2 * 1_600_000
    finally:
        ray_tpu.shutdown()


def test_transfer_plane_serves_both_tiers(backend):
    """The chunked TCP transfer plane must serve arena objects from pinned
    views (releasing the pin after send) and spilled objects from disk —
    same as it always did for the file backend."""
    src = make_object_store(f"xfer{backend}src")
    dst = make_object_store(f"xfer{backend}dst")
    srv = ObjectPlaneServer(src, host="127.0.0.1")
    try:
        payload = os.urandom(300_000)
        src.put_parts("aa11", [payload], len(payload))
        spilled = os.urandom(120_000)
        src.put_parts("bb22", [spilled], len(spilled))
        assert src.spill("bb22")  # serve-from-spill path
        fetcher = ObjectFetcher(dst)
        assert fetcher.fetch("aa11", srv.address)
        assert fetcher.fetch("bb22", srv.address)
        assert bytes(dst.get("aa11").buf) == payload
        assert bytes(dst.get("bb22").buf) == spilled
        assert fetcher.fetch("nope", srv.address) is False  # miss path
        if hasattr(src, "used"):  # arena: the send must not leak its pin
            src.delete("aa11")
            assert src.used() == 0 or not src.contains("aa11")
            assert src.used() == 0, "transfer leaked a pin; delete deferred"
    finally:
        srv.stop()
        src.cleanup_session()
        dst.cleanup_session()


def test_arena_unavailable_degrades_to_file(monkeypatch, caplog):
    """No C++ toolchain (g++ missing / compile failure) must not crash
    init(): the selector warns, pins the file backend into the env so
    children agree, and returns the file store."""
    import subprocess

    from ray_tpu._private import shm_arena
    from ray_tpu._private.object_store import ShmObjectStore

    def broken_toolchain():
        raise subprocess.CalledProcessError(1, ["g++"])

    monkeypatch.setenv("RAY_TPU_STORE_BACKEND", "arena")
    monkeypatch.setattr(shm_arena, "_ensure_lib", broken_toolchain)
    with caplog.at_level("WARNING"):
        store = make_object_store("degrade_test")
    try:
        assert isinstance(store, ShmObjectStore)
        assert os.environ["RAY_TPU_STORE_BACKEND"] == "file"
        assert any("falling back" in r.message for r in caplog.records)
    finally:
        store.cleanup_session()


def test_dag_channels_smoke(backend_session):
    """Compiled-DAG channel plane over each backend: the exec-loop actors
    and the driver share whichever store is configured."""
    from ray_tpu.dag import InputNode

    @ray_tpu.remote
    class Adder:
        def __init__(self, bias):
            self.bias = bias

        def work(self, x):
            return x + self.bias

    actors = [Adder.remote(1), Adder.remote(10)]
    with InputNode() as inp:
        node = inp
        for a in actors:
            node = a.work.bind(node)
    compiled = node.experimental_compile()
    try:
        for i in range(3):
            assert ray_tpu.get(compiled.execute(i)) == i + 11
    finally:
        compiled.teardown()
