"""Sharded proxy plane units: the seqlock routing-table shm segment,
SO_REUSEPORT / fd-passing port sharing, the HTTP body-size cap, the
single-flight route refresh, batched phase telemetry, and the zero-copy
request envelope.

(integration: test_serve_chaos.py::test_proxy_shard_sigkill_under_traffic
drives the whole plane — shard kill, controller replacement, shm leak
check — under live HTTP traffic.)
"""

import json
import os
import socket
import struct
import threading
import time

import pytest

import ray_tpu
from ray_tpu.serve import proxy_plane as pp


# ------------------------------------------------------- routing shm seqlock


def _segment(tmp_path, capacity=64 * 1024, create=True):
    return pp.RoutingTableShm(str(tmp_path / "seg"), capacity, _create=create)


def test_routing_shm_publish_read_roundtrip(tmp_path):
    w = _segment(tmp_path)
    r = pp.RoutingTableShm(str(tmp_path / "seg"), 0)  # attach: sizes itself
    try:
        table = {"version": 7, "routes": {"/a": "app_A"}, "deployments": {}}
        w.publish(table)
        got, ver, ts = r.read(-1)
        assert got == table and ver == 7 and ts > 0

        # unchanged version: reader pays only the header peek
        assert r.read(7) == (None, 7, ts)
        assert r.peek()[0] == 7

        # version moves → next read returns the new table
        w.publish({"version": 8, "routes": {}, "deployments": {}})
        got2, ver2, _ = r.read(7)
        assert ver2 == 8 and got2["version"] == 8
    finally:
        r.close()
        w.close()
        w.unlink()


def test_routing_shm_capacity_guard(tmp_path):
    w = _segment(tmp_path, capacity=1024)
    try:
        with pytest.raises(ValueError):
            w.publish({"version": 1, "pad": "x" * 4096})
    finally:
        w.close()
        w.unlink()


def test_routing_shm_torn_read_retries_until_publish(tmp_path):
    """A reader landing mid-write (odd seq) retries until the writer's
    publish completes instead of returning torn state."""
    w = _segment(tmp_path)
    r = pp.RoutingTableShm(str(tmp_path / "seg"), 0)
    try:
        w.publish({"version": 1, "routes": {}})
        # simulate a write in progress: odd sequence word
        seq = struct.unpack_from("<q", w._mm, 0)[0]
        struct.pack_into("<q", w._mm, 0, seq + 1)

        def finish():
            time.sleep(0.01)
            w.publish({"version": 2, "routes": {"/b": "app_B"}})

        t = threading.Thread(target=finish)
        t.start()
        got, ver, _ = r.read(-1)  # must block-retry through the odd window
        t.join()
        assert ver == 2 and got["routes"] == {"/b": "app_B"}
    finally:
        r.close()
        w.close()
        w.unlink()


def test_routing_shm_wedged_writer_times_out(tmp_path):
    w = _segment(tmp_path)
    r = pp.RoutingTableShm(str(tmp_path / "seg"), 0)
    try:
        struct.pack_into("<q", w._mm, 0, 1)  # writer died mid-write
        with pytest.raises(TimeoutError):
            r.read(-1)
    finally:
        r.close()
        w.close()
        w.unlink()


def test_routing_shm_create_attach_unlink(tmp_path):
    path = str(tmp_path / "seg")
    w = pp.RoutingTableShm(path, 4096, _create=True)
    with pytest.raises(FileExistsError):
        pp.RoutingTableShm(path, 4096, _create=True)  # O_EXCL create
    w.close()
    w.unlink()
    assert not os.path.exists(path)
    w.unlink()  # idempotent


# ----------------------------------------------------- port sharing / fd pass


def test_reserve_port_pins_without_accepting():
    holder = pp.reserve_port("127.0.0.1", 0)
    try:
        port = holder.getsockname()[1]
        # the holder never listens: a connect must NOT be accepted by it,
        # while a REUSEPORT listener on the same port serves fine
        if pp.REUSEPORT_AVAILABLE:
            srv = pp.make_listen_socket("127.0.0.1", port, reuse_port=True)
            srv.listen(8)
            c = socket.create_connection(("127.0.0.1", port), timeout=5)
            conn, _ = srv.accept()
            conn.close()
            c.close()
            srv.close()
    finally:
        holder.close()


@pytest.mark.skipif(not pp.FDPASS_AVAILABLE, reason="no send_fds/recv_fds")
def test_listener_fd_donor_roundtrip(tmp_path):
    listen = pp.make_listen_socket("127.0.0.1", 0)
    uds = str(tmp_path / "don.sock")
    donor = pp.ListenerFdDonor(listen, uds)
    try:
        got = pp.receive_listener_fd(uds, timeout=10.0)
        # the received fd is THE listening socket: an accept on it serves
        # a connection made to the donor's port
        assert got.getsockname() == listen.getsockname()
        got.listen(8)
        c = socket.create_connection(("127.0.0.1", donor.port), timeout=5)
        conn, _ = got.accept()
        conn.sendall(b"hi")
        assert c.recv(2) == b"hi"
        conn.close()
        c.close()
        got.close()
    finally:
        donor.close()
    assert not os.path.exists(uds)


# ------------------------------------------------------------- HTTP body cap


def test_http_body_cap_returns_413(monkeypatch):
    from ray_tpu._private.ray_config import RayConfig
    from ray_tpu.serve.http_server import AsyncHTTPServer

    monkeypatch.setenv("RAY_TPU_SERVE_MAX_HTTP_BODY_BYTES", "1024")
    RayConfig.reset()
    try:
        srv = AsyncHTTPServer(
            lambda method, path, headers, body: (200, "application/json",
                                                 b'{"ok": true}'),
            "127.0.0.1", 0).start()
        try:
            import http.client

            c = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=10)
            c.request("POST", "/x", body=b"x" * 4096,
                      headers={"Content-Type": "application/json"})
            r = c.getresponse()
            out = json.loads(r.read())
            assert r.status == 413
            assert out["max_body_bytes"] == 1024
            c.close()

            # under the cap still serves
            c = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=10)
            c.request("POST", "/x", body=b"x" * 512,
                      headers={"Content-Type": "application/json"})
            assert c.getresponse().status == 200
            c.close()
        finally:
            srv.stop()
    finally:
        monkeypatch.delenv("RAY_TPU_SERVE_MAX_HTTP_BODY_BYTES")
        RayConfig.reset()


# ------------------------------------------------------- single-flight fetch


@pytest.fixture(scope="module")
def tiny_cluster():
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, num_workers=1, max_workers=2)
    yield
    ray_tpu.shutdown()


class _CountingController:
    """Stands in for the ServeController handle: each get_routing_table
    fetch is counted and served as a real object ref (the proxy resolves
    it through ray_tpu.wait/get)."""

    def __init__(self):
        self.calls = 0
        outer = self

        class _Method:
            def remote(self, version):
                outer.calls += 1
                time.sleep(0.05)  # a real RPC takes time: lets racers pile up
                return ray_tpu.put({"version": outer.calls,
                                    "routes": {"/sf": "app"},
                                    "deployments": {}})

        self.get_routing_table = _Method()


def _bare_proxy(controller):
    from ray_tpu.serve.proxy import ProxyActor

    p = object.__new__(ProxyActor._cls)
    p.controller = controller
    p._routes = {}
    p._version = -1
    p._table = None
    p._handles = {}
    p._lock = threading.Lock()
    p._routes_ts = 0.0
    p._sf_lock = threading.Lock()
    p._sf_event = None
    p._pending_table = None
    p._routes_shm = None
    p._batcher = None
    return p


def test_refresh_routes_single_flight(tiny_cluster):
    ctl = _CountingController()
    p = _bare_proxy(ctl)
    threads = [threading.Thread(target=p._refresh_routes, kwargs={"force": True})
               for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert ctl.calls == 1, \
        f"{ctl.calls} controller fetches for 8 concurrent force refreshes"
    assert p._routes == {"/sf": "app"}

    # past the coalescing window a NEW forced refresh fetches again
    time.sleep(0.06)
    p._refresh_routes(force=True)
    assert ctl.calls == 2


def test_refresh_prefers_shm_over_rpc(tiny_cluster, tmp_path):
    ctl = _CountingController()
    p = _bare_proxy(ctl)
    seg = pp.RoutingTableShm(str(tmp_path / "seg"), 64 * 1024, _create=True)
    try:
        seg.publish({"version": 3, "routes": {"/shm": "app"},
                     "deployments": {}})
        p._routes_shm = pp.RoutingTableShm(str(tmp_path / "seg"), 0)
        p._refresh_routes(force=True)
        assert p._routes == {"/shm": "app"} and p._version == 3
        assert ctl.calls == 0, "shm-backed refresh must not RPC"
    finally:
        if p._routes_shm is not None:
            p._routes_shm.close()
        seg.close()
        seg.unlink()


# ------------------------------------------------------------ phase batching


def test_phase_batcher_groups_and_flushes():
    from ray_tpu.serve import request_context as rc
    from ray_tpu.util import metrics

    flushes = []
    b = rc.PhaseBatcher(flush_s=3600.0, on_flush=lambda: flushes.append(1))
    try:
        for _ in range(5):
            b.add(rc.PROXY_PHASE, "parse", 0.001)
        b.add(rc.PROXY_PHASE, "route", 0.002)
        assert len(b._buf) == 6
        b.flush()
        assert b._buf == [] and flushes == [1]
        snap = {m["name"]: m for m in metrics.snapshot()}
        series = snap["ray_tpu_serve_proxy_phase_seconds"]["series"]
        by_phase = {dict(tuple(t) for t in tags).get("phase"): st
                    for tags, st in series}
        assert by_phase["parse"]["count"] >= 5
        assert by_phase["route"]["count"] >= 1
    finally:
        b.close()


def test_observe_phase_routes_through_batcher():
    from ray_tpu.serve import request_context as rc

    b = rc.PhaseBatcher(flush_s=3600.0)
    rc.set_phase_batcher(b)
    try:
        rc.observe_phase(rc.PROXY_PHASE, "handle", 0.01)
        assert b._buf == [(rc.PROXY_PHASE, "handle", 0.01)]
    finally:
        rc.set_phase_batcher(None)
        b.close()


# ---------------------------------------------------------- zero-copy escrow


def test_build_request_escrows_large_body(tiny_cluster, monkeypatch):
    from ray_tpu._private.constants import SERVE_BODY_REF_KEY
    from ray_tpu._private.ray_config import RayConfig

    monkeypatch.setenv("RAY_TPU_SERVE_ZERO_COPY_THRESHOLD_BYTES", "1024")
    RayConfig.reset()
    try:
        p = _bare_proxy(_CountingController())
        rec: dict = {}
        big = json.dumps({"pad": "x" * 4096}).encode()
        env = p._build_request("/z", "POST", big, "rid-1", rec)
        assert env["body"] is None and SERVE_BODY_REF_KEY in env
        assert rec["_body_ref"] is not None  # pinned for the request's life
        raw = ray_tpu.get(ray_tpu.ObjectRef(env[SERVE_BODY_REF_KEY]),
                          timeout=10.0)
        assert raw == big

        small = b'{"a": 1}'
        env2 = p._build_request("/z", "POST", small, "rid-2", {})
        assert env2["body"] == {"a": 1} and SERVE_BODY_REF_KEY not in env2
    finally:
        monkeypatch.delenv("RAY_TPU_SERVE_ZERO_COPY_THRESHOLD_BYTES")
        RayConfig.reset()
