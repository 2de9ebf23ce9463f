"""Serve data-plane hardening: asyncio HTTP server behavior — keep-alive,
concurrency, graceful drain, and zero dropped requests across a scale-down.

(reference: python/ray/serve/_private/proxy.py:706 uvicorn proxy with
draining, serve/_private/deployment_state.py:1713 graceful replica
shutdown — VERDICT round-2 item 6.)
"""

import json
import threading
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture
def serve_cluster():
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=32, num_workers=2, max_workers=16)
    yield
    serve.shutdown()
    ray_tpu.shutdown()


def _post(url, payload, timeout=60):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


def test_http_keepalive_many_requests_one_connection(serve_cluster):
    @serve.deployment
    def echo(req):
        return {"got": (req.get("body") or {}).get("x")}

    serve.run(echo.bind(), name="ka", route_prefix="/ka")
    serve.start(http_port=0)
    host, port = serve.http_address()

    import http.client

    conn = http.client.HTTPConnection(host, port, timeout=30)
    try:
        for i in range(20):
            body = json.dumps({"x": i})
            conn.request("POST", "/ka", body=body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            out = json.loads(resp.read())
            assert resp.status == 200 and out["got"] == i
    finally:
        conn.close()
    serve.delete("ka")


def test_http_concurrent_requests(serve_cluster):
    @serve.deployment(num_replicas=2, max_ongoing_requests=8)
    def work(req):
        time.sleep(0.2)
        return {"ok": (req.get("body") or {}).get("i")}

    serve.run(work.bind(), name="conc", route_prefix="/conc")
    serve.start(http_port=0)
    host, port = serve.http_address()

    results: dict[int, tuple] = {}

    def call(i):
        try:
            results[i] = _post(f"http://{host}:{port}/conc", {"i": i})
        except Exception as e:  # noqa: BLE001
            results[i] = ("error", repr(e))

    threads = [threading.Thread(target=call, args=(i,)) for i in range(12)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    wall = time.monotonic() - t0
    assert all(r[0] == 200 for r in results.values()), results
    # 12 x 0.2s of work finished concurrently, not serially (2.4s)
    assert wall < 2.2, f"requests appear serialized: {wall:.1f}s"
    serve.delete("conc")


@pytest.mark.slow
def test_scale_down_drops_no_requests(serve_cluster):
    """Requests in flight on replicas being scaled away complete: replicas
    drain before dying and the router stops sending them new work."""

    @serve.deployment(num_replicas=4, max_ongoing_requests=4)
    def slow(req):
        time.sleep(0.4)
        return {"ok": (req.get("body") or {}).get("i")}

    # a loaded 1-core CI box can queue requests past the 5s default drain
    # grace; widen it so the test asserts draining, not box speed
    slow = slow.options(graceful_shutdown_timeout_s=30.0)

    serve.run(slow.bind(), name="sd", route_prefix="/sd")
    serve.start(http_port=0)
    host, port = serve.http_address()

    results: dict[int, tuple] = {}
    stop = threading.Event()

    def caller(i):
        j = 0
        while not stop.is_set():
            key = i * 1000 + j
            try:
                results[key] = _post(f"http://{host}:{port}/sd", {"i": key})
            except Exception as e:  # noqa: BLE001
                results[key] = ("error", repr(e))
            j += 1

    threads = [threading.Thread(target=caller, args=(i,)) for i in range(6)]
    for t in threads:
        t.start()
    time.sleep(1.5)  # steady state on 4 replicas
    # scale down to 1 replica mid-traffic (config-only redeploy)
    slow2 = slow.options(num_replicas=1,
                         graceful_shutdown_timeout_s=30.0)
    serve.run(slow2.bind(), name="sd", route_prefix="/sd")
    time.sleep(2.5)  # drain + keep serving on the survivor
    stop.set()
    for t in threads:
        t.join(timeout=60)

    assert results, "no traffic?"
    errors = {k: v for k, v in results.items() if v[0] != 200}
    assert not errors, f"{len(errors)}/{len(results)} dropped: {list(errors.items())[:3]}"
    st = serve.status()
    assert st["sd_slow"]["replicas"] == 1
    serve.delete("sd")


def test_graceful_proxy_shutdown_drains(serve_cluster):
    @serve.deployment
    def slowreq(req):
        time.sleep(1.0)
        return {"done": True}

    serve.run(slowreq.bind(), name="gs", route_prefix="/gs")
    serve.start(http_port=0)
    host, port = serve.http_address()

    out: list = []

    def call():
        try:
            out.append(_post(f"http://{host}:{port}/gs", {}, timeout=30))
        except Exception as e:  # noqa: BLE001
            out.append(("error", repr(e)))

    t = threading.Thread(target=call)
    t.start()
    time.sleep(0.3)  # request in flight
    serve.shutdown()  # proxy.stop(graceful=True) must let it finish
    t.join(timeout=30)
    assert out and out[0][0] == 200, out


def test_stop_ends_the_loop_thread_at_once():
    """stop() after a served request returns without sitting out its join
    timeout, and the loop thread is gone (it used to park forever: the one
    CancelledError was spent on serve_forever())."""
    from ray_tpu.serve.http_server import AsyncHTTPServer

    srv = AsyncHTTPServer(
        lambda method, path, headers, body: (200, "application/json", b"{}"),
        "127.0.0.1", 0).start()
    assert _post(f"http://127.0.0.1:{srv.port}/x", {}) == (200, {})
    t0 = time.monotonic()
    srv.stop(graceful=True)
    assert time.monotonic() - t0 < 2.0
    assert not srv._thread.is_alive()


# ------------------------------------------------- streams and the request pool
#
# A bare AsyncHTTPServer and handlers that yield: no cluster. A streamed
# answer is delivered on a thread of its own (`serve-http-deliver`), so the
# request pool (`executor_workers`) runs handlers only.


def _gauge_streams_open(stat):
    import os

    from ray_tpu.util import metrics as met

    series = met._registry["ray_tpu_serve_proxy_streams_open"]._snapshot_series()
    want = [["proxy", str(os.getpid())], ["stat", stat]]
    return next(v for tags, v in series if [list(t) for t in tags] == want)


def _delivery_threads(but=()):
    return [t for t in threading.enumerate()
            if t.name.startswith("serve-http-deliver") and t not in but]


def _read_stream(base, path="/stream", out=None):
    body = urllib.request.urlopen(
        urllib.request.Request(base + path, data=b"{}"), timeout=30).read()
    if out is not None:
        out.append(body)
    return body


def _wait_for(cond, timeout_s):
    deadline = time.monotonic() + timeout_s
    while not cond() and time.monotonic() < deadline:
        time.sleep(0.01)
    return cond()


class _Ticks:
    """A blocking iterator with a `close()` that counts: a chunk every 50 ms
    until `until` is set (then it ends) or, after `raise_after` chunks, a
    ValueError."""

    def __init__(self, until, raise_after=None, chunk=b"data: tick\n\n"):
        self.until, self.raise_after, self.chunk = until, raise_after, chunk
        self.pulls = self.closed = 0

    def __iter__(self):
        return self

    def __next__(self):
        if self.raise_after is not None and self.pulls >= self.raise_after:
            raise ValueError("boom")
        if self.until.wait(0.05):
            raise StopIteration
        self.pulls += 1
        return self.chunk

    def close(self):
        self.closed += 1


@pytest.mark.parametrize("path, answer", [("/x", {}), ("/v1/stats", {"answer": 1})],
                         ids=["plain", "state_route"])
def test_a_request_is_answered_while_streams_are_open(path, answer):
    """Eight answers being delivered and a pool of two threads: a request's
    hand-off does not queue behind them, whichever pool its route runs on
    (`.../stats` reads the deployment's own state on threads of its own)."""
    from ray_tpu.serve.http_server import AsyncHTTPServer, _reads_state

    assert _reads_state("/v1/stats") and _reads_state("/app/health/?x=1")
    assert not _reads_state("/v1/completions") and not _reads_state("/stats/now")
    release = threading.Event()
    yielded = []

    def handler(method, path, headers, body):
        if path.endswith("/stats"):
            return 200, "application/json", b'{"answer": 1}'
        if path.endswith("/stream"):
            def chunks():
                yield b"data: 0\n\n"
                yielded.append(1)
                release.wait(20)
            return 200, "text/event-stream", chunks()
        return 200, "application/json", b"{}"

    srv = AsyncHTTPServer(handler, "127.0.0.1", 0, executor_workers=2).start()
    base = f"http://127.0.0.1:{srv.port}"
    bodies: list = []
    streams = [threading.Thread(target=_read_stream, args=(base, "/stream", bodies))
               for _ in range(8)]
    try:
        for t in streams:
            t.start()
        assert _wait_for(lambda: len(yielded) == 8, 10)   # all open, all quiet
        assert _gauge_streams_open("now") == 8
        t0 = time.monotonic()
        assert _post(base + path, {}) == (200, answer)
        assert time.monotonic() - t0 < 1.0
        release.set()
        for t in streams:
            t.join(10)
        assert bodies == [b"data: 0\n\n"] * 8
    finally:
        release.set()
        srv.stop(graceful=False)


def test_every_open_stream_is_delivered_at_once():
    """64 streams against a pool of two threads, each of which goes on only
    once all 64 have yielded their first chunk: delivery bounded by the pool
    would wait here until the barrier breaks. Every stream's items arrive
    whole and in order, threads switching every 10 us; the gauge's
    high-water mark says how many were open."""
    import sys

    from ray_tpu.serve.http_server import AsyncHTTPServer

    n = 64
    barrier = threading.Barrier(n)
    want = b"".join(b"data: %d\n\n" % i for i in range(40))

    def handler(method, path, headers, body):
        def chunks():
            yield b"data: 0\n\n"
            barrier.wait(30)          # broken: raises, and an error chunk follows
            for i in range(1, 40):
                yield b"data: %d\n\n" % i
        return 200, "text/event-stream", chunks()

    srv = AsyncHTTPServer(handler, "127.0.0.1", 0, executor_workers=2).start()
    base = f"http://127.0.0.1:{srv.port}"
    bodies: list = []
    streams = [threading.Thread(target=_read_stream, args=(base, "/stream", bodies))
               for _ in range(n)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        t0 = time.monotonic()
        for t in streams:
            t.start()
        for t in streams:
            t.join(30)
        assert bodies == [want] * n
        assert time.monotonic() - t0 < 20.0
        assert _wait_for(lambda: srv._streams_open == 0, 5)
        assert _gauge_streams_open("peak") == n == srv._streams_open_peak
        assert _gauge_streams_open("now") == 0
    finally:
        sys.setswitchinterval(interval)
        barrier.abort()
        srv.stop(graceful=False)


@pytest.mark.parametrize("how", ["ends", "raises", "hang_up", "slow_client"])
def test_a_streams_iterator_is_closed_once(how):
    """However a stream ends, with more streams open than the pool has
    threads: the iterator's `close()` runs once (it is what frees the
    engine's slot and pages). An iterator that raises is delivered as an
    error chunk; a client that hangs up ends its stream within a second; a
    client that does not read stops the pulls (back-pressure)."""
    import http.client

    from ray_tpu.serve.http_server import AsyncHTTPServer

    others_end, this_ends = threading.Event(), threading.Event()
    big = b"x" * (256 * 1024)
    its = {"/others": [], "/this": []}

    def handler(method, path, headers, body):
        it = (_Ticks(this_ends, raise_after=2 if how == "raises" else None,
                     chunk=big if how == "slow_client" else b"data: tick\n\n")
              if path == "/this" else _Ticks(others_end))
        its[path].append(it)
        return 200, "text/event-stream", it

    srv = AsyncHTTPServer(handler, "127.0.0.1", 0, executor_workers=2).start()
    base = f"http://127.0.0.1:{srv.port}"
    others = [threading.Thread(target=_read_stream, args=(base, "/others"))
              for _ in range(4)]
    try:
        for t in others:
            t.start()
        assert _wait_for(lambda: len(its["/others"]) == 4
                         and all(i.pulls for i in its["/others"]), 10)
        if how in ("ends", "raises"):
            threading.Timer(0.2, this_ends.set).start()
            body = _read_stream(base, "/this")
            if how == "raises":
                assert body == (b"data: tick\n\n" * 2
                                + b'data: {"error": "ValueError: boom"}\n\n')
            else:
                assert body and body == b"data: tick\n\n" * (len(body) // 12)
        else:
            conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=30)
            conn.request("POST", "/this", body=b"{}")
            resp = conn.getresponse()
            if how == "slow_client":
                # unread, the answer fills the socket's buffers and the
                # server's few items ahead; then the pulls stop
                it = its["/this"][0]
                assert _wait_for(lambda: it.pulls > 16, 10)
                time.sleep(1.0)
                pulls = it.pulls
                time.sleep(0.5)
                assert it.pulls == pulls < 400
            else:
                assert resp.read(5)
            resp.close()      # the response holds the socket too: close both
            conn.close()
        (it,) = its["/this"]
        assert _wait_for(lambda: it.closed, 1.0 if how != "slow_client" else 3.0)
        time.sleep(0.2)
        assert it.closed == 1
        assert all(i.closed == 0 for i in its["/others"])
    finally:
        others_end.set()
        this_ends.set()
        for t in others:
            t.join(10)
        srv.stop(graceful=False)
    assert all(i.closed == 1 for i in its["/others"])


@pytest.mark.parametrize("end_within_grace", [True, False])
def test_stop_drains_open_streams_and_leaves_no_delivery_thread(end_within_grace):
    """stop(graceful=True) lets open streams finish for up to `drain_grace_s`,
    cuts those that have not, and no delivery thread outlives it."""
    from ray_tpu.serve.http_server import AsyncHTTPServer

    before = _delivery_threads()
    ends = threading.Event()
    its: list = []

    def handler(method, path, headers, body):
        its.append(_Ticks(ends))
        return 200, "text/event-stream", its[-1]

    srv = AsyncHTTPServer(handler, "127.0.0.1", 0, executor_workers=2,
                          drain_grace_s=1.0).start()
    base = f"http://127.0.0.1:{srv.port}"
    bodies: list = []

    def read():
        try:
            _read_stream(base, "/stream", bodies)
        except Exception as e:  # noqa: BLE001 — a cut stream is an incomplete read
            bodies.append(e)

    streams = [threading.Thread(target=read) for _ in range(4)]
    for t in streams:
        t.start()
    try:
        assert _wait_for(lambda: len(its) == 4 and all(i.pulls for i in its), 10)
        assert len(_delivery_threads(before)) == 4
        if end_within_grace:
            threading.Timer(0.3, ends.set).start()
        t0 = time.monotonic()
        srv.stop(graceful=True)
        took = time.monotonic() - t0
        assert not _delivery_threads(before) and not srv._deliveries
        assert not srv._thread.is_alive()
        assert [i.closed for i in its] == [1] * 4
        assert _gauge_streams_open("now") == 0
        for t in streams:
            t.join(10)
        if end_within_grace:
            assert took < 1.0
            assert all(isinstance(b, bytes) and b.endswith(b"tick\n\n") for b in bodies), bodies
        else:
            assert 1.0 <= took < 2.5
            assert len(bodies) == 4    # every client was let go, cut short or not
    finally:
        ends.set()
        for t in streams:
            t.join(10)
