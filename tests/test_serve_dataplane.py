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


def test_a_state_route_answers_while_streams_hold_every_request_thread():
    """A streamed answer holds one of the request pool's threads for its
    whole life and handlers queue behind the pumps, so with the pool full a
    request waits; a route that reads the deployment's own state (`.../stats`)
    is answered on threads of its own, at once."""
    from ray_tpu.serve.http_server import AsyncHTTPServer, _reads_state

    assert _reads_state("/v1/stats") and _reads_state("/app/health/?x=1")
    assert not _reads_state("/v1/completions") and not _reads_state("/stats/now")
    release = threading.Event()

    def handler(method, path, headers, body):
        if path.endswith("/stats"):
            return 200, "application/json", b'{"answer": 1}'
        if path.endswith("/stream"):
            def chunks():
                yield b"data: 0\n\n"
                release.wait(20)
            return 200, "text/event-stream", chunks()
        return 200, "application/json", b"{}"

    srv = AsyncHTTPServer(handler, "127.0.0.1", 0, executor_workers=2).start()
    base = f"http://127.0.0.1:{srv.port}"
    streams = [threading.Thread(target=lambda: urllib.request.urlopen(
        urllib.request.Request(base + "/stream", data=b"{}"), timeout=30).read())
        for _ in range(2)]
    try:
        for t in streams:
            t.start()
        time.sleep(0.5)                                   # both pumps hold their thread
        late = []
        other = threading.Thread(target=lambda: late.append(_post(base + "/x", {})))
        other.start()
        t0 = time.monotonic()
        assert _post(base + "/v1/stats", {}) == (200, {"answer": 1})
        assert time.monotonic() - t0 < 2.0
        other.join(1.0)
        assert other.is_alive() and not late              # a request waits for a thread
        release.set()
        other.join(10)
        assert late == [(200, {})]
    finally:
        release.set()
        for t in streams:
            t.join(10)
        srv.stop(graceful=False)
