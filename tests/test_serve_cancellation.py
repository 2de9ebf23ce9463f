"""End-to-end request cancellation, deadline propagation, and overload
shedding for the serve/LLM data plane.

Covers the three tentpole planes:

- engine: `abort_request` reclaims the decode slot + granted KV pages
  mid-stream (not at max_tokens); per-request deadlines expire between
  decode steps and refuse work at admission;
- serve: replica-side cancel latch (`_CancelHolder`), streaming-generator
  cancel through `DeploymentResponseGenerator.cancel()`, HTTP client
  disconnect propagating proxy → handle → replica;
- overload: bounded admission (`max_queued_requests`) sheds with
  RequestShedError, surfaced over HTTP as 503 + Retry-After, and
  deadline expiry as 504.
"""

from __future__ import annotations

import http.client
import json
import pickle
import threading
import time

import jax
import jax.numpy as jnp
import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.exceptions import (DeadlineExceededError, RequestCancelledError,
                                RequestShedError)
from ray_tpu.llm.engine import SamplingParams, TPUEngine
from ray_tpu.models import transformer
from ray_tpu.models.transformer import TransformerConfig

TINY = dict(vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
            d_ff=128, max_seq_len=128, dtype=jnp.float32, remat=False)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = TransformerConfig(**TINY)
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _paged_engine(cfg, params, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("min_bucket", 8)
    kw.setdefault("page_size", 8)
    return TPUEngine(cfg, params, **kw)


def _wait_pool_restored(eng, timeout_s=10.0):
    """Poll until every slot and page is back in the pool."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        st = eng.stats()
        if (st["free_slots"] == st["max_slots"]
                and st["free_pages"] == st["num_pages"] - 1):
            return st
        time.sleep(0.02)
    raise AssertionError(f"pool not restored: {eng.stats()}")


# ------------------------------------------------------------------ engine


def test_engine_abort_reclaims_mid_stream(tiny_model):
    """The order, not a duration: once the row's first token is out the
    scheduler's sweep waits for the abort to be queued, so the request cannot
    end by count first, however late this thread runs."""
    cfg, params = tiny_model
    eng = _paged_engine(cfg, params)
    queued, reqs, sweep = threading.Event(), [], eng._apply_aborts

    def held_sweep():
        if reqs and reqs[0].generated:
            assert queued.wait(60.0)
        sweep()

    eng._apply_aborts = held_sweep
    try:
        req = eng.submit([1, 2, 3, 4], SamplingParams(max_tokens=48))
        reqs.append(req)
        it = iter(req)
        next(it)  # at least one decode step has run: the slot is bound
        eng.abort_request(req.rid)
        queued.set()
        with pytest.raises(RequestCancelledError):
            for _ in it:
                pass
        # the sweep after the first token applied it: the step in flight
        # then was the row's last
        assert req.generated <= 2 and req.dispatched <= 3
        st = _wait_pool_restored(eng)
        assert st["aborts"] == 1
        # the engine keeps serving after an abort
        out = list(eng.submit([5, 6, 7], SamplingParams(max_tokens=4)))
        assert len(out) == 4
    finally:
        eng.shutdown()


def test_engine_deadline_expires_mid_stream(tiny_model):
    cfg, params = tiny_model
    eng = _paged_engine(cfg, params)
    try:
        req = eng.submit([1, 2, 3], SamplingParams(max_tokens=56),
                         deadline_ts=time.time() + 0.3)
        toks = []
        with pytest.raises(DeadlineExceededError):
            for t in req:
                toks.append(t)
        assert len(toks) < 56  # it did NOT run to max_tokens
        _wait_pool_restored(eng)
    finally:
        eng.shutdown()


def test_engine_deadline_refused_at_admission(tiny_model):
    cfg, params = tiny_model
    eng = _paged_engine(cfg, params)
    try:
        req = eng.submit([1, 2, 3], SamplingParams(max_tokens=8),
                         deadline_ts=time.time() - 1.0)  # already expired
        with pytest.raises(DeadlineExceededError):
            list(req)
        st = _wait_pool_restored(eng)
        assert st["aborts"] == 1
    finally:
        eng.shutdown()


def test_engine_abort_unknown_rid_is_noop(tiny_model):
    cfg, params = tiny_model
    eng = _paged_engine(cfg, params)
    try:
        eng.abort_request(123456)  # never submitted: tombstones, no crash
        out = list(eng.submit([1, 2], SamplingParams(max_tokens=3)))
        assert len(out) == 3
    finally:
        eng.shutdown()


# ----------------------------------------------------------- serve plumbing


def test_request_shed_error_pickles_retry_after():
    e = pickle.loads(pickle.dumps(RequestShedError("full", retry_after_s=2.5)))
    assert isinstance(e, RequestShedError)
    assert e.retry_after_s == 2.5


def test_cancel_holder_latches_in_either_order():
    from ray_tpu.serve.replica import _CancelHolder

    fired = []
    h = _CancelHolder()
    h.register(lambda: fired.append("a"))
    h.cancel()
    assert fired == ["a"]
    # registering AFTER the cancel landed fires immediately (the race
    # between engine submit and on_cancel registration must not lose it)
    h.register(lambda: fired.append("b"))
    assert fired == ["a", "b"]
    h.cancel()  # idempotent
    assert fired == ["a", "b"]


# -------------------------------------------------------------- end-to-end


@pytest.fixture
def serve_session():
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8, num_workers=2, max_workers=10)
    yield
    serve.shutdown()
    ray_tpu.shutdown()


@serve.deployment
class Interruptible:
    """Streams slowly and counts how its streams end, so tests can observe
    replica-side cancellation from outside the replica process."""

    def __init__(self):
        self.interrupted = 0
        self.completed = 0

    def stream_request(self, request: dict):
        try:
            for i in range(100):
                yield {"i": i}
                time.sleep(0.1)
            self.completed += 1
        except GeneratorExit:
            # the replica wrapper closes the generator on cancel
            self.interrupted += 1
            raise

    def __call__(self, request: dict):
        return {"interrupted": self.interrupted, "completed": self.completed}


@serve.deployment(max_ongoing_requests=1, max_queued_requests=0)
class SlowOne:
    def __call__(self, request: dict):
        time.sleep(float((request.get("body") or {}).get("sleep", 1.0)))
        return {"ok": True}


def _post(port, path, body, headers=None, timeout=30):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    payload = json.dumps(body)
    hdrs = {"Content-Type": "application/json",
            "Content-Length": str(len(payload))}
    hdrs.update(headers or {})
    conn.request("POST", path, body=payload, headers=hdrs)
    resp = conn.getresponse()
    data = resp.read()
    out = (resp.status, dict(resp.getheaders()), data)
    conn.close()
    return out


def _poll_state(handle, pred, timeout_s=15.0):
    deadline = time.monotonic() + timeout_s
    state = None
    while time.monotonic() < deadline:
        state = handle.call_sync({}, timeout_s=10.0)
        if pred(state):
            return state
        time.sleep(0.2)
    raise AssertionError(f"state never satisfied predicate: {state}")


def test_stream_cancel_via_handle(serve_session):
    serve.start(http_port=0)
    handle = serve.run(Interruptible.bind(), name="canc",
                       route_prefix="/canc")
    gen = handle.options(stream=True, method_name="stream_request").remote({})
    it = iter(gen)
    next(it)  # stream is live on the replica
    gen.cancel()
    state = _poll_state(handle, lambda s: s["interrupted"] >= 1)
    assert state["completed"] == 0


def test_http_client_disconnect_cancels_stream(serve_session):
    serve.start(http_port=0)
    handle = serve.run(Interruptible.bind(), name="disc",
                       route_prefix="/disc")
    _, port = serve.http_address()
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    payload = json.dumps({})
    conn.request("POST", "/disc", body=payload,
                 headers={"Content-Type": "application/json",
                          "Accept": "text/event-stream",
                          "Content-Length": str(len(payload))})
    resp = conn.getresponse()
    assert resp.status == 200
    resp.read1(64)  # at least one chunk arrived: the stream is mid-flight
    # http.client's response holds a makefile() of the socket: without
    # resp.close() the fd stays open (_io_refs > 0) and no FIN is ever
    # sent, so close BOTH to actually drop the connection
    resp.close()
    conn.close()
    state = _poll_state(handle, lambda s: s["interrupted"] >= 1)
    assert state["completed"] == 0


def test_overload_sheds_503_with_retry_after(serve_session):
    serve.start(http_port=0)
    serve.run(SlowOne.bind(), name="shed", route_prefix="/shed")
    _, port = serve.http_address()
    results = []

    def hit():
        results.append(_post(port, "/shed", {"sleep": 1.5}))

    threads = [threading.Thread(target=hit) for _ in range(3)]
    for t in threads:
        t.start()
        time.sleep(0.1)  # deterministic arrival order
    for t in threads:
        t.join()
    statuses = sorted(r[0] for r in results)
    assert statuses[0] == 200, results
    assert 503 in statuses, statuses
    shed = next(r for r in results if r[0] == 503)
    assert shed[1].get("Retry-After"), shed[1]
    assert "shed" in json.loads(shed[2])["error"].lower() or \
        "window" in json.loads(shed[2])["error"].lower()


def test_deadline_header_maps_to_504(serve_session):
    serve.start(http_port=0)
    serve.run(SlowOne.options(max_queued_requests=-1).bind(),
              name="dl", route_prefix="/dl")
    _, port = serve.http_address()
    t0 = time.monotonic()
    status, headers, data = _post(
        port, "/dl", {"sleep": 5.0},
        headers={"x-ray-tpu-deadline-s": "0.4"})
    elapsed = time.monotonic() - t0
    assert status == 504, (status, data)
    assert elapsed < 4.0, f"deadline did not cut the wait: {elapsed:.1f}s"
