"""Cancellation chaos: clients drop mid-decode under concurrency.

The robustness bar for end-to-end cancellation (ISSUE 16): when a subset
of in-flight requests is abandoned — decode-slot aborts on the engine,
ticket aborts on the transfer plane, HTTP disconnects at the proxy — every
slot and every granted KV page returns to the pool within bounded steps,
/dev/shm holds no leaked channel segments, and the SURVIVING requests'
outputs stay token-exact against the monolithic engine.
"""

import glob
import http.client
import json
import threading
import time

import jax
import jax.numpy as jnp
import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu._private.constants import SHM_CHANNEL_GLOB
from ray_tpu.exceptions import RequestCancelledError
from ray_tpu.llm.engine import SamplingParams, TPUEngine
from ray_tpu.llm.kv_transfer import (BatchedKVPuller, KVPageStream,
                                     KVTransferError, PagedKVExporter)
from ray_tpu.models import decoding, transformer
from ray_tpu.models.transformer import TransformerConfig

from tests.test_llm_pd import _prefill_ticket  # serve-free prefill half

TINY = dict(vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
            d_ff=128, max_seq_len=128, dtype=jnp.float32, remat=False)
PAGE = 16
MAX_LEN = 64


@pytest.fixture(scope="module")
def tiny_model():
    cfg = TransformerConfig(**TINY)
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _paged_engine(cfg, params, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_len", MAX_LEN)
    kw.setdefault("min_bucket", PAGE)
    kw.setdefault("page_size", PAGE)
    return TPUEngine(cfg, params, **kw)


def _shm_channels() -> set:
    return set(glob.glob(SHM_CHANNEL_GLOB))


def _wait_pool_restored(eng, timeout_s=15.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        st = eng.stats()
        if (st["free_slots"] == st["max_slots"]
                and st["free_pages"] == st["num_pages"] - 1):
            return st
        time.sleep(0.02)
    raise AssertionError(f"pool not restored: {eng.stats()}")


# ----------------------------------------------------------- transfer plane


@pytest.mark.pd
def test_puller_abort_kills_transfer_and_retires_sender(tiny_model):
    """BatchedKVPuller.abort mid-stream: the sink fails with a
    cancellation KVTransferError, the sender's next write observes the
    closed channel and retires the transfer, and teardown leaves no
    /dev/shm segments behind."""
    cfg, params = tiny_model
    before = _shm_channels()
    slow = PagedKVExporter(send_timeout_s=30.0, prefetch_pages=1,
                           page_interval_s=0.12)
    puller = BatchedKVPuller()
    try:
        ticket = _prefill_ticket(cfg, params, list(range(2, 50)), slow)
        assert not ticket.get("sync")
        stream = KVPageStream(ticket["n_pages"], ticket["page_size"])
        puller.pull(ticket, stream, timeout_s=30.0)
        assert puller.abort(ticket["ticket"]) is True
        deadline = time.monotonic() + 10.0
        while stream._error is None and time.monotonic() < deadline:
            time.sleep(0.02)
        assert isinstance(stream._error, KVTransferError)
        assert "cancel" in str(stream._error).lower()
        # the sender observes the closed channel and retires
        deadline = time.monotonic() + 10.0
        while slow.pending() and time.monotonic() < deadline:
            time.sleep(0.05)
        assert slow.pending() == 0
        # a settled/unknown ticket abort is a no-op
        assert puller.abort(ticket["ticket"]) is False
        assert puller.abort("no-such-ticket") is False
    finally:
        slow.teardown()
        puller.teardown()
    assert _shm_channels() - before == set()


# ----------------------------------------------------------- engine + PD


@pytest.mark.pd
@pytest.mark.slow
def test_disconnect_storm_survivors_token_exact(tiny_model):
    """Concurrent mix of streamed-admission PD requests and plain decodes;
    half the clients 'drop' mid-decode (engine abort + ticket abort, the
    exact calls the serve layer makes on disconnect). Every slot and page
    returns to the pool, no shm segment leaks, and the surviving requests
    produce EXACTLY the monolithic engine's tokens."""
    cfg, params = tiny_model
    before = _shm_channels()
    mono = _paged_engine(cfg, params)
    dec = _paged_engine(cfg, params)
    # 0.15 s/page: a 4-page dropped transfer stays open ~0.6 s — the
    # abort at 0.25 s lands deterministically mid-transfer
    slow = PagedKVExporter(send_timeout_s=30.0, prefetch_pages=1,
                           page_interval_s=0.15)
    puller = BatchedKVPuller()
    sp = SamplingParams(max_tokens=12, temperature=0.0)
    prompts = [list(range(2, 40)),   # PD survivor
               list(range(2, 52)),   # PD dropped (engine + ticket abort)
               [1, 5, 9, 2],         # plain survivor
               [3] * 48]             # PD dropped (ticket abort only)
    try:
        want = [mono.generate(prompts[0], sp), None,
                mono.generate(prompts[2], sp), None]

        tickets = [_prefill_ticket(cfg, params, prompts[i], slow)
                   for i in (0, 1, 3)]
        tickets = {0: tickets[0], 1: tickets[1], 3: tickets[2]}
        streams = {i: KVPageStream(t["n_pages"], t["page_size"])
                   for i, t in tickets.items()}
        for i, t in tickets.items():
            puller.pull(t, streams[i], timeout_s=30.0)
        reqs = {i: dec.submit_prefilled(
                    length=t["length"], first_token=t["first_token"],
                    params=sp, kv_stream=streams[i])
                for i, t in tickets.items()}
        reqs[2] = dec.submit(prompts[2], sp)

        results: dict[int, object] = {}

        def consume(i, req):
            try:
                results[i] = list(req)
            except BaseException as e:  # noqa: BLE001 — recorded for asserts
                results[i] = e

        threads = [threading.Thread(target=consume, args=(i, r))
                   for i, r in reqs.items()]
        for t in threads:
            t.start()
        time.sleep(0.25)  # dropped transfers are mid-stream
        # client drops, in both orders the serve layer can issue them:
        # request 1 gets the full DecodeServer._abort pair (engine abort
        # first, then ticket), request 3 only the ticket abort — the
        # transfer-failure path must reclaim the slot on its own
        dec.abort_request(reqs[1].rid)
        puller.abort(tickets[1]["ticket"])
        assert puller.abort(tickets[3]["ticket"]) is True
        for t in threads:
            t.join(timeout=60.0)
        assert not any(t.is_alive() for t in threads)

        # dropped requests surfaced the cancel, not a hang or a full run
        assert isinstance(results[1], (RequestCancelledError,
                                       KVTransferError)), results[1]
        assert isinstance(results[3], KVTransferError), results[3]
        # survivors are token-exact
        assert not isinstance(results[0], BaseException), results[0]
        assert [tickets[0]["first_token"]] + list(results[0]) == want[0]
        assert results[2] == want[2]

        st = _wait_pool_restored(dec)
        assert st["aborts"] >= 1
        # the engine keeps serving after the storm
        assert mono.generate(prompts[2], sp) == dec.generate(prompts[2], sp)
    finally:
        slow.teardown()
        puller.teardown()
        mono.shutdown()
        dec.shutdown()
    assert _shm_channels() - before == set()


# ----------------------------------------------------------------- serve


@serve.deployment(max_ongoing_requests=8)
class StormTarget:
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
            self.interrupted += 1
            raise

    def __call__(self, request: dict):
        return {"interrupted": self.interrupted, "completed": self.completed}


@pytest.mark.serve_chaos
@pytest.mark.slow
def test_http_disconnect_storm_interrupts_every_stream():
    """N concurrent SSE clients all drop mid-stream: every replica-side
    generator is interrupted (none runs to completion) — the proxy's
    abandoned-stream cancel keeps up under a disconnect storm."""
    N = 4
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8, num_workers=2, max_workers=10)
    try:
        serve.start(http_port=0)
        handle = serve.run(StormTarget.bind(), name="storm",
                           route_prefix="/storm")
        _, port = serve.http_address()

        def drop_one():
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
            payload = json.dumps({})
            conn.request("POST", "/storm", body=payload,
                         headers={"Content-Type": "application/json",
                                  "Accept": "text/event-stream",
                                  "Content-Length": str(len(payload))})
            resp = conn.getresponse()
            assert resp.status == 200
            resp.read1(64)  # stream is live
            resp.close()  # drop the fd for real (see test_serve_cancellation)
            conn.close()

        threads = [threading.Thread(target=drop_one) for _ in range(N)]
        for t in threads:
            t.start()
            time.sleep(0.05)
        for t in threads:
            t.join(timeout=30.0)
        deadline = time.monotonic() + 20.0
        state = None
        while time.monotonic() < deadline:
            state = handle.call_sync({}, timeout_s=10.0)
            if state["interrupted"] >= N:
                break
            time.sleep(0.2)
        assert state and state["interrupted"] >= N, state
        assert state["completed"] == 0, state
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
