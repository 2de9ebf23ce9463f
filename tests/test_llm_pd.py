"""PD disaggregation tests: page-granular KV handoff over shm channels.

Covers the kv_transfer plane (ticket/pull protocol, teardown hygiene,
mid-transfer death) and the engine's page-granular submit_prefilled
(decode-slot admission, token-exactness vs the monolithic engine).
Serve-level composition is covered by tests/test_llm.py
test_pd_disaggregation; everything here is engine/plane-level and fast.
"""

import glob
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu._private.constants import SHM_CHANNEL_GLOB
from ray_tpu.llm.engine import SamplingParams, TPUEngine, bucket_for
from ray_tpu.llm.kv_transfer import (BatchedKVPuller, KVPageStream,
                                     KVTransferError, PagedKVExporter,
                                     pull_all, pull_pages)
from ray_tpu.models import decoding, transformer
from ray_tpu.models.transformer import TransformerConfig

pytestmark = pytest.mark.pd

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


def _prefill_ticket(cfg, params, prompt, exporter, *, page_size=PAGE,
                    min_bucket=PAGE, max_len=MAX_LEN):
    """The prefill half of the PD path, serve-free: prompt forward →
    greedy first token → page export."""
    n = len(prompt)
    bucket = bucket_for(n, min_bucket, max_len)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :n] = prompt
    logits, kv = decoding.prefill(params, jnp.asarray(padded),
                                  jnp.int32(n), cfg)
    first = int(jnp.argmax(logits))
    return exporter.export(np.asarray(kv["k"]), np.asarray(kv["v"]),
                           n, first, page_size)


def _shm_channels() -> set:
    return set(glob.glob(SHM_CHANNEL_GLOB))


def _wait(pred, timeout_s=5.0):
    deadline = time.monotonic() + timeout_s
    while not pred():
        if time.monotonic() > deadline:
            return False
        time.sleep(0.02)
    return True


def test_pd_page_handoff_token_exact(tiny_model):
    """The acceptance bar: prefill → page export → shm pull → page-granular
    slot admission produces EXACTLY the monolithic engine's tokens."""
    cfg, params = tiny_model
    mono = _paged_engine(cfg, params)
    dec = _paged_engine(cfg, params)
    exporter = PagedKVExporter(send_timeout_s=10.0)
    sp = SamplingParams(max_tokens=8, temperature=0.0)
    try:
        for prompt in ([1, 5, 9, 2, 7], [3] * 20, list(range(2, 35))):
            want = mono.generate(prompt, sp)
            ticket = _prefill_ticket(cfg, params, prompt, exporter)
            assert ticket["n_pages"] == bucket_for(
                len(prompt), PAGE, MAX_LEN) // PAGE
            k_pages, v_pages = pull_all(ticket, timeout_s=10.0)
            assert all(p.shape[1] == PAGE for p in k_pages)
            req = dec.submit_prefilled(
                length=ticket["length"], first_token=ticket["first_token"],
                params=sp, k_pages=k_pages, v_pages=v_pages)
            got = [ticket["first_token"]] + list(req)
            assert got == want
    finally:
        exporter.teardown()
        mono.shutdown()
        dec.shutdown()


def test_pd_transfer_metrics_counted(tiny_model):
    from ray_tpu.util import metrics as met

    cfg, params = tiny_model
    exporter = PagedKVExporter(send_timeout_s=10.0)
    try:
        ticket = _prefill_ticket(cfg, params, list(range(1, 20)), exporter)
        pull_all(ticket, timeout_s=10.0)
        by_name = {m["name"]: m for m in met.snapshot()}
        pages = sum(v for _t, v in
                    by_name["ray_tpu_llm_pd_kv_pages_total"]["series"])
        bytes_ = sum(v for _t, v in
                     by_name["ray_tpu_llm_pd_transfer_bytes_total"]["series"])
        assert pages >= ticket["n_pages"]
        assert bytes_ > 0
    finally:
        exporter.teardown()


def test_decode_slot_admission_under_concurrency(tiny_model):
    """More transferred requests than decode slots AND a page pool too
    small to host them all at once: the backlog/requeue path must drain
    everything, token-exactly, without cross-contamination."""
    cfg, params = tiny_model
    mono = _paged_engine(cfg, params)
    # 2 slots, pool of 5 usable pages; each request needs 2 → at most two
    # resident, the rest ride the backlog
    dec = _paged_engine(cfg, params, max_slots=2, num_pages=6)
    exporter = PagedKVExporter(send_timeout_s=30.0)
    sp = SamplingParams(max_tokens=8, temperature=0.0)
    prompts = [[i + 1] * 20 for i in range(6)]
    try:
        want = [mono.generate(p, sp) for p in prompts]
        got = [None] * len(prompts)

        def run(i):
            ticket = _prefill_ticket(cfg, params, prompts[i], exporter)
            k_pages, v_pages = pull_all(ticket, timeout_s=30.0)
            req = dec.submit_prefilled(
                length=ticket["length"], first_token=ticket["first_token"],
                params=sp, k_pages=k_pages, v_pages=v_pages)
            got[i] = [ticket["first_token"]] + list(req)

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert got == want
        st = dec.stats()
        assert st["active"] == 0 and st["free_pages"] == 5
    finally:
        exporter.teardown()
        mono.shutdown()
        dec.shutdown()


def test_transfer_plane_teardown_no_shm_leaks(tiny_model):
    """Completed, never-pulled, and aborted transfers must all retire
    their /dev/shm segments."""
    cfg, params = tiny_model
    before = _shm_channels()
    exporter = PagedKVExporter(send_timeout_s=30.0)
    # short-fuse exporter ONLY for the never-pulled leg — the completed
    # transfer must not share its timeout (a >0.5s CI stall mid-pull would
    # otherwise retire the channel under the puller: an unrelated flake)
    impatient = PagedKVExporter(send_timeout_s=0.5)
    prompt = list(range(1, 20))
    # completed transfer
    t1 = _prefill_ticket(cfg, params, prompt, exporter)
    pull_all(t1, timeout_s=10.0)
    # never pulled: the sender times out (0.5s) and unlinks on its own
    _prefill_ticket(cfg, params, prompt, impatient)
    # aborted mid-flight
    t3 = _prefill_ticket(cfg, params, prompt, exporter)
    exporter.abort(t3["ticket"])
    assert _wait(lambda: exporter.pending() == 0)
    assert _wait(lambda: impatient.pending() == 0)
    exporter.teardown()
    impatient.teardown()
    assert _wait(lambda: _shm_channels() - before == set()), \
        f"leaked: {_shm_channels() - before}"


def test_prefill_death_mid_transfer_clean_error(tiny_model):
    """A prefill replica dying mid-transfer surfaces as KVTransferError
    naming the ticket — a per-REQUEST failure; the decode engine and other
    requests keep serving."""
    cfg, params = tiny_model
    dec = _paged_engine(cfg, params)
    exporter = PagedKVExporter(send_timeout_s=10.0)
    sp = SamplingParams(max_tokens=8, temperature=0.0)
    try:
        # prompt spanning several pages so the abort lands mid-stream
        ticket = _prefill_ticket(cfg, params, list(range(1, 40)), exporter)
        assert ticket["n_pages"] >= 3
        pulled = []
        with pytest.raises(KVTransferError) as ei:
            for i, kp, vp in pull_pages(ticket, timeout_s=10.0):
                pulled.append(i)
                if len(pulled) == 1:
                    exporter.abort(ticket["ticket"])  # replica death
        assert ticket["ticket"] in str(ei.value)
        assert len(pulled) < ticket["n_pages"]

        # a ticket whose channel is already gone (replica restarted):
        with pytest.raises(KVTransferError, match="not found"):
            list(pull_pages({**ticket, "ticket": "tkt2",
                             "path": "/dev/shm/rtpu_chan_gone"}, 1.0))

        # the decode pool is unharmed: a fresh request serves end-to-end
        mono = _paged_engine(cfg, params)
        want = mono.generate([1, 5, 9], sp)
        mono.shutdown()
        t2 = _prefill_ticket(cfg, params, [1, 5, 9], exporter)
        k_pages, v_pages = pull_all(t2, timeout_s=10.0)
        req = dec.submit_prefilled(
            length=t2["length"], first_token=t2["first_token"], params=sp,
            k_pages=k_pages, v_pages=v_pages)
        assert [t2["first_token"]] + list(req) == want
    finally:
        exporter.teardown()
        dec.shutdown()


def test_submit_prefilled_exact_fit_and_validation(tiny_model):
    """The off-by-one: length + max_tokens == max_len EXACTLY fits; one
    past it is rejected. Mixed/mismatched page forms are rejected."""
    cfg, params = tiny_model
    dec = _paged_engine(cfg, params)
    exporter = PagedKVExporter(send_timeout_s=10.0)
    try:
        prompt = [1, 5, 9, 2, 7]
        ticket = _prefill_ticket(cfg, params, prompt, exporter)
        k_pages, v_pages = pull_all(ticket, timeout_s=10.0)
        n = ticket["length"]
        req = dec.submit_prefilled(
            length=n, first_token=ticket["first_token"],
            params=SamplingParams(max_tokens=MAX_LEN - n),
            k_pages=k_pages, v_pages=v_pages)
        out = [ticket["first_token"]] + list(req)
        assert len(out) == MAX_LEN - n
        with pytest.raises(ValueError, match="does not fit"):
            dec.submit_prefilled(
                length=n, first_token=0,
                params=SamplingParams(max_tokens=MAX_LEN - n + 1),
                k_pages=k_pages, v_pages=v_pages)
        # whole arrays are no form of input: nothing is taken by position
        with pytest.raises(TypeError, match="positional"):
            dec.submit_prefilled(k_pages[0], v_pages[0], n, 0)
        with pytest.raises(ValueError, match="equal-length"):
            dec.submit_prefilled(length=n, first_token=0,
                                 k_pages=k_pages, v_pages=[])
    finally:
        exporter.teardown()
        dec.shutdown()


def test_streamed_admission_token_exact_partial_pages(tiny_model):
    """Tentpole acceptance: a SLOW sender streams pages while the decode
    engine keeps emitting tokens for another request — and the slow
    request's output is still token-exact. The fast request must finish
    while the slow transfer is still open (the overlap, observed)."""
    cfg, params = tiny_model
    mono = _paged_engine(cfg, params)
    dec = _paged_engine(cfg, params)
    # one page per message, 120ms apart: a 4-page transfer stays open
    # ~0.5s while decode runs
    slow = PagedKVExporter(send_timeout_s=30.0, prefetch_pages=1,
                          page_interval_s=0.12)
    puller = BatchedKVPuller()
    sp = SamplingParams(max_tokens=8, temperature=0.0)
    prompt = list(range(2, 50))
    fast_prompt = [1, 5, 9]
    try:
        want = mono.generate(prompt, sp)
        fast_want = mono.generate(fast_prompt,
                                  SamplingParams(max_tokens=6,
                                                 temperature=0.0))
        # warm the decode engine's compiles so the fast request's wall
        # time below measures steady state, not XLA compilation
        dec.generate(fast_prompt, SamplingParams(max_tokens=2,
                                                 temperature=0.0))

        ticket = _prefill_ticket(cfg, params, prompt, slow)
        assert ticket["n_pages"] >= 3 and not ticket.get("sync")
        stream = KVPageStream(ticket["n_pages"], ticket["page_size"])
        puller.pull(ticket, stream, timeout_s=30.0)
        req = dec.submit_prefilled(
            length=ticket["length"], first_token=ticket["first_token"],
            params=sp, kv_stream=stream)
        # while pages stream, a fresh request decodes end-to-end
        fast = dec.submit(fast_prompt, SamplingParams(max_tokens=6,
                                                      temperature=0.0))
        fast_got = list(fast)
        fast_done_ts = time.time()
        assert fast_got == fast_want
        got = [ticket["first_token"]] + list(req)
        assert got == want
        # the overlap really happened: the fast request finished before
        # the slow transfer delivered its last page
        assert stream.finished_ts is not None
        assert fast_done_ts < stream.finished_ts, \
            "decode did not emit while pages were still streaming"
        st = dec.stats()
        assert st["streaming"] == 0 and st["active"] == 0
    finally:
        slow.teardown()
        puller.teardown()
        mono.shutdown()
        dec.shutdown()


def test_prefill_death_mid_stream_after_first_page(tiny_model):
    """Prefill dies AFTER the first page was admitted into the slot: the
    request fails with a per-request KVTransferError, the slot and every
    granted page are reclaimed, no /dev/shm leaks, and the engine keeps
    serving."""
    cfg, params = tiny_model
    before = _shm_channels()
    dec = _paged_engine(cfg, params)
    slow = PagedKVExporter(send_timeout_s=30.0, prefetch_pages=1,
                          page_interval_s=0.1)
    exporter = PagedKVExporter(send_timeout_s=10.0)
    puller = BatchedKVPuller()
    sp = SamplingParams(max_tokens=8, temperature=0.0)
    try:
        free_pages0 = dec.stats()["free_pages"]
        ticket = _prefill_ticket(cfg, params, list(range(2, 50)), slow)
        stream = KVPageStream(ticket["n_pages"], ticket["page_size"])
        puller.pull(ticket, stream, timeout_s=30.0)
        req = dec.submit_prefilled(
            length=ticket["length"], first_token=ticket["first_token"],
            params=sp, kv_stream=stream)
        assert _wait(lambda: stream.fed >= 1)
        slow.abort(ticket["ticket"])  # the replica "dies" mid-stream
        with pytest.raises(KVTransferError) as ei:
            list(req)
        assert ticket["ticket"] in str(ei.value)
        # slot + granted pages reclaimed
        assert _wait(lambda: dec.stats()["streaming"] == 0)
        st = dec.stats()
        assert st["active"] == 0
        assert st["free_slots"] == st["max_slots"]
        assert st["free_pages"] == free_pages0
        # the engine keeps serving (streamed path)
        mono = _paged_engine(cfg, params)
        want = mono.generate([1, 5, 9], sp)
        mono.shutdown()
        t2 = _prefill_ticket(cfg, params, [1, 5, 9], exporter)
        s2 = KVPageStream(t2["n_pages"], t2["page_size"])
        puller.pull(t2, s2, timeout_s=10.0)
        req2 = dec.submit_prefilled(
            length=t2["length"], first_token=t2["first_token"], params=sp,
            kv_stream=s2)
        assert [t2["first_token"]] + list(req2) == want
        assert _wait(lambda: slow.pending() == 0)
        assert _wait(lambda: exporter.pending() == 0)
    finally:
        slow.teardown()
        exporter.teardown()
        puller.teardown()
        dec.shutdown()
    assert _wait(lambda: _shm_channels() - before == set()), \
        f"leaked: {_shm_channels() - before}"


def test_batched_puller_multiplexes_concurrent_transfers(tiny_model):
    """One puller drives N concurrent transfers (one polling thread, not
    N parked readers) and the warm-path drain retires a ticket without
    adopting it."""
    cfg, params = tiny_model
    before = _shm_channels()
    # force the threaded (non-sync) path so the puller actually
    # multiplexes live channels
    exporter = PagedKVExporter(send_timeout_s=30.0, prefetch_pages=1,
                               page_interval_s=0.01)
    puller = BatchedKVPuller()
    prompts = [[i + 1] * 40 for i in range(4)]
    try:
        tickets = [_prefill_ticket(cfg, params, p, exporter)
                   for p in prompts]
        streams = [KVPageStream(t["n_pages"], t["page_size"])
                   for t in tickets]
        for t, s in zip(tickets, streams):
            puller.pull(t, s, timeout_s=30.0)
        assert _wait(lambda: all(s.finished_ts for s in streams))
        # pages arrived complete and in-order per ticket
        for t, s in zip(tickets, streams):
            got = sorted(i for i, _k, _v in s.take_ready())
            assert got == list(range(t["n_pages"]))
        assert puller.pending() == 0
        # warm path: drain without adopting — sender retires the channel
        t = _prefill_ticket(cfg, params, prompts[0], exporter)
        puller.drain(t, timeout_s=30.0)
        assert _wait(lambda: exporter.pending() == 0)
    finally:
        exporter.teardown()
        puller.teardown()
    assert _wait(lambda: _shm_channels() - before == set())


def test_transfer_roundtrip_bfloat16():
    """The TPU KV dtype crosses the raw wire bit-exactly: ml_dtypes
    bfloat16 has no buffer protocol of its own, so the frame must route
    through the uint8 reinterpret on BOTH the sync and threaded paths."""
    import ml_dtypes

    bf16 = np.dtype(ml_dtypes.bfloat16)
    rng = np.random.default_rng(0)
    k = rng.standard_normal((2, 32, 2, 16)).astype(bf16)
    v = rng.standard_normal((2, 32, 2, 16)).astype(bf16)
    sync_ex = PagedKVExporter(send_timeout_s=10.0)
    slow_ex = PagedKVExporter(send_timeout_s=10.0, prefetch_pages=1,
                              page_interval_s=0.01)  # forces threaded
    puller = BatchedKVPuller()
    try:
        t = sync_ex.export(k, v, 20, 7, 16)
        assert t["sync"]
        kp, vp = pull_all(t, timeout_s=10.0)
        assert kp[0].dtype == bf16
        for i in range(t["n_pages"]):
            assert np.array_equal(kp[i], k[:, i * 16:(i + 1) * 16])
            assert np.array_equal(vp[i], v[:, i * 16:(i + 1) * 16])
        t2 = slow_ex.export(k, v, 20, 7, 16)
        assert not t2["sync"]
        stream = KVPageStream(t2["n_pages"], 16)
        puller.pull(t2, stream, timeout_s=10.0)
        assert _wait(lambda: stream.finished_ts is not None)
        for i, kpage, _vpage in sorted(stream.take_ready()):
            assert np.array_equal(kpage, k[:, i * 16:(i + 1) * 16])
    finally:
        sync_ex.teardown()
        slow_ex.teardown()
        puller.teardown()


def test_submit_prefilled_kv_stream_validation(tiny_model):
    cfg, params = tiny_model
    dec = _paged_engine(cfg, params)
    try:
        stream = KVPageStream(2, PAGE)
        with pytest.raises(ValueError, match="kv_stream alone"):
            dec.submit_prefilled(length=5, first_token=0,
                                 k_pages=[None], v_pages=[None],
                                 kv_stream=stream)
        with pytest.raises(ValueError, match="must agree"):
            dec.submit_prefilled(length=5, first_token=0,
                                 kv_stream=KVPageStream(2, PAGE * 2))
    finally:
        dec.shutdown()


def _cut_into_pages(a, page_size=PAGE) -> list:
    """A prefill's [L, T, Hkv, Dh] array as the transfer plane sends it."""
    return [a[:, i:i + page_size] for i in range(0, a.shape[1], page_size)]


def test_submit_prefilled_whole_arrays_land_in_pages(tiny_model):
    """The decode engine takes pages, whoever cut them: the ones pulled off
    the transfer plane, and a prefill's whole arrays cut here — both
    token-exact."""
    cfg, params = tiny_model
    slot_ref = _paged_engine(cfg, params, max_slots=2)
    dec = _paged_engine(cfg, params, max_slots=2)
    exporter = PagedKVExporter(send_timeout_s=10.0)
    sp = SamplingParams(max_tokens=8, temperature=0.0)
    prompt = [1, 5, 9, 2, 7]
    try:
        want = slot_ref.generate(prompt, sp)
        ticket = _prefill_ticket(cfg, params, prompt, exporter)
        k_pages, v_pages = pull_all(ticket, timeout_s=10.0)
        req = dec.submit_prefilled(
            length=ticket["length"], first_token=ticket["first_token"],
            params=sp, k_pages=k_pages, v_pages=v_pages)
        assert [ticket["first_token"]] + list(req) == want
        k = np.concatenate(k_pages, axis=1)
        v = np.concatenate(v_pages, axis=1)
        req = dec.submit_prefilled(
            length=ticket["length"], first_token=ticket["first_token"], params=sp,
            k_pages=_cut_into_pages(k), v_pages=_cut_into_pages(v))
        assert [ticket["first_token"]] + list(req) == want
    finally:
        exporter.teardown()
        slot_ref.shutdown()
        dec.shutdown()
