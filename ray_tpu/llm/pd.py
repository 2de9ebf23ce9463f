"""Prefill/decode disaggregation over the paged-KV shm transfer plane.

(reference: llm/_internal/serve/serving_patterns/prefill_decode/pd_server.py
— a PDProxyServer sends each request to a prefill deployment, transfers the
KV cache to a decode deployment (NIXL/LMCache over RDMA in the reference),
and streams tokens from the decoder.)

TPU mapping here:

- **PrefillServer** runs the prompt-only forward on a prefill-shaped mesh
  and exports the resulting KV as paged-KV **pages** through
  `ray_tpu/llm/kv_transfer.py` (per-ticket MutableShmChannel + sender
  thread). Its reply is a small **ticket** — the proxy never materializes
  KV.
- **DecodeServer** runs STREAMED admission: the ticket registers with the
  replica's shared `BatchedKVPuller` (one polling thread for every
  in-flight transfer) and the engine adopts pages into the paged pool AS
  THEY ARRIVE (`submit_prefilled(kv_stream=...)`) — the decode loop keeps
  stepping other slots while later pages stream, and the row activates on
  the last page. Tokens stream out as they are produced.
- **PDProxyServer** composes the two pools and **streams**: the decode
  call is a serve streaming handle, so the proxy forwards tokens as they
  arrive instead of blocking on the full completion, and reports
  first-token latency separately from completion latency.

Prefill and decode replicas must share a host (/dev/shm) — the on-pod PD
layout. ICI remote-DMA is the cross-host follow-on.
"""

from __future__ import annotations

import dataclasses
import logging
import time

import numpy as np

from ray_tpu import serve
from ray_tpu.exceptions import DeadlineExceededError
from ray_tpu.serve import replica as _replica
from ray_tpu.llm.config import LLMConfig, PDConfig
from ray_tpu.llm.engine import SamplingParams, bucket_for
from ray_tpu.llm.kv_transfer import (BatchedKVPuller, KVPageStream,
                                     PagedKVExporter, pull_all)
from ray_tpu.llm.tokenizer import load_tokenizer
from ray_tpu.serve import request_context as _rc
from ray_tpu.util import tracing as _tracing

logger = logging.getLogger(__name__)

_TTFT_BOUNDS = (0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                1.0, 2.5, 5.0, 10.0)


def _ttft_histogram():
    from ray_tpu.util import metrics as met

    return met.get_or_create(
        met.Histogram, "ray_tpu_llm_pd_ttft_seconds",
        "PD time-to-first-token split by phase (prefill: request->ticket; "
        "decode: dispatch->first decode-produced token)",
        boundaries=list(_TTFT_BOUNDS), tag_keys=("phase",))


def _pd_engine_kwargs(llm_config: LLMConfig) -> dict:
    """One normalization of engine_kwargs shared by BOTH pools, so prefill
    bucketing and the decode page pool can never disagree on shapes: PD
    defaults to pd_config.page_size, and min_bucket is bumped so every
    prompt bucket slices into whole pages."""
    pd = llm_config.pd_config or PDConfig()
    ek = dict(llm_config.engine_kwargs)
    ek.setdefault("page_size", pd.page_size)
    ek["min_bucket"] = max(ek.get("min_bucket", 32), ek["page_size"])
    return ek


class _PrefillJob:
    __slots__ = ("ids", "n", "bucket", "event", "logits", "k", "v", "error")

    def __init__(self, ids, n, bucket):
        import threading

        self.ids = ids
        self.n = n
        self.bucket = bucket
        self.event = threading.Event()
        self.logits = self.k = self.v = None
        self.error: BaseException | None = None


class PrefillCoalescer:
    """Admission batching for the dedicated prefill tier.

    Concurrent same-bucket prompts coalesce into ONE ``[B, T]``
    ``decoding.prefill_batch`` forward — the structural advantage of
    disaggregation the monolithic engine cannot copy: its prefills
    interleave with decode steps one prompt at a time. Baton-passing
    combiner, no dedicated thread: the first waiting caller becomes the
    leader, runs ONE batch (everything same-bucket queued at that
    moment, including its own job), releases leadership, and waiting
    callers promote themselves — batching emerges from bursts without
    adding a scheduling hop or an artificial wait (``window_s`` can add
    one for sparse arrivals). Each row's logits/KV are bit-identical to
    a solo ``[1, T]`` prefill — causality keeps rows independent."""

    def __init__(self, params, cfg, *, min_bucket: int, max_len: int,
                 max_batch: int = 4, window_s: float = 0.0):
        import threading

        self.params = params
        self.cfg = cfg
        self.min_bucket = min_bucket
        self.max_len = max_len
        self.max_batch = max(1, int(max_batch))
        self.window_s = float(window_s)
        self._cond = threading.Condition()
        self._pending: list = []
        self._leader_active = False
        self._stop = False
        self.batches = 0   # forwards run
        self.jobs = 0      # prompts served (jobs/batches = mean batch)

    def _run(self, batch: list) -> None:
        import jax.numpy as jnp

        from ray_tpu.models import decoding

        try:
            T = batch[0].bucket
            # prefill() floors the batch take to a power of two, so the
            # row count here is always one of O(log max_batch) shapes —
            # no pad rows, no wasted forward FLOPs
            tb = np.zeros((len(batch), T), np.int32)
            lens = np.zeros((len(batch),), np.int32)
            for b, j in enumerate(batch):
                tb[b, :j.n] = j.ids
                lens[b] = j.n
            logits, kv = decoding.prefill_batch(
                self.params, jnp.asarray(tb), jnp.asarray(lens), self.cfg)
            for b, j in enumerate(batch):
                j.logits = logits[b]
                j.k = kv["k"][:, b]
                j.v = kv["v"][:, b]
            self.batches += 1
            self.jobs += len(batch)
        except BaseException as e:  # noqa: BLE001 — the waiters MUST be
            # released with the failure, or every straggler hangs forever
            for j in batch:
                j.error = e
        finally:
            for j in batch:
                j.event.set()

    def prefill(self, token_ids: list):
        """Blocking: returns (logits_at_last [V], k [L, T, Hkv, Dh],
        v [L, T, Hkv, Dh], bucket) for this prompt, computed inside
        whichever coalesced forward picked the job up."""
        n = len(token_ids)
        job = _PrefillJob(token_ids, n, bucket_for(n, self.min_bucket,
                                                   self.max_len))
        with self._cond:
            if self._stop:
                raise RuntimeError("prefill coalescer is torn down")
            self._pending.append(job)
        while not job.event.is_set():
            with self._cond:
                while (not job.event.is_set() and self._leader_active
                       and not self._stop):
                    self._cond.wait(timeout=0.5)
                if job.event.is_set():
                    break
                if self._stop:
                    job.error = RuntimeError(
                        "prefill coalescer torn down mid-batch")
                    break
                self._leader_active = True
            try:
                if self.window_s:
                    time.sleep(self.window_s)  # sparse arrivals: wait a beat
                with self._cond:
                    batch = []
                    if self._pending:
                        bucket = self._pending[0].bucket  # FIFO fairness
                        group = [j for j in self._pending
                                 if j.bucket == bucket][:self.max_batch]
                        # floor power of two: prefill_batch compiles per
                        # pow2 row count, and padding 3→4 or 5→8 would
                        # BURN the rows batching is supposed to save —
                        # leftovers catch the next baton immediately
                        take = 1 << (len(group).bit_length() - 1)
                        batch = group[:take]
                        for j in batch:
                            self._pending.remove(j)
                if batch:
                    self._run(batch)
            finally:
                with self._cond:
                    self._leader_active = False
                    self._cond.notify_all()
        if job.error is not None:
            raise job.error
        return job.logits, job.k, job.v, job.bucket

    def teardown(self) -> None:
        """Fail queued jobs and refuse new ones. Safe to call twice."""
        with self._cond:
            self._stop = True
            pending, self._pending = self._pending, []
            self._cond.notify_all()
        for j in pending:
            j.error = RuntimeError("prefill coalescer torn down")
            j.event.set()


@serve.deployment(max_ongoing_requests=8)
class PrefillServer:
    """Prompt-only forward: pages the prefilled KV into the transfer plane
    and returns the ticket + the first sampled token."""

    def __init__(self, llm_config: LLMConfig):
        import jax

        from ray_tpu.models import decoding

        self.cfg, self.params = llm_config.build_model()
        if self.cfg.mla or self.cfg.n_dense_layers:
            raise NotImplementedError(
                "PD disaggregation moves per-head K and V pages of one kind "
                "of layer (prefill_batch, kv_transfer); a model with latent "
                "attention or leading dense layers is served by one engine")
        self._decoding = decoding
        self._jax = jax
        ek = _pd_engine_kwargs(llm_config)
        pd = llm_config.pd_config or PDConfig()
        self.page_size = ek["page_size"]
        self.min_bucket = max(ek.get("min_bucket", 32), self.page_size)
        self.max_len = ek.get("max_len", self.cfg.max_seq_len)
        import threading

        self.key = jax.random.PRNGKey(ek.get("seed", 0))
        # replica methods run on several threads, and the coalescer wakes
        # a whole batch of them at once: the read-split-write of the
        # shared key must be atomic or concurrent requests sample with
        # the SAME subkey (correlated first tokens)
        self._key_lock = threading.Lock()
        self.exporter = PagedKVExporter(
            send_timeout_s=pd.transfer_timeout_s,
            prefetch_pages=pd.prefetch_depth)
        # admission batching: concurrent prompts share one [B, T] forward
        self.coalescer = PrefillCoalescer(
            self.params, self.cfg, min_bucket=self.min_bucket,
            max_len=self.max_len, max_batch=pd.prefill_batch_max,
            window_s=pd.prefill_batch_window_s)

    def prefill(self, token_ids: list, temperature: float = 0.0) -> dict:
        """Returns the transfer TICKET (kv_transfer.py) — the KV itself
        streams page-by-page to whichever decode replica pulls it.
        Concurrent calls coalesce into one batched forward
        (PrefillCoalescer) before each row exports its own ticket."""
        jax, decoding = self._jax, self._decoding

        n = len(token_ids)
        bucket = bucket_for(n, self.min_bucket, self.max_len)
        if n > bucket:
            raise ValueError(f"prompt of {n} tokens exceeds max_len {self.max_len}")
        t0 = time.time()
        logits, k, v, bucket = self.coalescer.prefill(list(token_ids))
        with self._key_lock:
            self.key, sub = jax.random.split(self.key)
        first = int(decoding.sample(logits[None, :], sub, temperature)[0])
        _tracing.emit_child_span("pd:prefill_forward", t0, time.time(),
                                 tokens=n, bucket=bucket)
        # sampled requests: the sender thread runs outside the request's
        # contextvar scope, so its pd:kv_send span context rides the ticket
        return self.exporter.export(np.asarray(k), np.asarray(v),
                                    n, first, self.page_size,
                                    trace_ctx=_tracing.inject())

    def abort_transfer(self, ticket_id: str) -> None:
        """Best-effort: retire an exported ticket whose consumer went away
        (client disconnect before/while the decode side pulled) so the
        sender thread stops now instead of at its send timeout. A ticket
        another replica exported — or one already settled — is a no-op."""
        self.exporter.abort(ticket_id)

    def transfer_stats(self) -> dict:
        return {"pending_transfers": self.exporter.pending(),
                "failed_transfers": self.exporter.failures,
                "last_failure": self.exporter.last_failure,
                "page_size": self.page_size,
                "prefill_batches": self.coalescer.batches,
                "prefill_jobs": self.coalescer.jobs}

    def __del__(self):
        try:
            self.coalescer.teardown()
            self.exporter.teardown()
        except Exception:
            pass


@serve.deployment(max_ongoing_requests=8)
class DecodeServer:
    """Continues generation from a transferred paged-KV prefix, admitting
    pulled pages straight into the engine's continuous-batching slots."""

    def __init__(self, llm_config: LLMConfig):
        from ray_tpu.llm.engine import TPUEngine

        pd = llm_config.pd_config or PDConfig()
        cfg = dataclasses.replace(llm_config,
                                  engine_kwargs=_pd_engine_kwargs(llm_config))
        self.engine = TPUEngine.from_config(cfg)
        self.pull_timeout_s = pd.transfer_timeout_s
        # ONE polling thread multiplexes every in-flight transfer on this
        # replica (streamed admission); None = legacy pull-then-admit
        self.puller = BatchedKVPuller() if pd.batched_pull else None

    def decode_stream(self, ticket: dict, params: dict | None = None):
        """Generator over generated token ids: the transferred first token
        immediately (TTFT is not gated on the page transfer), then the
        engine's tokens as the decode loop produces them. The default
        path STREAMS admission: the ticket registers with the replica's
        batched puller and the engine adopts pages as they arrive, so
        decode of other slots overlaps this request's transfer and the
        slot activates on the last page. Transfer failures raise
        KVTransferError — a clean per-request error; the engine and the
        other in-flight requests keep serving.

        Sampled requests emit the decode-side phase spans here:
        ``pd:kv_transfer`` (the page pull), ``pd:admission`` (submit →
        slot bind, retroactive from the engine's request stamps) and
        ``pd:decode`` (first engine token → stream end)."""
        from ray_tpu.llm.engine import _iter_request
        from ray_tpu.llm.kv_transfer import pull_pages

        # capture: the generator body runs across many __next__ calls but
        # always on the activated task's thread — the captured context is
        # the one stable handle for retroactive span emission
        ctx = _tracing.current_context()
        sp = SamplingParams(**(params or {}))
        yield ticket["first_token"]
        if sp.max_tokens <= 1:
            # budget spent by the transferred token: drain the channel so
            # the prefill side retires it, but skip slot admission — via
            # the SAME batched puller (one wake serves this drain and
            # every live transfer), never the whole prefix in host memory
            if self.puller is not None:
                self.puller.drain(ticket, timeout_s=self.pull_timeout_s)
            else:
                for _ in pull_pages(ticket, timeout_s=self.pull_timeout_s):
                    pass
            return
        t_pull = time.time()
        deadline_ts = _replica.request_deadline() or 0.0
        if self.puller is not None:
            stream = KVPageStream(ticket["n_pages"], ticket["page_size"])
            self.puller.pull(ticket, stream, timeout_s=self.pull_timeout_s)
            req = self.engine.submit_prefilled(
                length=ticket["length"], first_token=ticket["first_token"],
                params=sp, kv_stream=stream, deadline_ts=deadline_ts)
        else:
            stream = None
            k_pages, v_pages = pull_all(ticket, timeout_s=self.pull_timeout_s)
            _tracing.emit_span_for(ctx, "pd:kv_transfer", t_pull, time.time(),
                                   ticket=ticket.get("ticket", ""),
                                   pages=ticket["n_pages"])
            req = self.engine.submit_prefilled(
                length=ticket["length"], first_token=ticket["first_token"],
                params=sp, k_pages=k_pages, v_pages=v_pages,
                deadline_ts=deadline_ts)

        fin = {"done": False}

        def _abort():
            """Reclaim BOTH planes mid-stream: the decode slot + granted
            KV pages (engine abort) and the in-flight page transfer
            (puller abort closes the channel, which also makes the
            prefill-side sender retire its ticket). Idempotent: finished
            requests no-op in both registries."""
            if fin["done"]:
                return
            try:
                self.engine.abort_request(req.rid)
                if self.puller is not None:
                    self.puller.abort(ticket.get("ticket", ""))
            finally:
                _rc.count_cancellation("pd")

        # serve-plane cancel (client disconnect seen by the proxy, explicit
        # cancel(), timed-out caller) lands here via the replica's holder
        _replica.on_cancel(_abort)
        n = 0
        t_dec = time.time()
        try:
            it = _iter_request(req)
            for tok in it:
                if n == 0 and ctx is not None:
                    if stream is not None:
                        # streamed path: the transfer overlapped decode;
                        # its span closes at the stream's last page
                        _tracing.emit_span_for(
                            ctx, "pd:kv_transfer", t_pull,
                            stream.finished_ts or time.time(),
                            ticket=ticket.get("ticket", ""),
                            pages=ticket["n_pages"])
                    if req.admitted_ts:
                        # the engine stamped the slot bind: emit the
                        # admission wait retroactively now that it is known
                        _tracing.emit_span_for(ctx, "pd:admission",
                                               req.submitted_ts,
                                               req.admitted_ts)
                n += 1
                yield tok
            fin["done"] = True
        finally:
            if not fin["done"]:
                # consumer abandoned the stream (GeneratorExit from the
                # replica's close()) or it failed mid-decode: reclaim now
                _abort()
            if ctx is not None:
                _tracing.emit_span_for(ctx, "pd:decode", t_dec, time.time(),
                                       tokens=n)

    def decode(self, ticket: dict, params: dict | None = None) -> list:
        """Blocking form (compat surface for non-streaming callers)."""
        return list(self.decode_stream(ticket, params))

    def engine_stats(self) -> dict:
        st = self.engine.stats()
        if self.puller is not None:
            st["pulls_in_flight"] = self.puller.pending()
        return st

    def __del__(self):
        try:
            if self.puller is not None:
                self.puller.teardown()
            self.engine.shutdown()
        except Exception:
            pass


@serve.deployment
class PDProxyServer:
    """(reference: pd_server.py PDProxyServer — composes the two pools.)

    The decode leg is a serve STREAMING handle: tokens forward as they are
    produced, first-token latency is measured (and exported per phase via
    ray_tpu_llm_pd_ttft_seconds) instead of being buried in one blocking
    result() call."""

    def __init__(self, prefill_handle, decode_handle, tokenizer_spec="byte",
                 request_timeout_s: float = 120.0):
        self.prefill = prefill_handle
        self.decode = decode_handle
        self.tokenizer = load_tokenizer(tokenizer_spec)
        self.request_timeout_s = request_timeout_s
        self._m_ttft = _ttft_histogram()

    def _pump(self, body: dict, timing: dict):
        """Drive one request through both pools, yielding token ids as they
        arrive; `timing` is filled with the latency split for `usage`."""
        ids = self.tokenizer.encode(body.get("prompt", ""))
        timing["prompt_tokens"] = len(ids)
        t0 = time.monotonic()
        w0 = time.time()
        # the proxy's own request deadline (set by the HTTP ingress) rides
        # into both pools; each leg's blocking wait is clamped to the
        # remaining budget so a queued prefill can't eat the decode's time
        deadline_ts = _replica.request_deadline()
        budget_s = self.request_timeout_s
        if deadline_ts:
            rem = _rc.deadline_remaining(deadline_ts)
            if rem is not None:
                if rem <= 0:
                    _rc.count_cancellation("pd")
                    raise DeadlineExceededError(
                        "pd proxy: deadline expired before prefill dispatch")
                budget_s = min(budget_s, rem)
        ticket = self.prefill.prefill.remote(
            ids, float(body.get("temperature", 0.0)),
            _deadline_ts=deadline_ts,
        ).result(timeout_s=budget_s)
        # the first token is sampled BY prefill and rides the ticket: its
        # arrival is the request's time-to-first-token
        timing["ttft_s"] = time.monotonic() - t0
        self._m_ttft.observe(timing["ttft_s"], tags={"phase": "prefill"})
        _tracing.emit_child_span("pd:prefill", w0, w0 + timing["ttft_s"],
                                 prompt_tokens=len(ids))
        t1 = time.monotonic()
        w1 = time.time()
        stream = self.decode.options(
            stream=True, stream_item_timeout_s=self.request_timeout_s,
        ).decode_stream.remote(
            ticket, {"max_tokens": int(body.get("max_tokens", 32)),
                     "temperature": float(body.get("temperature", 0.0))},
            _deadline_ts=deadline_ts)
        finished = False
        try:
            for i, tok in enumerate(stream):
                if i == 1:
                    # first DECODE-produced token: page pull + slot admission
                    # + one decode step — the decode half of the TTFT split
                    decode_ttft = time.monotonic() - t1
                    timing["decode_ttft_s"] = decode_ttft
                    self._m_ttft.observe(decode_ttft, tags={"phase": "decode"})
                    _tracing.emit_child_span("pd:decode_first_token", w1,
                                             w1 + decode_ttft)
                yield tok
            finished = True
        finally:
            if not finished:
                # abandoned mid-decode (client gone) or failed: cancel the
                # decode replica's stream (which aborts the engine request
                # and the page pull) and best-effort retire the exported
                # ticket on the prefill tier so its sender stops too
                cancel = getattr(stream, "cancel", None)
                if cancel is not None:
                    try:
                        cancel()
                    except Exception as e:  # noqa: BLE001 — best-effort
                        logger.debug("pd decode-stream cancel failed: %r", e)
                try:
                    self.prefill.abort_transfer.remote(
                        ticket.get("ticket", ""))
                except Exception as e:  # noqa: BLE001 — best-effort
                    logger.debug("pd prefill abort_transfer failed: %r", e)
        timing["total_time_s"] = time.monotonic() - t0

    def _usage(self, timing: dict, n_out: int) -> dict:
        return {"prompt_tokens": timing.get("prompt_tokens", 0),
                "completion_tokens": n_out,
                # first-token latency reported SEPARATELY from completion
                "ttft_s": round(timing.get("ttft_s", 0.0), 4),
                "total_time_s": round(timing.get("total_time_s", 0.0), 4)}

    def _record(self, request: dict, timing: dict, t0: float,
                n_out: int, status) -> None:
        """PD-phase flight-recorder entry: richer than the HTTP proxy's
        (prefill vs decode TTFT split), same ring/GCS log."""
        rec = {"request_id": request.get("request_id") or _rc.new_request_id(),
               "component": "pd_proxy", "ts": time.time(),
               "phases": {"prefill": round(timing.get("ttft_s", 0.0), 6),
                          "decode_first_token": round(
                              timing.get("decode_ttft_s", 0.0), 6)},
               "completion_tokens": n_out}
        _rc.record_request(rec, t0, status=status)

    def __call__(self, request: dict) -> dict:
        body = request.get("body") or request
        timing: dict = {}
        t0 = time.perf_counter()
        status = "error"
        out_ids: list = []
        try:
            out_ids = list(self._pump(body, timing))
            status = 200
        finally:
            # failed requests (KVTransferError, replica death) are exactly
            # the ones the flight recorder must explain — record either way
            self._record(request, timing, t0, len(out_ids), status)
        return {"choices": [{"index": 0,
                             "text": self.tokenizer.decode(out_ids),
                             "finish_reason": "stop"}],
                "usage": self._usage(timing, len(out_ids))}

    def stream_request(self, request: dict):
        """Streaming HTTP entry (SSE through the proxy): one chunk per
        token, then a final usage-bearing chunk — parity with
        LLMServer.stream_request."""
        body = request.get("body") or request
        timing: dict = {}
        n = 0
        t0 = time.perf_counter()
        status = "aborted"  # GeneratorExit (client gone) or mid-stream error
        gen = self._pump(body, timing)
        try:
            for tok in gen:
                n += 1
                yield {"object": "text_completion.chunk",
                       "choices": [{"index": 0,
                                    "text": self.tokenizer.decode([tok]),
                                    "finish_reason": None}]}
            status = "stream"
        finally:
            # explicit close: on abandonment the pump's finally must run
            # NOW (cancel the decode stream, retire the prefill ticket),
            # not whenever the suspended frame gets collected
            gen.close()
            self._record(request, timing, t0, n, status)
        yield {"object": "text_completion.chunk",
               "choices": [{"index": 0, "text": "", "finish_reason": "stop"}],
               "usage": self._usage(timing, n)}


def build_pd_openai_app(llm_config: LLMConfig) -> serve.Application:
    pd = llm_config.pd_config or PDConfig()
    actor_opts = llm_config.replica_actor_options()
    prefill = PrefillServer.options(
        num_replicas=pd.num_prefill_replicas,
        ray_actor_options=actor_opts).bind(llm_config)
    decode = DecodeServer.options(
        num_replicas=pd.num_decode_replicas,
        ray_actor_options=actor_opts).bind(llm_config)
    return PDProxyServer.bind(
        prefill, decode,
        llm_config.model_loading_config.tokenizer or "byte")
