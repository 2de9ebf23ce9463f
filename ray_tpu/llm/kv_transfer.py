"""Paged-KV transfer plane for PD disaggregation.

The prefill→decode handoff moves the prefilled KV prefix at paged-KV
**page granularity** over `MutableShmChannel` — the compiled-DAG plane's
seqlock shm transport, reused — with a ticket/pull protocol:

- the prefill side computes the prompt KV, slices it into
  ``[L, page_size, Hkv, Dh]`` pages, and ``export()``s them: a per-ticket
  shm channel is created and a sender streams pages into it in messages
  of up to ``prefetch_pages`` pages (the seqlock write blocks until the
  reader consumed the previous message, so at most one message — the
  prefetch window — is in flight per transfer: natural backpressure, no
  buffering tier). A prefix that fits ONE message is written
  synchronously in ``export()`` itself ("sync" tickets — no sender
  thread at all; the reader retires the channel);
- the proxy only ever sees the **ticket** (a small dict: channel path,
  page count, shapes, first token) — it never materializes KV;
- the decode side attaches by path. The streamed-admission path
  registers the ticket with a ``BatchedKVPuller`` — ONE polling thread
  multiplexes every in-flight transfer, so N concurrent pulls cost one
  channel wake per cycle, not N — which feeds a ``KVPageStream`` the
  engine adopts pages from AS THEY ARRIVE (page-granular
  ``write_kv_pages``; the decode loop keeps stepping other slots while
  later pages stream). ``pull_pages()``/``pull_all()`` remain as the
  blocking single-ticket surface.

Page bytes cross the channel RAW (vectored writes + zero-copy read
views; pickle only frames the tiny per-message header), so a page costs
one memcpy per side.

Both ends must share one host (/dev/shm), which is the on-pod PD layout:
prefill and decode replicas co-locate per host and the proxy fans out
across hosts. Cross-host transfer is the ICI/RDMA follow-on.

(reference: llm/_internal/serve/serving_patterns/prefill_decode/pd_server.py
— the PDProxyServer + NIXL/LMCache KV-transfer pattern; here the transport
is the repo's own mutable-shm channel instead of RDMA, and the unit is the
paged-KV page so decode admission needs no reshape.)
"""

from __future__ import annotations

import logging
import struct
import threading
import uuid

import numpy as np

logger = logging.getLogger(__name__)

from ray_tpu.experimental.channel.channel import ChannelClosed
from ray_tpu.experimental.channel.mutable_shm import (MutableShmChannel,
                                                      create_mutable_channel)

# framing slack per page message (length prefix + pickled header); the
# payload itself is raw page bytes written vectored into the channel
_WIRE_SLACK = 8192

_LEN = struct.Struct("<q")


def _raw_bytes(a: np.ndarray):
    """Zero-copy byte view of a C-contiguous array. Routed through a
    uint8 reinterpret because extension dtypes (ml_dtypes bfloat16 —
    the TPU KV dtype) have no buffer protocol of their own."""
    return memoryview(a.view(np.uint8).reshape(-1))


def _pack_page_message(start: int, kps: list, vps: list) -> list:
    """Raw frame for one transfer message: [len][pickled tiny header]
    [k0][v0][k1][v1]... — page bytes go into the channel VECTORED
    (MutableShmChannel.write_vectored), never through pickle, so a page
    crosses the wire with exactly one memcpy per side."""
    import pickle

    hdr = pickle.dumps({"i": int(start), "n": len(kps),
                        "shape": tuple(kps[0].shape),
                        "dtype": kps[0].dtype},
                       protocol=pickle.HIGHEST_PROTOCOL)
    parts = [_LEN.pack(len(hdr)), hdr]
    for kp, vp in zip(kps, vps):
        parts.append(_raw_bytes(kp))
        parts.append(_raw_bytes(vp))
    return parts


def _unpack_page_view(view):
    """Parse one raw page message. The returned arrays VIEW the channel
    buffer — the caller must copy what it keeps BEFORE ack_read()."""
    import pickle

    (hlen,) = _LEN.unpack_from(view, 0)
    meta = pickle.loads(view[_LEN.size:_LEN.size + hlen])
    shape = meta["shape"]
    dt = np.dtype(meta["dtype"])
    count = 1
    for d in shape:
        count *= d
    nb = count * dt.itemsize
    off = _LEN.size + hlen
    kps, vps = [], []
    for _ in range(meta["n"]):
        kps.append(np.frombuffer(view, dt, count=count,
                                 offset=off).reshape(shape))
        off += nb
        vps.append(np.frombuffer(view, dt, count=count,
                                 offset=off).reshape(shape))
        off += nb
    return meta["i"], kps, vps


class KVTransferError(RuntimeError):
    """A KV handoff failed mid-flight: the per-REQUEST failure (the other
    transfers and both replica pools keep serving)."""


def _metrics():
    from ray_tpu.util import metrics as met

    return (
        met.get_or_create(
            met.Counter, "ray_tpu_llm_pd_transfer_bytes_total",
            "KV bytes moved prefill->decode over the shm transfer plane"),
        met.get_or_create(
            met.Counter, "ray_tpu_llm_pd_kv_pages_total",
            "KV pages moved prefill->decode over the shm transfer plane"),
    )


def _prefetch_metric():
    from ray_tpu.util import metrics as met

    return met.get_or_create(
        met.Counter, "ray_tpu_llm_pd_pages_prefetched_total",
        "KV pages pulled onto the decode host ahead of slot activation "
        "(streamed admission: batched puller + inline sync pulls)")


class _Transfer:
    __slots__ = ("ticket_id", "channel", "thread", "failed", "trace_ctx",
                 "created")

    def __init__(self, ticket_id: str, channel: MutableShmChannel,
                 trace_ctx: dict | None = None):
        import time as _time

        self.ticket_id = ticket_id
        self.channel = channel
        self.thread: threading.Thread | None = None  # None = sync transfer
        self.failed: str | None = None
        # sampled request's span context, captured at export: the sender
        # thread runs outside the request's contextvar scope
        self.trace_ctx = trace_ctx
        self.created = _time.monotonic()


class PagedKVExporter:
    """Prefill-side registry of in-flight page transfers.

    ``export()`` returns the ticket immediately. A prefix that fits one
    message ("sync") is written in the caller's thread — the reader
    retires the channel, and ``_reap_settled`` sweeps never-pulled ones.
    Larger transfers stream from a REUSED sender pool and retire their
    channel after a ``wait_drained`` barrier. A receiver that never
    attaches, or dies mid-pull, times the sender out after
    ``send_timeout_s`` — the channel is torn down either way, so
    /dev/shm can't accumulate segments.
    """

    def __init__(self, *, send_timeout_s: float = 60.0,
                 prefetch_pages: int = 2, page_interval_s: float = 0.0):
        self.send_timeout_s = float(send_timeout_s)
        # pages per channel message: the transfer's in-flight window. >1
        # amortizes the seqlock handshake + header framing over several
        # pages at the cost of prefetch_pages*page_bytes of channel buffer
        self.prefetch_pages = max(1, int(prefetch_pages))
        # pacing injection between messages (tests/benchmarks: a "slow
        # sender" proves decode keeps emitting under partial admission)
        self.page_interval_s = float(page_interval_s)
        self._live: dict[str, _Transfer] = {}
        self._lock = threading.Lock()
        # one self-rescheduling timer reaps never-pulled SYNC channels
        # even on an idle exporter (threaded senders time out on their
        # own thread; sync transfers have no thread to do it)
        self._reap_timer: threading.Timer | None = None
        self._torn_down = False
        self._m_bytes, self._m_pages = _metrics()
        self.failures = 0        # transfers that did not complete
        self.last_failure = ""   # "<ticket>: <reason>" for triage

    # ------------------------------------------------------------- export

    def export(self, k: np.ndarray, v: np.ndarray, length: int,
               first_token: int, page_size: int,
               trace_ctx: dict | None = None) -> dict:
        """Slice a bucketed prompt KV (``[L, T, Hkv, Dh]``, T a multiple of
        ``page_size``) into pages and start streaming them. Returns the
        ticket the proxy forwards to the decode pool. ``trace_ctx`` (a
        sampled request's span context) makes the sender emit a
        ``pd:kv_send`` span covering the whole transfer."""
        k = np.asarray(k)
        v = np.asarray(v)
        L, T = k.shape[0], k.shape[1]
        if page_size <= 0 or T % page_size:
            raise ValueError(
                f"prefill bucket {T} is not a multiple of page_size "
                f"{page_size}: configure the prefill server with "
                f"min_bucket >= page_size")
        n_pages = T // page_size
        depth = min(self.prefetch_pages, n_pages)
        page_bytes = (k.nbytes + v.nbytes) // n_pages
        tid = uuid.uuid4().hex[:16]
        self._reap_settled()
        ch = create_mutable_channel(depth * page_bytes + _WIRE_SLACK)
        # whole prefix in ONE message: write it NOW in the caller's thread
        # (a fresh channel can never block) and let the READER retire the
        # channel — no sender thread, no cross-thread handoff latency. The
        # reaper (`_reap_settled`) sweeps never-pulled sync channels.
        sync = n_pages <= depth and not self.page_interval_s
        try:
            tr = _Transfer(tid, ch, trace_ctx)
            if sync:
                import time as _time

                t_send0 = _time.time()
                kps = [np.ascontiguousarray(
                    k[:, i * page_size:(i + 1) * page_size])
                    for i in range(n_pages)]
                vps = [np.ascontiguousarray(
                    v[:, i * page_size:(i + 1) * page_size])
                    for i in range(n_pages)]
                ch.write_vectored(_pack_page_message(0, kps, vps), timeout=0)
                self._m_bytes.inc(sum(p.nbytes for p in kps)
                                  + sum(p.nbytes for p in vps))
                self._m_pages.inc(n_pages)
                with self._lock:
                    self._live[tid] = tr
                self._arm_reap_timer()
                if trace_ctx:
                    from ray_tpu.util import tracing

                    # the send happened right here (inline single-message
                    # write) — same span name the threaded sender emits
                    tracing.emit_span_for(
                        trace_ctx, "pd:kv_send", t_send0, _time.time(),
                        ok=True, ticket=tid, pages=n_pages, failed="",
                        sync=True)
            else:
                with self._lock:
                    self._live[tid] = tr
                tr.thread = threading.Thread(
                    target=self._send, args=(tr, k, v, page_size, n_pages),
                    daemon=True, name=f"pd-kv-send-{tid[:6]}")
                # ONE thread per threaded transfer (multi-message = long
                # prompt; spawn cost is noise next to the stream, and a
                # common thread pool would let one dead-reader transfer
                # head-of-line-block every later export). Spawn can fail
                # (ulimit under load); until start() succeeds the
                # sender's finally owns nothing, so the segment (and the
                # ticket registration) must be rolled back here or
                # /dev/shm leaks one segment per failed export
                tr.thread.start()
        except BaseException:
            with self._lock:
                self._live.pop(tid, None)
            ch.close()
            ch.unlink()
            raise
        return {
            "ticket": tid,
            "path": ch.path,
            "capacity": ch.capacity,
            "n_pages": n_pages,
            "prefetch": depth,
            "sync": sync,
            "page_size": page_size,
            "length": int(length),
            "first_token": int(first_token),
            "bucket": T,
            "page_shape": (L, page_size, k.shape[2], k.shape[3]),
            "dtype": str(k.dtype),
        }

    def _send(self, tr: _Transfer, k, v, page_size: int, n_pages: int):
        import time as _time

        from ray_tpu.serve import request_context as rc

        ch = tr.channel
        depth = min(self.prefetch_pages, n_pages)
        t_send0 = _time.time()
        try:
            for start in range(0, n_pages, depth):
                m = min(depth, n_pages - start)
                kps = [np.ascontiguousarray(
                    k[:, (start + i) * page_size:(start + i + 1) * page_size])
                    for i in range(m)]
                vps = [np.ascontiguousarray(
                    v[:, (start + i) * page_size:(start + i + 1) * page_size])
                    for i in range(m)]
                if self.page_interval_s:
                    _time.sleep(self.page_interval_s)
                t_w = _time.perf_counter()
                ch.write_vectored(_pack_page_message(start, kps, vps),
                                  timeout=self.send_timeout_s)
                # per-message backpressure wait: the seqlock write blocks
                # until the reader consumed the previous message, so this
                # IS how long the handoff serialized on the decode side
                rc.observe_phase(rc.PD_PHASE, "transfer_send_wait",
                                 _time.perf_counter() - t_w)
                self._m_bytes.inc(sum(p.nbytes for p in kps)
                                  + sum(p.nbytes for p in vps))
                self._m_pages.inc(m)
            # the final page is published but possibly unread: wait for the
            # reader's ack before unlinking the segment
            ch.wait_drained(timeout=self.send_timeout_s)
        except ChannelClosed:
            tr.failed = "closed"  # teardown/abort raced the send: expected
        except TimeoutError:
            tr.failed = "timeout"  # receiver never attached or died mid-pull
            logger.warning("kv transfer %s: send timed out after %.1fs "
                           "(decode side never pulled, or died mid-pull)",
                           tr.ticket_id, self.send_timeout_s)
        except Exception as e:  # noqa: BLE001 — must never leak the segment
            tr.failed = f"{type(e).__name__}: {e}"
            logger.warning("kv transfer %s: sender failed: %s",
                           tr.ticket_id, tr.failed)
        finally:
            ch.close()
            ch.unlink()
            with self._lock:
                self._live.pop(tr.ticket_id, None)
                if tr.failed is not None:
                    self.failures += 1
                    self.last_failure = f"{tr.ticket_id}: {tr.failed}"
            if tr.trace_ctx:
                from ray_tpu.util import tracing

                tracing.emit_span_for(
                    tr.trace_ctx, "pd:kv_send", t_send0, _time.time(),
                    ok=tr.failed is None, ticket=tr.ticket_id,
                    pages=n_pages, failed=tr.failed or "")

    # ---------------------------------------------------------- lifecycle

    def _arm_reap_timer(self) -> None:
        """Ensure ONE timer is pending whenever sync transfers are live:
        a never-pulled sync channel (decode replica died before pulling)
        must retire after send_timeout_s even if this exporter never
        exports again — an idle replica cannot pin /dev/shm."""
        with self._lock:
            if self._torn_down or self._reap_timer is not None:
                return
            if not any(tr.thread is None for tr in self._live.values()):
                return
            t = threading.Timer(self.send_timeout_s + 1.0, self._reap_tick)
            t.daemon = True
            self._reap_timer = t
        t.start()

    def _reap_tick(self) -> None:
        with self._lock:
            self._reap_timer = None
        self._reap_settled()
        self._arm_reap_timer()  # re-arms iff sync transfers remain

    def _reap_settled(self) -> None:
        """Retire settled SYNC transfers: drained ones silently (the
        reader consumed the message and unlinked the name), expired
        never-pulled ones as failures. Threaded transfers own their
        retirement in the sender's finally. Called from export()/
        pending() and the reap timer — teardown sweeps whatever remains."""
        import time as _time

        now = _time.monotonic()
        done: list[_Transfer] = []
        with self._lock:
            for tr in list(self._live.values()):
                if tr.thread is not None:
                    continue
                drained = tr.channel.drained()
                expired = now - tr.created > self.send_timeout_s
                if drained or expired:
                    self._live.pop(tr.ticket_id, None)
                    if expired and not drained:
                        tr.failed = "timeout"
                        self.failures += 1
                        self.last_failure = f"{tr.ticket_id}: timeout " \
                                            "(decode side never pulled)"
                    done.append(tr)
        for tr in done:
            tr.channel.close()
            tr.channel.unlink()

    def pending(self) -> int:
        self._reap_settled()
        with self._lock:
            return len(self._live)

    def abort(self, ticket_id: str) -> None:
        """Kill one in-flight transfer (its puller observes ChannelClosed →
        KVTransferError). Used when the prefill replica is shutting down or
        the request was cancelled upstream."""
        with self._lock:
            tr = self._live.get(ticket_id)
        if tr is None:
            return
        if tr.thread is None:  # sync transfer: retire it here
            tr.channel.close()
            tr.channel.unlink()
            with self._lock:
                self._live.pop(ticket_id, None)
            return
        tr.channel.close()
        tr.thread.join(timeout=5.0)

    def teardown(self) -> None:
        """Close every live channel, join the senders, unlink the
        segments. Safe to call twice; after it returns /dev/shm holds none
        of this exporter's ``rtpu_chan_*`` files."""
        with self._lock:
            self._torn_down = True
            timer, self._reap_timer = self._reap_timer, None
            live = list(self._live.values())
        if timer is not None:
            timer.cancel()
        for tr in live:
            tr.channel.close()
        for tr in live:
            if tr.thread is not None:
                tr.thread.join(timeout=5.0)
            tr.channel.unlink()  # sync transfers retire here too
        with self._lock:
            for tr in live:
                self._live.pop(tr.ticket_id, None)


# ----------------------------------------------------------------- receiver


def pull_pages(ticket: dict, timeout_s: float = 60.0):
    """Decode-side pull: attach to the ticket's channel and yield
    ``(index, k_page, v_page)`` in order (each ``[L, page_size, Hkv, Dh]``).
    Every failure mode surfaces as KVTransferError naming the ticket — the
    per-request error contract."""
    import time as _time

    from ray_tpu.serve import request_context as rc

    tid = ticket.get("ticket", "?")
    try:
        ch = MutableShmChannel(ticket["path"], ticket["capacity"])
    except FileNotFoundError:
        raise KVTransferError(
            f"kv transfer {tid}: channel {ticket['path']} not found — the "
            "prefill replica died (or retired the ticket), or prefill and "
            "decode are not co-hosted (shm transfer is same-host)") from None
    i = 0
    try:
        while i < ticket["n_pages"]:
            t_r = _time.perf_counter()
            try:
                view = ch.read_view(timeout=timeout_s)
            except ChannelClosed:
                raise KVTransferError(
                    f"kv transfer {tid}: prefill side closed after "
                    f"{i}/{ticket['n_pages']} pages (replica death or "
                    "abort mid-transfer)") from None
            except TimeoutError:
                raise KVTransferError(
                    f"kv transfer {tid}: timed out waiting for page {i} of "
                    f"{ticket['n_pages']} after {timeout_s}s") from None
            # per-message channel wait: how long decode admission stalled
            # on the transfer plane for this prefetch window
            rc.observe_phase(rc.PD_PHASE, "transfer_wait",
                             _time.perf_counter() - t_r)
            start, kviews, vviews = _unpack_page_view(view)
            # copy BEFORE acking: the writer may overwrite after the ack
            pages = [(start + off, np.array(kv), np.array(vv))
                     for off, (kv, vv) in enumerate(zip(kviews, vviews))]
            del kviews, vviews, view
            ch.ack_read()
            yield from pages
            i += len(pages)
        if ticket.get("sync"):
            # sync transfer fully consumed: the READER retires the
            # channel (the exporter never spawned a sender to do it)
            ch.close()
            ch.unlink()
    finally:
        ch.close_mapping()


def pull_all(ticket: dict, timeout_s: float = 60.0):
    """Pull the whole transfer: ``(k_pages, v_pages)`` as ordered lists of
    per-page arrays, ready for ``TPUEngine.submit_prefilled(k_pages=...)``."""
    k_pages: list = [None] * ticket["n_pages"]
    v_pages: list = [None] * ticket["n_pages"]
    for i, kp, vp in pull_pages(ticket, timeout_s):
        k_pages[i] = kp
        v_pages[i] = vp
    return k_pages, v_pages


# -------------------------------------------------------- streamed admission


class KVPageStream:
    """Thread-safe hand-off between the transfer plane and the engine.

    The puller (or an inline sync pull) ``feed()``s pages as they come
    off the channel; the engine scheduler ``take_ready()``s them between
    decode steps and adopts each into the paged pool
    (``TPUEngine.submit_prefilled(kv_stream=...)``), activating the slot
    once all ``n_pages`` landed. ``fail()`` turns the in-flight request
    into a per-request error — the engine reclaims the slot and its
    granted pages.
    """

    def __init__(self, n_pages: int, page_size: int):
        self.n_pages = int(n_pages)
        self.page_size = int(page_size)
        self._lock = threading.Lock()
        self._ready: list = []
        self._error: BaseException | None = None
        self.fed = 0
        self.finished_ts: float | None = None
        # set by the engine at submit: wakes the scheduler so a parked
        # (no-active-slot) loop adopts new pages immediately
        self._wake = None

    # ---------------------------------------------------- transfer side

    def feed(self, index: int, k_page, v_page) -> None:
        with self._lock:
            self._ready.append((int(index), k_page, v_page))
            self.fed += 1
        wake = self._wake
        if wake is not None:
            wake()

    def finish(self) -> None:
        import time as _time

        self.finished_ts = _time.time()
        wake = self._wake
        if wake is not None:
            wake()

    def fail(self, exc: BaseException) -> None:
        with self._lock:
            self._error = exc
        wake = self._wake
        if wake is not None:
            wake()

    # ------------------------------------------------------ engine side

    def take_ready(self) -> list:
        with self._lock:
            out, self._ready = self._ready, []
            return out

    def take_error(self) -> BaseException | None:
        with self._lock:
            return self._error


class _DiscardSink:
    """Drain-only sink: the prefix-cache warm path (decode budget already
    spent by the transferred token) still has to consume the channel so
    the prefill side retires it, but adopts nothing."""

    #: pull paths skip the copy-out-of-shm entirely for sinks that drop
    #: the pages — a long-prompt drain costs acks, not memcpys
    keeps_pages = False

    def feed(self, index, k_page, v_page) -> None:
        pass

    def finish(self) -> None:
        pass

    def fail(self, exc) -> None:
        pass


def pull_sync(ticket: dict, sink) -> bool:
    """Inline pull for single-message ('sync') tickets.

    A sync ticket's message was published BEFORE the ticket was returned,
    so the decode-side caller consumes it right here — no puller
    registration, no cross-thread wake; on a loaded host that hop costs
    more than the copy. Feeds ``sink`` like the puller would (feed per
    page, then finish) and retires the channel (reader-side ownership).
    Returns False when the ticket is not sync — register it with the
    BatchedKVPuller instead."""
    if not ticket.get("sync"):
        return False
    tid = ticket.get("ticket", "?")
    try:
        ch = MutableShmChannel(ticket["path"], ticket["capacity"])
    except FileNotFoundError:
        raise KVTransferError(
            f"kv transfer {tid}: channel {ticket['path']} not found — the "
            "prefill replica died (or retired the ticket), or prefill and "
            "decode are not co-hosted (shm transfer is same-host)") from None
    try:
        try:
            view = ch.read_view(timeout=0)
        except (ChannelClosed, TimeoutError):
            raise KVTransferError(
                f"kv transfer {tid}: sync message missing (aborted or "
                "reaped before the pull)") from None
        start, kviews, vviews = _unpack_page_view(view)
        if getattr(sink, "keeps_pages", True):
            # copy BEFORE acking: the writer side may reap/reuse after
            pages = [(start + off, np.array(kv), np.array(vv))
                     for off, (kv, vv) in enumerate(zip(kviews, vviews))]
        else:
            pages = []  # drain-only sink: ack without paying the memcpy
        n_fed = len(kviews)
        del kviews, vviews, view
        ch.ack_read()
        # fully consumed: the READER retires the channel (the exporter
        # never spawned a sender to do it)
        ch.close()
        ch.unlink()
    finally:
        ch.close_mapping()
    _prefetch_metric().inc(n_fed)
    for idx, kp, vp in pages:
        sink.feed(idx, kp, vp)
    sink.finish()
    return True


class _Pull:
    __slots__ = ("ticket_id", "channel", "sink", "n_pages", "next_i",
                 "timeout_s", "last_progress", "aborted")

    def __init__(self, ticket_id, channel, sink, n_pages, timeout_s, now):
        self.ticket_id = ticket_id
        self.channel = channel
        self.sink = sink
        self.n_pages = n_pages
        self.next_i = 0
        self.timeout_s = timeout_s
        self.last_progress = now
        self.aborted = False  # abort(): finished by the polling thread


class BatchedKVPuller:
    """One polling thread multiplexes EVERY in-flight ticket pull.

    The per-ticket ``pull_pages`` loop parks one thread per transfer in
    the seqlock wait — at concurrency N the decode host pays N wake-ups
    (and N spinning waiters) per page interval. Here a single thread
    sweeps all registered channels per cycle with non-blocking ``poll()``
    reads, so N concurrent transfers cost ONE wake, and pages flow into
    their ``KVPageStream`` sinks the moment the sender publishes them.
    Single-message ("sync") tickets bypass the thread entirely — consumed
    inline at ``pull()``.
    """

    def __init__(self, *, name: str = "pd-kv-pull"):
        self._lock = threading.Lock()
        self._pulls: list[_Pull] = []
        self._work = threading.Event()
        self._stop = False
        self._thread: threading.Thread | None = None
        self._name = name
        self._m_prefetched = _prefetch_metric()

    # ------------------------------------------------------ registration

    def pull(self, ticket: dict, sink, timeout_s: float = 60.0) -> None:
        """Register one transfer; returns immediately. ``sink`` receives
        ``feed(i, k_page, v_page)`` per page in order, then ``finish()``
        — or ``fail(KVTransferError)`` on death/timeout. Raises
        KVTransferError synchronously when the channel is already gone
        (prefill replica died or retired the ticket)."""
        import time as _time

        tid = ticket.get("ticket", "?")
        if self._stop:
            raise KVTransferError(
                f"kv transfer {tid}: puller is torn down")
        if pull_sync(ticket, sink):
            # single-message ticket consumed inline on the caller's
            # thread — no registration, no polling-thread wake
            return
        try:
            ch = MutableShmChannel(ticket["path"], ticket["capacity"])
        except FileNotFoundError:
            raise KVTransferError(
                f"kv transfer {tid}: channel {ticket['path']} not found — "
                "the prefill replica died (or retired the ticket), or "
                "prefill and decode are not co-hosted (shm transfer is "
                "same-host)") from None
        p = _Pull(tid, ch, sink, int(ticket["n_pages"]), float(timeout_s),
                  _time.monotonic())
        with self._lock:
            # re-check under the lock: teardown() flips _stop and sweeps
            # _pulls under this lock, so a pull racing it must not
            # register a _Pull nobody will ever service
            if self._stop:
                ch.close_mapping()
                raise KVTransferError(
                    f"kv transfer {tid}: puller is torn down")
            self._pulls.append(p)
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._loop, daemon=True, name=self._name)
                self._thread.start()
        self._work.set()

    def drain(self, ticket: dict, timeout_s: float = 60.0) -> None:
        """Consume a ticket's pages without adopting them (warm path:
        the transferred first token already spent the decode budget).
        Non-blocking for threaded tickets — the sender retires its
        channel once drained; sync tickets are consumed inline."""
        self.pull(ticket, _DiscardSink(), timeout_s)

    def pending(self) -> int:
        with self._lock:
            return len(self._pulls)

    def abort(self, ticket_id: str) -> bool:
        """Cancel an in-flight registered pull (decode-tier ticket abort:
        the request was cancelled downstream). The polling thread — the
        only channel reader — closes the channel (the flipped shared flag
        stops the sender's stream in one write) and fails the sink on its
        next cycle, so no page read races the teardown. Thread-safe; a
        ticket already finished (or consumed inline by pull_sync) returns
        False."""
        with self._lock:
            for p in self._pulls:
                if p.ticket_id == ticket_id:
                    p.aborted = True
                    self._work.set()
                    return True
        return False

    # ------------------------------------------------------------- loop

    def _finish(self, p: _Pull, exc: BaseException | None) -> None:
        # only threaded (multi-message) tickets ever register here — sync
        # tickets are consumed inline by pull_sync, which also retires
        # their channel — so the sender side owns channel retirement
        p.channel.close_mapping()
        with self._lock:
            if p in self._pulls:
                self._pulls.remove(p)
        if exc is None:
            p.sink.finish()
        else:
            logger.warning("kv transfer %s: pull failed: %s",
                           p.ticket_id, exc)
            p.sink.fail(exc)

    def _sweep_one(self, p: _Pull, now: float) -> bool:
        """Drain every message currently ready on one channel; returns
        True if any page moved."""
        import time as _time

        from ray_tpu.serve import request_context as rc

        progressed = False
        while p.channel.poll():
            view = p.channel.read_view(timeout=0)
            # per-message wait: how long the decode side had this
            # transfer stalled before the window arrived
            rc.observe_phase(rc.PD_PHASE, "transfer_wait",
                             _time.monotonic() - p.last_progress)
            start, kviews, vviews = _unpack_page_view(view)
            if getattr(p.sink, "keeps_pages", True):
                # copy out BEFORE acking (the writer may overwrite after),
                # then feed — the sink keeps the copies
                pages = [(start + off, np.array(kv), np.array(vv))
                         for off, (kv, vv) in enumerate(zip(kviews, vviews))]
            else:
                pages = []  # drain-only sink: ack without the memcpy
            n = len(kviews)
            del kviews, vviews, view
            p.channel.ack_read()
            for idx, kp, vp in pages:
                p.sink.feed(idx, kp, vp)
            p.next_i += n
            self._m_prefetched.inc(n)
            p.last_progress = _time.monotonic()
            progressed = True
            if p.next_i >= p.n_pages:
                self._finish(p, None)
                return True
        if not progressed:
            if p.channel.closed():
                # abort/replica death: poll() drained whatever was already
                # published above, so a flipped flag here means the stream
                # ended incomplete
                self._finish(p, KVTransferError(
                    f"kv transfer {p.ticket_id}: prefill side closed after "
                    f"{p.next_i}/{p.n_pages} pages (replica death or abort "
                    "mid-transfer)"))
            elif now - p.last_progress > p.timeout_s:
                self._finish(p, KVTransferError(
                    f"kv transfer {p.ticket_id}: timed out waiting for page "
                    f"{p.next_i} of {p.n_pages} after {p.timeout_s}s"))
        return progressed

    def _loop(self) -> None:
        import time as _time

        quiet_since: float | None = None
        while not self._stop:
            with self._lock:
                pulls = list(self._pulls)
            if not pulls:
                self._work.wait(timeout=0.1)
                self._work.clear()
                quiet_since = None
                continue
            progressed = False
            for p in pulls:
                try:
                    if p.aborted:
                        # reader-side close: the shared flag stops the
                        # sender's stream at its next write, then the sink
                        # fails so the engine reclaims the granted slot
                        p.channel.close()
                        self._finish(p, KVTransferError(
                            f"kv transfer {p.ticket_id}: cancelled by the "
                            f"decode side after {p.next_i}/{p.n_pages} "
                            "pages (request aborted)"))
                        progressed = True
                        continue
                    progressed |= self._sweep_one(p, _time.monotonic())
                except ChannelClosed:
                    self._finish(p, KVTransferError(
                        f"kv transfer {p.ticket_id}: prefill side closed "
                        f"after {p.next_i}/{p.n_pages} pages (replica "
                        "death or abort mid-transfer)"))
                except KVTransferError as e:
                    self._finish(p, e)
                except Exception as e:  # noqa: BLE001 — one bad channel
                    # must not take down the other transfers' pull loop
                    self._finish(p, KVTransferError(
                        f"kv transfer {p.ticket_id}: pull failed: "
                        f"{type(e).__name__}: {e}"))
            if progressed:
                quiet_since = None
                continue
            # nothing ready on ANY channel: one escalating WAITABLE sleep
            # covers the whole set — the "one wake, not N" part; a new
            # pull() registration interrupts it (threaded tickets can
            # publish their first message at any moment)
            now = _time.monotonic()
            if quiet_since is None:
                quiet_since = now
            quiet = now - quiet_since
            if quiet < 0.002:
                _time.sleep(50e-6)
            else:
                self._work.wait(timeout=200e-6 if quiet < 0.02 else 1e-3)
                self._work.clear()

    def teardown(self) -> None:
        """Stop the thread and fail every outstanding pull. Safe to call
        twice; after it returns no mapping of this puller's remains."""
        with self._lock:
            self._stop = True
            t = self._thread
        self._work.set()
        if t is not None:
            t.join(timeout=5.0)
        with self._lock:
            pulls, self._pulls = list(self._pulls), []
        for p in pulls:
            p.channel.close_mapping()
            p.sink.fail(KVTransferError(
                f"kv transfer {p.ticket_id}: puller torn down mid-pull"))
