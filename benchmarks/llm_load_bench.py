"""Closed-loop LLM load harness: arrival-rate sweep + PD-vs-monolithic A/B.

The sustained-load counterpart of llm_serving_bench.py (which measures the
engine's intrinsic TTFT/throughput): this one drives the serving stack the
way traffic does —

- **closed loop**: N client threads, each issuing its next request the
  moment the previous one completes (the A/B mode: PD disaggregation vs
  one monolithic continuous-batching engine at concurrency >= 8);
- **open loop**: Poisson arrivals at a swept rate (req/s), the regime
  where queueing shows up in p99 TTFT long before throughput saturates
  (measurement template: the Gemma-on-TPU serving comparison,
  arXiv 2605.25645 — PAPERS.md).

The PD stack here is the real transfer plane in-process: the prefill
tier (PrefillCoalescer) runs the prompt forward and exports paged KV
through ray_tpu/llm/kv_transfer.py (MutableShmChannel per ticket); the
decode engine admits pages AS THEY ARRIVE through the shared
BatchedKVPuller + streamed submit_prefilled(kv_stream=...). No serve
control plane — the handoff and the slots are what's under test.

Measures in this process on the TPU or raises (no CPU stand-in); writes
the ``pd`` section of LLM_BENCH.json (merging, not clobbering, the serving
bench's fields).
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)  # run as a script: benchmarks/ is sys.path[0]

from ray_tpu._private import accelerators  # noqa: E402


# ---------------------------------------------------------------- stacks


class _MonoStack:
    """One continuous-batching paged engine: the baseline."""

    def __init__(self, cfg, params, *, page_size, max_slots, max_len,
                 min_bucket):
        from ray_tpu.llm.engine import TPUEngine

        self.engine = TPUEngine(cfg, params, max_slots=max_slots,
                                max_len=max_len, min_bucket=min_bucket,
                                kv_layout="paged", page_size=page_size)

    def request(self, ids, max_tokens: int):
        from ray_tpu.llm.engine import SamplingParams

        t0 = time.perf_counter()
        req = self.engine.submit(ids, SamplingParams(max_tokens=max_tokens))
        req.out_queue.get()  # first token
        ttft = time.perf_counter() - t0
        n = 1 + sum(1 for _ in req)
        return ttft, n

    def generate(self, ids, max_tokens: int) -> list:
        from ray_tpu.llm.engine import SamplingParams

        return self.engine.generate(ids,
                                    SamplingParams(max_tokens=max_tokens))

    def shutdown(self):
        self.engine.shutdown()


class _PDStack:
    """Disaggregated: the prefill tier coalesces concurrent prompts into
    batched forwards (PrefillCoalescer) and exports paged KV over the shm
    transfer plane; the decode engine admits pages AS THEY ARRIVE through
    the shared batched puller (streamed admission — the production path)."""

    def __init__(self, cfg, params, *, page_size, max_slots, max_len,
                 min_bucket, prefetch_depth: int = 2,
                 prefill_batch_max: int = 4):
        import jax  # noqa: F401 — imported for the device backend

        from ray_tpu.llm.engine import TPUEngine
        from ray_tpu.llm.kv_transfer import BatchedKVPuller, PagedKVExporter
        from ray_tpu.llm.pd import PrefillCoalescer

        self.cfg, self.params = cfg, params
        self.page_size = page_size
        self.min_bucket = max(min_bucket, page_size)
        self.max_len = max_len
        self.exporter = PagedKVExporter(send_timeout_s=120.0,
                                        prefetch_pages=prefetch_depth)
        self.puller = BatchedKVPuller()
        self.coalescer = PrefillCoalescer(
            params, cfg, min_bucket=self.min_bucket, max_len=max_len,
            max_batch=prefill_batch_max)
        self.decode = TPUEngine(cfg, params, max_slots=max_slots,
                                max_len=max_len, min_bucket=self.min_bucket,
                                kv_layout="paged", page_size=page_size)

    def _prefill(self, ids) -> dict:
        import jax.numpy as jnp
        import numpy as np

        logits, k, v, _bucket = self.coalescer.prefill(list(ids))
        first = int(jnp.argmax(logits))  # greedy (temperature 0 workload)
        return self.exporter.export(np.asarray(k), np.asarray(v),
                                    len(ids), first, self.page_size)

    def _submit(self, ticket, max_tokens: int):
        from ray_tpu.llm.engine import SamplingParams
        from ray_tpu.llm.kv_transfer import KVPageStream

        stream = KVPageStream(ticket["n_pages"], ticket["page_size"])
        self.puller.pull(ticket, stream, timeout_s=120.0)
        return self.decode.submit_prefilled(
            length=ticket["length"], first_token=ticket["first_token"],
            params=SamplingParams(max_tokens=max_tokens), kv_stream=stream)

    def request(self, ids, max_tokens: int):
        t0 = time.perf_counter()
        ticket = self._prefill(ids)  # calling thread joins the coalescer
        ttft = time.perf_counter() - t0  # first token rides the ticket
        req = self._submit(ticket, max_tokens)
        n = 1 + sum(1 for _ in req)
        return ttft, n

    def generate(self, ids, max_tokens: int) -> list:
        ticket = self._prefill(ids)
        req = self._submit(ticket, max_tokens)
        return [ticket["first_token"]] + list(req)

    def shutdown(self):
        self.coalescer.teardown()
        self.puller.teardown()
        self.decode.shutdown()
        self.exporter.teardown()


# ---------------------------------------------------------------- drivers


def _phase_totals() -> dict:
    """{phase: (sum_s, count)} for the PD-relevant phase histograms in
    THIS process's metrics registry (the whole harness is in-process).
    Deltas around a round attribute its time: transfer wait, admission
    wait, decode inter-token — the breakdown the next PD-optimization PR
    starts from."""
    from ray_tpu.util import metrics as met

    out: dict = {}
    for m in met.snapshot():
        if m["name"] not in ("ray_tpu_llm_pd_phase_seconds",
                             "ray_tpu_llm_engine_phase_seconds"):
            continue
        for tags, st in m["series"]:
            phase = dict(tuple(t) for t in tags).get("phase")
            s, c = out.get(phase, (0.0, 0))
            out[phase] = (s + st.get("sum", 0.0), c + st.get("count", 0))
    return out


def _phase_breakdown(pre: dict, post: dict, n_requests: int) -> dict:
    """Per-phase mean/total deltas between two _phase_totals snapshots."""
    out: dict = {}
    for phase in ("transfer_wait", "transfer_send_wait", "admission_wait",
                  "inter_token"):
        s0, c0 = pre.get(phase, (0.0, 0))
        s1, c1 = post.get(phase, (0.0, 0))
        if c1 > c0:
            out[phase] = {
                "mean_ms": round((s1 - s0) / (c1 - c0) * 1e3, 4),
                "total_s": round(s1 - s0, 4),
                "count": c1 - c0,
            }
    # derived: where one request's time went on average, the attribution
    # view the PD-vs-monolithic gap analysis needs
    if n_requests:
        for phase, rec in out.items():
            rec["per_request_ms"] = round(
                rec["total_s"] / n_requests * 1e3, 3)
    return out


def _pct(sorted_vals: list, q: float) -> float:
    if not sorted_vals:
        return 0.0
    i = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[i]


def _stats(results: list, wall: float) -> dict:
    ttfts = sorted(r[0] for r in results)
    return {
        "requests": len(results),
        "p50_ttft_ms": round(_pct(ttfts, 0.50) * 1e3, 2),
        "p99_ttft_ms": round(_pct(ttfts, 0.99) * 1e3, 2),
        "tokens_per_s": round(sum(r[1] for r in results) / max(wall, 1e-9), 1),
        "wall_s": round(wall, 2),
    }


def _closed_loop(stack, prompts, *, concurrency: int, n_requests: int,
                 max_tokens: int) -> dict:
    """N clients, each firing its next request on completion."""
    results: list = []
    lock = threading.Lock()
    counter = iter(range(n_requests))

    def client():
        while True:
            with lock:
                i = next(counter, None)
            if i is None:
                return
            r = stack.request(prompts[i % len(prompts)], max_tokens)
            with lock:
                results.append(r)

    threads = [threading.Thread(target=client) for _ in range(concurrency)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    out = _stats(results, time.perf_counter() - t0)
    out["concurrency"] = concurrency
    return out


def _open_loop(stack, prompts, *, rate_rps: float, duration_s: float,
               max_tokens: int, rng) -> dict:
    """Poisson arrivals at rate_rps for duration_s; every arrival gets its
    own client thread (queueing shows up as TTFT, not as lost arrivals)."""
    results: list = []
    lock = threading.Lock()
    threads: list = []
    t0 = time.perf_counter()
    i = 0
    next_at = t0
    while True:
        next_at += rng.exponential(1.0 / rate_rps)
        now = time.perf_counter()
        if next_at - t0 > duration_s:
            break
        if next_at > now:
            time.sleep(next_at - now)

        def client(idx=i):
            r = stack.request(prompts[idx % len(prompts)], max_tokens)
            with lock:
                results.append(r)

        th = threading.Thread(target=client)
        th.start()
        threads.append(th)
        i += 1
    for th in threads:
        th.join()
    out = _stats(results, time.perf_counter() - t0)
    out["rate_rps"] = rate_rps
    out["offered"] = i
    return out


class _AdmissionGate:
    """Replica-admission semantics (serve/replica.py) for an in-process
    stack: at most `max_ongoing` requests executing, at most `max_queued`
    waiting for a slot — anything beyond is SHED with RequestShedError
    instead of queued, exactly what a bounded replica does at 3x load."""

    def __init__(self, max_ongoing: int, max_queued: int):
        self._sem = threading.BoundedSemaphore(max_ongoing)
        self._max_queued = max_queued
        self._pending = 0
        self._lock = threading.Lock()

    def enter(self) -> None:
        from ray_tpu.exceptions import RequestShedError

        if self._sem.acquire(blocking=False):
            return
        with self._lock:
            if self._pending >= self._max_queued:
                raise RequestShedError(
                    f"admission queue full ({self._max_queued} waiting)")
            self._pending += 1
        self._sem.acquire()
        with self._lock:
            self._pending -= 1

    def leave(self) -> None:
        self._sem.release()


def _overload_round(stack, prompts, *, capacity_rps: float, factor: float,
                    duration_s: float, max_tokens: int, max_ongoing: int,
                    max_queued: int, rng) -> dict:
    """Open loop at `factor` x the measured closed-loop capacity against a
    bounded admission gate: the overload row. Records the shed rate and
    the ACCEPTED requests' p99 TTFT — the property under test is that
    bounded admission keeps latency for admitted work flat while excess
    arrivals get a fast refusal, instead of every request drowning in an
    unbounded queue."""
    from ray_tpu.exceptions import RequestShedError

    gate = _AdmissionGate(max_ongoing, max_queued)
    rate = max(capacity_rps * factor, 0.5)
    accepted: list = []
    shed = [0]
    lock = threading.Lock()
    threads: list = []
    t0 = time.perf_counter()
    i = 0
    next_at = t0
    while True:
        next_at += rng.exponential(1.0 / rate)
        now = time.perf_counter()
        if next_at - t0 > duration_s:
            break
        if next_at > now:
            time.sleep(next_at - now)

        def client(idx=i):
            try:
                gate.enter()
            except RequestShedError:
                with lock:
                    shed[0] += 1
                return
            try:
                r = stack.request(prompts[idx % len(prompts)], max_tokens)
            finally:
                gate.leave()
            with lock:
                accepted.append(r)

        th = threading.Thread(target=client)
        th.start()
        threads.append(th)
        i += 1
    for th in threads:
        th.join()
    wall = time.perf_counter() - t0
    out = _stats(accepted, wall)
    out.update({
        "offered": i,
        "offered_rps": round(rate, 2),
        "capacity_rps": round(capacity_rps, 2),
        "overload_factor": factor,
        "shed": shed[0],
        "shed_rate": round(shed[0] / max(i, 1), 3),
        "max_ongoing": max_ongoing,
        "max_queued": max_queued,
    })
    return out


# ----------------------------------------------------- decode-step microbench


def _decode_step_bench(cfg, params, *, page_size, max_len, batch,
                       lengths, iters=30) -> dict:
    """Ragged vs gather-per-slot decode step on ONE paged state with mixed
    sequence lengths — the kernel-level half of the PD win. The gather
    step's attention walks every row's full [max_pages*page] span; the
    ragged step walks only the batch's live page bound (Pallas kernel on
    TPU, the bit-consistent reference elsewhere)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import decoding, decoding_paged as dp

    P = page_size
    MP = max_len // P
    num_pages = batch * MP + 1
    state = dp.init_paged_state(cfg, batch, max_len, num_pages, P)
    free = list(range(1, num_pages))
    min_bucket = P
    for slot, n in enumerate(lengths):
        bucket = min_bucket
        while bucket < n:
            bucket *= 2
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n] = 1 + np.arange(n) % (cfg.vocab_size - 2)
        logits, kv = decoding.prefill(params, jnp.asarray(padded),
                                      jnp.int32(n), cfg)
        need = MP  # full reservation: the gather step's worst (usual) case
        pages = [free.pop() for _ in range(need)]
        row = np.zeros((MP,), np.int32)
        row[:need] = pages
        state = dp.insert_sequence_paged(
            state, slot, kv, jnp.int32(n),
            jnp.asarray(int(jnp.argmax(logits)), jnp.int32),
            jnp.asarray(row), cfg)
    bound = 1
    while bound * P < max(lengths) + iters + 1:
        bound *= 2
    bound = min(bound, MP)

    def run(step):
        st = {k: jnp.array(v) for k, v in state.items()}
        st, logits = step(st)          # compile + warm
        jax.block_until_ready(logits)
        t0 = time.perf_counter()
        for _ in range(iters):
            st, logits = step(st)
        jax.block_until_ready(logits)
        return (time.perf_counter() - t0) / iters * 1e3

    ms_gather = run(lambda st: dp.decode_step_paged(params, st, cfg))
    ms_ragged = run(lambda st: dp.decode_step_paged_ragged(
        params, st, cfg, bound, True))
    return {
        "batch": batch,
        "lengths": list(map(int, lengths)),
        "pages_bound": bound,
        "max_pages_per_seq": MP,
        "impl": "kernel",
        "ms_per_step_gather": round(ms_gather, 4),
        "ms_per_step_ragged": round(ms_ragged, 4),
        "speedup": round(ms_gather / max(ms_ragged, 1e-9), 3),
    }


# ---------------------------------------------------------------- measure


def _measure() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import llama_config, transformer

    cfg_kw = dict(vocab_size=32000, max_seq_len=2048, d_model=2048,
                  n_layers=8, n_heads=16, n_kv_heads=8, d_ff=8192,
                  dtype=jnp.bfloat16, remat=False)
    page_size, prompt_len, gen_len, conc = 64, 512, 128, 8
    rates, open_duration_s = [2.0, 4.0, 8.0], 10.0
    n_ab = 2 * conc

    cfg = llama_config("tiny", **cfg_kw)
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [[int(x) for x in rng.integers(
        1, cfg_kw["vocab_size"] - 1, size=prompt_len)] for _ in range(16)]
    stack_kw = dict(page_size=page_size, max_slots=conc,
                    max_len=cfg_kw["max_seq_len"],
                    min_bucket=max(32, page_size))
    results: dict = {"device": accelerators.device_report(),
                     "page_size": page_size, "prompt_len": prompt_len,
                     "gen_len": gen_len}

    pd = _PDStack(cfg, params, prefill_batch_max=conc, **stack_kw)
    mono = _MonoStack(cfg, params, **stack_kw)
    try:
        # warmup both stacks (prefill + decode compiles) and check the
        # disaggregated path is token-exact against the monolithic engine
        exact = pd.generate(prompts[0], gen_len) == mono.generate(
            prompts[0], gen_len)
        results["pd_token_exact"] = bool(exact)
        # warm the coalescer's padded batch shapes (1/2/4 rows): the A/B
        # round must measure the steady state, not three compiles
        from ray_tpu.models import decoding as _dec

        bucket = len(prompts[0])
        b = 1
        while b <= conc:
            jax.block_until_ready(_dec.prefill_batch(
                params, jnp.zeros((b, bucket), jnp.int32),
                jnp.ones((b,), jnp.int32), cfg)[0])
            b *= 2

        # ---- A/B: closed loop at concurrency `conc`, interleaved -------
        # five alternating rounds per stack, median (by tokens/s) kept:
        # single ~0.3s rounds on a busy box swing +-10%, which is larger
        # than the effect under test
        rounds: dict = {"pd": [], "monolithic": []}
        for _rnd in range(5):
            for name, stack in (("pd", pd), ("monolithic", mono)):
                pre = _phase_totals()
                r = _closed_loop(stack, prompts, concurrency=conc,
                                 n_requests=n_ab, max_tokens=gen_len)
                # per-phase attribution for BOTH stacks (admission wait +
                # inter-token for monolithic; + transfer waits for PD), so
                # a future regression attributes to the right engine
                r["phase_breakdown"] = _phase_breakdown(
                    pre, _phase_totals(), n_ab)
                rounds[name].append(r)
        ab = {name: sorted(rs, key=lambda r: r["tokens_per_s"])[len(rs) // 2]
              for name, rs in rounds.items()}
        ab["rounds_per_stack"] = 5
        # top-level copy kept: the capture pipeline and the PR 11
        # attribution docs key on this location
        results["phase_breakdown"] = ab["pd"]["phase_breakdown"]
        ab["ttft_p50_speedup"] = round(
            ab["monolithic"]["p50_ttft_ms"]
            / max(ab["pd"]["p50_ttft_ms"], 1e-6), 3)
        ab["tokens_per_s_ratio"] = round(
            ab["pd"]["tokens_per_s"]
            / max(ab["monolithic"]["tokens_per_s"], 1e-9), 3)
        results["ab"] = ab

        # ---- arrival-rate sweep: open loop on the PD stack -------------
        sweep = []
        arrival_rng = np.random.default_rng(1)
        for rate in rates:
            sweep.append(_open_loop(pd, prompts, rate_rps=rate,
                                    duration_s=open_duration_s,
                                    max_tokens=gen_len, rng=arrival_rng))
        results["arrival_sweep"] = sweep

        # ---- overload row: ~3x capacity against bounded admission ------
        # capacity = the stack's measured closed-loop completion rate; at
        # 3x offered load the bounded gate sheds the excess fast and the
        # admitted requests' p99 TTFT stays near the closed-loop value
        # (the ISSUE 16 overload-shedding acceptance row)
        capacity_rps = ab["pd"]["requests"] / max(ab["pd"]["wall_s"], 1e-9)
        results["overload"] = _overload_round(
            pd, prompts, capacity_rps=capacity_rps, factor=3.0,
            duration_s=open_duration_s, max_tokens=gen_len,
            max_ongoing=conc, max_queued=conc,
            rng=np.random.default_rng(2))
    finally:
        pd.shutdown()
        mono.shutdown()

    # ---- decode-step microbench: ragged vs gather-per-slot ------------
    results["decode_step"] = _decode_step_bench(
        cfg, params, page_size=64, max_len=2048, batch=8,
        lengths=[130, 260, 390, 140, 520, 180, 300, 450])
    results["config"] = {k: str(v) for k, v in cfg_kw.items()}
    return results


def main():
    accelerators.export_compile_cache_env()  # before jax is imported
    accelerators.require_tpu()
    out = {"ts": time.strftime("%Y-%m-%d %H:%M"), **_measure()}
    # merge INTO LLM_BENCH.json as the `pd` section — the serving bench
    # owns the file's top level and preserves this key on rewrite
    path = os.path.join(_ROOT, "LLM_BENCH.json")
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError):
        data = {}
    data["pd"] = out
    with open(path, "w") as f:
        json.dump(data, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
