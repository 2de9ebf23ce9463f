"""On-chip LLM serving benchmark: TTFT, decode throughput, concurrency,
prefix-cache and speculative variants — the serve/LLM counterpart of
bench.py (north-star row in BASELINE.md: "Serve req/s + p50 TTFT").

(reference: python/ray/serve/_private/benchmarks/ + release/llm_tests/ —
the serving suites the release pipeline gates on.)

Measures in this process on the TPU or raises (no CPU stand-in); writes
LLM_BENCH.json with the ``device`` the numbers came from.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import threading
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)  # run as a script: benchmarks/ is sys.path[0]

from ray_tpu._private import accelerators  # noqa: E402


def _build(cfg_kw: dict, engine_kw: dict):
    import jax

    from ray_tpu.llm.engine import TPUEngine
    from ray_tpu.models import llama_config, transformer

    cfg = llama_config("tiny", **cfg_kw)
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    return cfg, params, TPUEngine(cfg, params, **engine_kw)


def _measure() -> dict:
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.llm.engine import SamplingParams, TPUEngine
    from ray_tpu.models import transformer

    # serving-shaped decoder: wide like the train bench (MXU-friendly),
    # shorter stack so 8 concurrent 1k contexts fit HBM comfortably
    cfg_kw = dict(vocab_size=32000, max_seq_len=2048, d_model=2048,
                  n_layers=8, n_heads=16, n_kv_heads=8, d_ff=8192,
                  dtype=jnp.bfloat16, remat=False)
    prompt_len, gen_len, conc = 512, 128, 8
    prefix_len = 768

    rng = np.random.default_rng(0)
    sp = SamplingParams(max_tokens=gen_len, temperature=0.0)
    results: dict = {"device": accelerators.device_report()}

    def prompt(n):
        return [int(x) for x in rng.integers(1, cfg_kw["vocab_size"] - 1,
                                             size=n)]

    # ---- base engine: TTFT + single-stream + aggregate ------------------
    cfg, params, eng = _build(cfg_kw, dict(max_slots=conc,
                                           max_len=cfg_kw["max_seq_len"],
                                           kv_layout="slot"))
    try:
        list(eng.stream(prompt(prompt_len), sp))  # compile warmup

        # TTFT p50 over 8 fresh single requests
        ttfts = []
        for _ in range(8):
            t0 = time.perf_counter()
            req = eng.submit(prompt(prompt_len), sp)
            req.out_queue.get()
            ttfts.append((time.perf_counter() - t0) * 1e3)
            for _tok in req:  # drain
                pass
        results["ttft_ms_p50"] = round(statistics.median(ttfts), 2)

        # single-stream decode tok/s (excluding prefill: time the tail)
        req = eng.submit(prompt(prompt_len), sp)
        req.out_queue.get()
        t0 = time.perf_counter()
        n = sum(1 for _ in req)
        results["decode_tokens_per_s_single"] = round(
            n / (time.perf_counter() - t0), 1)

        # aggregate decode at concurrency `conc` (continuous batching):
        # submit from threads like a serve replica pool would
        done = []
        lock = threading.Lock()

        def client(i):
            toks = list(eng.stream(prompt(prompt_len), sp))
            with lock:
                done.append(len(toks))

        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(conc * 2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        results["aggregate_tokens_per_s"] = round(sum(done) / wall, 1)
        results["aggregate_concurrency"] = conc
        results["aggregate_requests"] = len(done)
    finally:
        eng.shutdown()

    # ---- prefix-cache variant ------------------------------------------
    def ttft_with_cache(enable: bool) -> float:
        _, _, e2 = _build(
            dict(cfg_kw),
            dict(max_slots=4, max_len=cfg_kw["max_seq_len"],
                 kv_layout="paged", page_size=32,
                 enable_prefix_cache=enable))
        try:
            shared = prompt(prefix_len)
            list(e2.stream(shared + prompt(4), SamplingParams(max_tokens=2)))
            vals = []
            for _ in range(4):
                t0 = time.perf_counter()
                req = e2.submit(shared + prompt(4),
                                SamplingParams(max_tokens=2))
                req.out_queue.get()
                vals.append((time.perf_counter() - t0) * 1e3)
                for _tok in req:
                    pass
            return statistics.median(vals)
        finally:
            e2.shutdown()

    cold = ttft_with_cache(False)
    hot = ttft_with_cache(True)
    results["prefix_ttft_ms_p50_no_cache"] = round(cold, 2)
    results["prefix_ttft_ms_p50_cached"] = round(hot, 2)
    results["prefix_ttft_speedup"] = round(cold / max(hot, 1e-6), 2)

    # ---- speculative variant (n-gram prompt lookup) --------------------
    # repetitive prompt: the regime speculation exploits — built ONCE so
    # both variants decode the identical sequence (token-exactness check)
    _spec_base = prompt(32)
    spec_prompt = (_spec_base * ((prompt_len // 32) + 1))[:prompt_len]

    # generation must be LONG enough for greedy decode to settle into a
    # repetition loop the n-gram drafter can exploit (spec_bench.py's
    # regime) — a short tail from random weights measures ~0 acceptance
    # and reads as a speculation regression when it's workload design
    spec_sp = SamplingParams(max_tokens=max(256, gen_len), temperature=0.0)

    def decode_rate(spec_k: int) -> tuple[float, list, dict]:
        _, _, e3 = _build(
            dict(cfg_kw),
            dict(max_slots=2, max_len=cfg_kw["max_seq_len"],
                 kv_layout="slot", speculative_k=spec_k))
        try:
            p = spec_prompt
            list(e3.stream(p, spec_sp))
            req = e3.submit(p, spec_sp)
            req.out_queue.get()
            t0 = time.perf_counter()
            toks = [t for t in req]
            rate = len(toks) / (time.perf_counter() - t0)
            stats = (e3.stats() or {}).get("speculative") or {}
            return rate, toks, {
                "tokens_per_step": round(stats.get("tokens_per_step", 0.0), 3),
                "acceptance_rate": round(stats.get("acceptance_rate", 0.0), 3),
            }
        finally:
            e3.shutdown()

    plain, toks_plain, _ = decode_rate(0)
    spec, toks_spec, spec_stats = decode_rate(4)
    results["speculative"] = {
        "k": 4,
        "decode_tokens_per_s_plain": round(plain, 1),
        "decode_tokens_per_s_speculative": round(spec, 1),
        "wall_speedup": round(spec / max(plain, 1e-9), 3),
        # the diagnosability pair (spec_bench.py, PERF.md): low acceptance
        # vs per-step overhead are different failure modes
        "tokens_per_step": spec_stats.get("tokens_per_step"),
        "acceptance_rate": spec_stats.get("acceptance_rate"),
        "outputs_token_exact": toks_plain == toks_spec,
    }
    # ---- instrumentation overhead: interleaved A/B rounds ---------------
    # Same protocol as dag_bench._alternating_overhead: alternate
    # instrumented (default RayConfig.serve_metrics + span sampling) and
    # uninstrumented rounds in ONE session, rebuilding the engine per round
    # so the construction-time knob read takes effect; interleaving cancels
    # scheduling drift. Budget: ≤5% median per-request latency (ISSUE 11).
    from ray_tpu._private.ray_config import RayConfig

    def serving_round(n_requests: int) -> list:
        # Measured path = the engine's per-token instrumentation
        # (admission_wait + inter_token observes, the dominant hot-path
        # cost) PLUS the per-request request-path surface driven exactly
        # as the proxy/handle/replica drive it — phase observes, the
        # sampling tick, and the flight-recorder append. All of it
        # self-gates on the same knobs, so the off mode measures the true
        # uninstrumented baseline.
        from ray_tpu.serve import request_context as rc

        e4 = TPUEngine(cfg, params, max_slots=conc,
                       max_len=cfg_kw["max_seq_len"], kv_layout="paged",
                       page_size=32)
        try:
            list(e4.stream(prompt(prompt_len), sp))  # jit-cache warm
            lats = []
            for i in range(n_requests):
                t0 = time.perf_counter()
                rec = {"request_id": rc.new_request_id(),
                       "component": "bench", "sampled": rc.sample_request()}
                for phase in ("accept", "parse", "route"):
                    rc.observe_phase(rc.PROXY_PHASE, phase, 1e-6, rec)
                rc.observe_phase(rc.HANDLE_PHASE, "pick", 1e-6, rec)
                rc.observe_phase(rc.REPLICA_PHASE, "queue_wait", 1e-6, rec)
                list(e4.stream(prompt(prompt_len), sp))
                rc.observe_phase(rc.REPLICA_PHASE, "execute",
                                 time.perf_counter() - t0, rec)
                rc.observe_phase(rc.HANDLE_PHASE, "rtt",
                                 time.perf_counter() - t0, rec)
                rc.record_request(rec, t0, status=200)
                lats.append(time.perf_counter() - t0)
            return lats
        finally:
            e4.shutdown()

    knobs = ("RAY_TPU_SERVE_METRICS", "RAY_TPU_SERVE_SPAN_SAMPLE_EVERY")
    saved = {k: os.environ.get(k) for k in knobs}
    samples: dict = {"on": [], "off": []}
    try:
        for _ in range(3):
            for mode in ("on", "off"):
                if mode == "off":
                    os.environ["RAY_TPU_SERVE_METRICS"] = "0"
                    os.environ["RAY_TPU_SERVE_SPAN_SAMPLE_EVERY"] = "0"
                else:
                    # FORCE defaults (pop ambient overrides): a shell
                    # exporting RAY_TPU_SERVE_METRICS=0 must not turn the
                    # comparison into off-vs-off
                    for k in knobs:
                        os.environ.pop(k, None)
                RayConfig.reset()
                samples[mode].extend(serving_round(4))
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        RayConfig.reset()
    med_on = statistics.median(samples["on"])
    med_off = statistics.median(samples["off"])
    overhead_pct = (med_on / max(med_off, 1e-9) - 1.0) * 100.0
    results["instrumentation_ab"] = {
        "median_request_ms_instrumented": round(med_on * 1e3, 3),
        "median_request_ms_uninstrumented": round(med_off * 1e3, 3),
        "overhead_pct": round(overhead_pct, 2),
        "budget_pct": 5.0,
        "within_budget": bool(overhead_pct <= 5.0),
        "requests_per_mode": len(samples["on"]),
    }

    results["config"] = {k: str(v) for k, v in cfg_kw.items()}
    results["prompt_len"] = prompt_len
    results["gen_len"] = gen_len
    return results


def main():
    accelerators.export_compile_cache_env()  # before jax is imported
    accelerators.require_tpu()
    out = {"ts": time.strftime("%Y-%m-%d %H:%M"), **_measure()}
    path = os.path.join(_ROOT, "LLM_BENCH.json")
    try:  # sections owned by OTHER benches (llm_load_bench's `pd`, future
        #   additions): keep every prior key this run didn't produce
        with open(path) as f:
            prior = json.load(f)
    except (OSError, ValueError):
        prior = {}
    for k, v in prior.items():
        out.setdefault(k, v)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
