"""LLM serving microbenchmark — `python -m ray_tpu.scripts.llm_bench`.

Measures the continuous-batching engine's TTFT (time to first streamed
token), per-request decode throughput, and aggregate tokens/s under
concurrent load; writes LLM_MICROBENCH.json at the repo root. Measures in
this process on the TPU or raises — there is no CPU stand-in (reference:
vLLM-style serving benchmarks — release/serve_tests + llm benchmarks).

Env: RAY_TPU_LLM_BENCH_{LAYERS,DMODEL,SLOTS,MAXLEN,CONCURRENCY,MAXTOKENS}
override the defaults.
"""

from __future__ import annotations

import os
import threading
import time


def main():
    from ray_tpu._private import accelerators

    accelerators.export_compile_cache_env()  # before jax is imported
    accelerators.require_tpu()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.llm import SamplingParams, TPUEngine
    from ray_tpu.models import llama_config, transformer

    E = lambda k, d: int(os.environ.get(f"RAY_TPU_LLM_BENCH_{k}", d))
    cfg = llama_config("tiny", vocab_size=32000, max_seq_len=2048,
                       d_model=E("DMODEL", 1024), n_layers=E("LAYERS", 8),
                       n_heads=16, n_kv_heads=8, d_ff=4096,
                       dtype=jnp.bfloat16)
    slots, max_len, conc, max_tokens = (E("SLOTS", 16), E("MAXLEN", 1024),
                                        E("CONCURRENCY", 16),
                                        E("MAXTOKENS", 64))

    params = transformer.init(jax.random.PRNGKey(0), cfg)
    eng = TPUEngine(cfg, params, max_slots=slots, max_len=max_len,
                    min_bucket=8)
    rng = np.random.default_rng(0)
    prompt = lambda n: rng.integers(1, cfg.vocab_size, n).tolist()

    results = []

    # warm: compile the decode step AND every prefill bucket the runs below
    # will hit (16/32/64) — a first-compile inside a timed window would
    # masquerade as throughput collapse
    for n in (16, 32, 40):
        eng.generate(prompt(n), SamplingParams(max_tokens=2))

    # TTFT + single-stream decode rate
    ttfts, rates = [], []
    for _ in range(3):
        t0 = time.perf_counter()
        first = None
        n = 0
        for _tok in eng.stream(prompt(32), SamplingParams(max_tokens=max_tokens)):
            if first is None:
                first = time.perf_counter() - t0
            n += 1
        dt = time.perf_counter() - t0
        ttfts.append(first)
        if n > 1 and dt > first:
            rates.append((n - 1) / (dt - first))
    ttfts = [t for t in ttfts if t is not None]
    med = lambda xs: sorted(xs)[len(xs) // 2] if xs else float("nan")
    results.append({"name": "ttft_ms_p50",
                    "value": round(med(ttfts) * 1e3, 1) if ttfts else None})
    results.append({"name": "decode_tokens_per_s_single",
                    "value": round(med(rates), 1) if rates else None})
    print(f"TTFT p50: {results[-2]['value']} ms; "
          f"single-stream decode: {results[-1]['value']} tok/s", flush=True)

    # aggregate throughput under concurrency
    done = []
    lock = threading.Lock()

    def client(i):
        out = eng.generate(prompt(24 + (i % 3) * 8),
                           SamplingParams(max_tokens=max_tokens))
        with lock:
            done.append(len(out))

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(conc)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    total = sum(done)
    results.append({"name": f"aggregate_tokens_per_s_c{conc}",
                    "value": round(total / wall, 1)})
    results.append({"name": "requests_completed", "value": len(done)})
    print(f"aggregate: {total/wall:,.0f} tok/s over {conc} concurrent "
          f"requests ({total} tokens in {wall:.1f}s)", flush=True)
    stats = eng.stats()
    eng.shutdown()

    from ray_tpu.scripts._artifacts import write_artifact

    # LLM_BENCH.json is owned by benchmarks/llm_serving_bench.py
    # (flat schema); this CLI microbenchmark keeps its own artifact
    print("wrote", write_artifact("LLM_MICROBENCH.json", {
        "device": accelerators.device_report(),
        "config": {"d_model": cfg.d_model, "layers": cfg.n_layers,
                   "slots": slots, "concurrency": conc},
        "engine_stats": stats, "results": results}))


if __name__ == "__main__":
    main()
