"""TTFT with vs without prefix caching on a repeated-prefix workload.

Workload: N requests sharing one long prompt prefix with short distinct
tails (the serve prefix router's steady state). Measures time-to-first-token
per request after a warmup request populates the cache / compilations.
Updates LLM_MICROBENCH.json with the prefix-cache rows
(LLM_BENCH.json is owned by llm_serving_bench.py, flat schema). Measures in
this process on the TPU or raises — there is no CPU stand-in.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ray_tpu._private import accelerators  # noqa: E402

accelerators.export_compile_cache_env()  # before jax is imported

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from ray_tpu.llm import SamplingParams, TPUEngine  # noqa: E402
from ray_tpu.models import transformer  # noqa: E402
from ray_tpu.models.transformer import TransformerConfig  # noqa: E402

CFG = dict(vocab_size=512, d_model=128, n_layers=4, n_heads=8, n_kv_heads=4,
           d_ff=256, max_seq_len=1024, dtype=jnp.float32, remat=False)
PAGE = 32
PREFIX_LEN = 768      # the shared system prompt / few-shot block
N_REQUESTS = 8


def measure(enable_cache: bool, cfg, params) -> list[float]:
    eng = TPUEngine(cfg, params, max_slots=4, max_len=1024, min_bucket=32,
                    kv_layout="paged", page_size=PAGE,
                    enable_prefix_cache=enable_cache)
    rng = np.random.default_rng(0)
    prefix = [int(x) for x in rng.integers(1, 500, size=PREFIX_LEN)]
    try:
        # warmup: populates compilations and (if enabled) the cache
        list(eng.stream(prefix + [1, 2, 3],
                        SamplingParams(max_tokens=2, temperature=0.0)))
        ttfts = []
        for i in range(N_REQUESTS):
            tail = [int(x) for x in rng.integers(1, 500, size=5)]
            t0 = time.perf_counter()
            req = eng.submit(prefix + tail,
                             SamplingParams(max_tokens=2, temperature=0.0))
            first = req.out_queue.get()  # first token or sentinel
            ttfts.append((time.perf_counter() - t0) * 1e3)
        return ttfts
    finally:
        eng.shutdown()


def main():
    accelerators.require_tpu()
    cfg = TransformerConfig(**CFG)
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    base = measure(False, cfg, params)
    cached = measure(True, cfg, params)
    p50_base = statistics.median(base)
    p50_cached = statistics.median(cached)
    speedup = p50_base / p50_cached if p50_cached else float("inf")
    rows = [
        {"name": "prefix_ttft_ms_p50_no_cache", "value": round(p50_base, 2)},
        {"name": "prefix_ttft_ms_p50_cached", "value": round(p50_cached, 2)},
        {"name": "prefix_ttft_speedup", "value": round(speedup, 2)},
    ]
    print(json.dumps({"prefix_workload": {
        "prefix_len": PREFIX_LEN, "page_size": PAGE,
        "device": accelerators.device_report()}, "results": rows}))
    path = os.path.join(os.path.dirname(__file__), "..", "LLM_MICROBENCH.json")
    try:
        doc = json.load(open(path))
        keep = [r for r in doc.get("results", [])
                if not r["name"].startswith("prefix_ttft") and
                r["name"] != "prefix_ttft_speedup"]
        doc["results"] = keep + rows
        doc["prefix_workload"] = {"prefix_len": PREFIX_LEN,
                                  "page_size": PAGE,
                                  "device": accelerators.device_report()}
        json.dump(doc, open(path, "w"), indent=1)
    except FileNotFoundError:
        pass


if __name__ == "__main__":
    main()
