"""Speculative-decoding microbenchmark: tokens/step and wall-clock speedup
of n-gram speculation vs plain decode on a repetitive workload.

Appends a `speculative` section to LLM_MICROBENCH.json
(LLM_BENCH.json is owned by llm_serving_bench.py, flat schema). CPU numbers are
relative (the verify-step cost ratio differs on the MXU, in speculation's
favor — decode is memory-bound there).

Usage:
    python benchmarks/spec_bench.py [--tokens N]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# hard-set: a host-side relative comparison, never a chip measurement
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, default=256)
    ap.add_argument("--k", type=int, default=4)
    args = ap.parse_args()

    from ray_tpu.llm import SamplingParams, TPUEngine
    from ray_tpu.models import transformer
    from ray_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(
        vocab_size=512, d_model=128, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=256, max_seq_len=1024, dtype=jnp.float32, remat=False)
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    # repetitive prompt: the regime speculation targets (templated text,
    # code, summarization-with-copying)
    prompt = [7, 3, 9, 4] * 8

    def run(spec_k: int):
        eng = TPUEngine(cfg, params, max_slots=2, max_len=1024,
                        min_bucket=32, speculative_k=spec_k)
        sp = SamplingParams(max_tokens=args.tokens, temperature=0.0)
        out = eng.generate(prompt, sp)  # warmup/compile
        t0 = time.perf_counter()
        out = eng.generate(prompt, sp)
        dt = time.perf_counter() - t0
        stats = eng.stats().get("speculative", {})
        eng.shutdown()
        return len(out) / dt, stats, out

    plain_tps, _, out_a = run(0)
    spec_tps, stats, out_b = run(args.k)
    assert out_a == out_b, "speculative output diverged from plain decode"

    section = {
        "k": args.k,
        "decode_tokens_per_s_plain": round(plain_tps, 1),
        "decode_tokens_per_s_speculative": round(spec_tps, 1),
        "wall_speedup": round(spec_tps / plain_tps, 3),
        "tokens_per_step": round(stats.get("tokens_per_step", 0.0), 3),
        "acceptance_rate": round(stats.get("acceptance_rate", 0.0), 3),
        "backend": jax.default_backend(),
        "outputs_token_exact": True,
    }
    print(json.dumps(section, indent=1))
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "LLM_MICROBENCH.json")
    try:
        doc = json.load(open(path))
    except (OSError, ValueError):
        doc = {}
    doc["speculative"] = section
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"appended to {path}")


if __name__ == "__main__":
    main()
