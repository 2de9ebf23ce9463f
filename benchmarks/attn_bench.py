"""Attention micro-bench on the real chip: flash (Pallas) vs reference (XLA)
fwd+bwd at the headline-bench shape, sweeping block sizes.

Usage: python benchmarks/attn_bench.py [T ...]
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp


def timeit(f, *args, iters=20):
    jax.block_until_ready(f(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


def main():
    from ray_tpu._private import accelerators
    from ray_tpu.ops.flash_attention import flash_attention, _reference_bhtd

    accelerators.require_tpu()

    B, H, D = 8, 16, 128
    seqs = [int(a) for a in sys.argv[1:]] or [2048]
    print("device:", accelerators.device_report())
    for T in seqs:
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, H, T, D), jnp.bfloat16)
        k = jax.random.normal(ks[1], (B, H, T, D), jnp.bfloat16)
        v = jax.random.normal(ks[2], (B, H, T, D), jnp.bfloat16)

        def ref_loss(q, k, v):
            return _reference_bhtd(q, k, v, causal=True, scale=D**-0.5).astype(jnp.float32).sum()

        gref = jax.jit(jax.grad(ref_loss, argnums=(0, 1, 2)))
        try:
            ms = timeit(gref, q, k, v)
            print(f"T={T} reference fwd+bwd: {ms:.2f} ms")
        except Exception as e:
            print(f"T={T} reference failed: {type(e).__name__}: {e}")

        for bq, bk in [(256, 256), (512, 512), (256, 512), (512, 256), (1024, 512)]:
            if T % bq or T % bk:
                continue

            def fl_loss(q, k, v, bq=bq, bk=bk):
                return flash_attention(q, k, v, True, None, bq, bk, False).astype(jnp.float32).sum()

            gfl = jax.jit(jax.grad(fl_loss, argnums=(0, 1, 2)))
            try:
                ms = timeit(gfl, q, k, v)
                print(f"T={T} flash bq={bq} bk={bk} fwd+bwd: {ms:.2f} ms")
            except Exception as e:
                print(f"T={T} flash bq={bq} bk={bk} failed: {type(e).__name__}: {str(e)[:200]}")


if __name__ == "__main__":
    main()
