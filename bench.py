"""Train-step throughput of one decoder on one chip, in this process.

Prints ONE JSON line:
  {"metric": "train_tokens_per_sec_per_chip", "value": N, "unit": "tokens/s",
   "detail": {...}}

Measures on the TPU or raises: there is no CPU path, no child process and no
cached earlier result. `detail` names the device the number came from, and
utilization is against the peak of that `device_kind` (unknown kind = error).
The shape is the d2048/L8 sweep shape of earlier rounds under the Llama
block; the benchmark that replaces this file uses published configurations.
"""

from __future__ import annotations

import functools
import json
import math
import sys
import time

from ray_tpu._private import accelerators

# bf16 peak FLOP/s per chip by jax `device_kind` (Google Cloud, "TPU v5e")
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}


def measure() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_tpu.models import llama_config, transformer

    accelerators.require_tpu()
    kind = jax.devices()[0].device_kind
    if kind not in PEAK_BF16_FLOPS:
        raise RuntimeError(f"no peak FLOP/s on record for device_kind {kind!r}")
    peak = PEAK_BF16_FLOPS[kind]

    cfg = llama_config(
        "tiny", vocab_size=32000, max_seq_len=2048, d_model=2048,
        n_layers=8, n_heads=16, n_kv_heads=8, d_ff=8192, dtype=jnp.bfloat16)
    batch, seq, steps = 16, 2048, 20

    params = transformer.init(jax.random.PRNGKey(0), cfg)
    n_params = sum(math.prod(p.shape) for p in jax.tree.leaves(params))
    opt = optax.adamw(1e-4, weight_decay=0.01)
    opt_state = opt.init(params)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(transformer.loss_fn)(params, tokens, cfg)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    tokens = jnp.asarray(
        np.random.randint(0, cfg.vocab_size, (batch, seq + 1), dtype=np.int32))

    t0 = time.perf_counter()
    params, opt_state, loss = step(params, opt_state, tokens)
    loss.block_until_ready()
    compile_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, loss = step(params, opt_state, tokens)
    loss.block_until_ready()  # donated params chain the steps sequentially
    dt = (time.perf_counter() - t0) / steps

    tokens_per_sec = batch * seq / dt
    # 6ND approximation for train FLOPs (fwd+bwd), attention excluded
    flops_per_token = 6 * n_params
    # secondary MFU including causal self-attention matmul FLOPs
    # (6·L·S·d_attn per token: 2 matmuls × 2·(S/2)·H·Dh fwd, ×3 for train)
    attn_flops_per_token = 6 * cfg.n_layers * seq * cfg.n_heads * cfg.head_dim
    return {
        "tokens_per_sec": tokens_per_sec,
        "model_params": n_params,
        "batch": batch, "seq": seq,
        "step_ms": round(dt * 1e3, 2),
        "first_step_s": round(compile_s, 2),
        "mfu_6nd": round(tokens_per_sec * flops_per_token / peak, 4),
        "mfu_incl_attn": round(
            tokens_per_sec * (flops_per_token + attn_flops_per_token) / peak, 4),
        "final_loss": round(float(loss), 3),
        "device": {"platform": jax.devices()[0].platform, "kind": kind,
                   "count": len(jax.devices())},
    }


def main():
    accelerators.export_compile_cache_env()  # before jax is imported
    result = measure()
    print(json.dumps({
        "metric": "train_tokens_per_sec_per_chip",
        "value": round(result.pop("tokens_per_sec"), 1),
        "unit": "tokens/s",
        "detail": result,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
