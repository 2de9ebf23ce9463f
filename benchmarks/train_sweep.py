"""Full train-step sweep on the real chip: attention impl x remat policy x
shape. Each config runs in-process sequentially; prints tokens/s + 6ND MFU.

Usage: python benchmarks/train_sweep.py [config_name ...]
"""

from __future__ import annotations

import functools
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax


def run(name, *, d_model=2048, n_layers=8, n_heads=16, n_kv_heads=8, d_ff=8192,
        batch=8, seq=2048, remat=True, remat_policy="nothing", steps=20,
        attn_impl=None, opt_kind="adamw", ce_chunk=None):
    from ray_tpu.models import llama_config, transformer

    cfg = llama_config(
        "tiny", vocab_size=32000, max_seq_len=seq, d_model=d_model,
        n_layers=n_layers, n_heads=n_heads, n_kv_heads=n_kv_heads, d_ff=d_ff,
        dtype=jnp.bfloat16, remat=remat, remat_policy=remat_policy,
    )
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    n_params = sum(math.prod(p.shape) for p in jax.tree.leaves(params))
    if opt_kind == "adamw":
        opt = optax.adamw(1e-4, weight_decay=0.01)
    elif opt_kind == "adafactor":
        opt = optax.adafactor(1e-4)
    elif opt_kind == "adamw_int8":
        from ray_tpu.train.optim import adamw_int8

        opt = adamw_int8(1e-4, weight_decay=0.01)
    else:
        raise ValueError(opt_kind)
    opt_state = opt.init(params)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(transformer.loss_fn)(
            params, tokens, cfg, attn_impl=attn_impl, ce_chunk=ce_chunk)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    tokens = jnp.asarray(
        np.random.randint(0, cfg.vocab_size, (batch, seq + 1), dtype=np.int32))
    failed = None
    try:
        t_c0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, tokens)
        float(loss)
        compile_s = time.perf_counter() - t_c0
        t0 = time.perf_counter()
        for _ in range(steps):
            params, opt_state, loss = step(params, opt_state, tokens)
        float(loss)
        dt = (time.perf_counter() - t0) / steps
    except Exception as e:
        failed = f"{type(e).__name__}: {str(e)[:200]}"
    if failed:
        # cleanup OUTSIDE the except clause: while it is live, the
        # interpreter's exception state keeps the traceback (and through
        # it this config's device buffers) alive, which would OOM every
        # subsequent config in this process
        print(f"{name}: FAILED {failed}", flush=True)
        import gc

        del params, opt_state, step
        gc.collect()
        jax.clear_caches()
        return
    tps = batch * seq / dt
    mfu = tps * 6 * n_params / 197e12
    print(f"{name}: params={n_params/1e6:.0f}M step={dt*1e3:.1f}ms "
          f"tok/s={tps:,.0f} mfu={mfu:.4f} (compile {compile_s:.0f}s)", flush=True)


CONFIGS = {
    "base_ref": dict(attn_impl="reference"),                      # round-2 bench config
    "flash": dict(),                                              # auto -> flash now
    "flash_dots": dict(remat_policy="dots"),
    "flash_noremat": dict(remat=False),
    "flash_noremat_b16": dict(remat=False, batch=16),
    "flash_b16": dict(batch=16),
    "flash_s4096": dict(seq=4096, batch=4),
    "flash_d2560": dict(d_model=2560, n_heads=20, n_kv_heads=10, d_ff=10240),
    "flash_L12": dict(n_layers=12),
    "flash_L12_dots": dict(n_layers=12, remat_policy="dots"),
    "flash_adafactor_noremat": dict(remat=False, opt_kind="adafactor"),
    # round-4 levers: int8 optimizer state frees ~4.8GB at 634M, enough to
    # relax remat. Full no-remat at b8 OOMed on hardware; dots-policy and
    # smaller-batch no-remat are the candidates.
    "int8_dots": dict(remat_policy="dots", opt_kind="adamw_int8"),
    "int8_noremat": dict(remat=False, opt_kind="adamw_int8"),
    "int8_noremat_b4": dict(remat=False, batch=4, opt_kind="adamw_int8"),
    "int8_noremat_b6": dict(remat=False, batch=6, opt_kind="adamw_int8"),
    "int8_flash": dict(opt_kind="adamw_int8"),
    "flash_b24": dict(batch=24),
    "flash_b32": dict(batch=32),
    "flash_b16_dots": dict(batch=16, remat_policy="dots"),
    "flash_b16_ce4096": dict(batch=16, ce_chunk=4096),
    "flash_b16_ce8192": dict(batch=16, ce_chunk=8192),
    # selective remat: recompute only every other layer in backward
    "flash_pairs": dict(remat_policy="pairs"),
    "flash_pairs_b12": dict(remat_policy="pairs", batch=12),
    "flash_pairs_b16": dict(remat_policy="pairs", batch=16),
}


def main():
    from ray_tpu._private import accelerators

    accelerators.require_tpu()
    names = sys.argv[1:] or ["base_ref", "flash", "flash_dots", "flash_noremat"]
    print("device:", accelerators.device_report(), flush=True)
    for n in names:
        run(n, **CONFIGS[n])


if __name__ == "__main__":
    main()
