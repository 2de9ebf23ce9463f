"""chip_smoke.py — the quickest proof that the main path still starts on the chip.

    python chip_smoke.py             # one TPU chip
    python chip_smoke.py --chips 4   # only the paths that exist across chips

One chip, both halves of the system through the entry points users call:

- train: `ray_tpu.init` -> `JaxTrainer(use_tpu=True)` -> a worker that holds
  the chip checks the Pallas kernels against their references, then takes
  AdamW steps of GPT-2 774M (published widths, s1024, bf16, remat) with
  `train/spmd.py`'s step on a one-device mesh, batches from `ray_tpu.data`,
  losses through `train.report`;
- serve: `serve.run(build_openai_app(LLMConfig(...)))` + the HTTP proxy ->
  `/v1/completions` against a Llama-1B paged engine, requests of three
  prompt lengths, several in flight at once, one streamed.

Four chips (`--chips 4`), and nothing of the above: the same train step on an
`fsdp=4` mesh against the one-device step, the engine's tensor-parallel decode
against the unsharded engine, four one-chip replicas behind the router.

This process never initialises a JAX backend: a chip belongs to one process,
and that is the worker the GCS binds it to. There is no CPU mode. Every phase
prints one JSON line of what the chip worker saw; the first failed check
raises, so the exit code is non-zero. The last line is
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`.
No time printed here is a benchmark.
"""

from __future__ import annotations

import argparse
import functools
import glob
import json
import os
import re
import sys
import threading
import time
import urllib.request

# batch 4, not 8: the compiler's account of the b8 step (9.3 GB of f32 params
# and Adam moments + 7.7 GB of temporaries) is past one v5e chip's 15.75 GiB;
# at b4 it is 15.2 GB. The widths are the published ones.
TRAIN = dict(family="gpt2", size="774m", model_kwargs={}, batch=4, seq=1024,
             steps=6, lr=3e-4, meshes=[{}])
SERVE = dict(family="llama", model_id="1b", model_kwargs={"max_seq_len": 2048},
             engine_kwargs={"page_size": 64, "max_slots": 16, "max_len": 2048},
             prompt_tokens=(64, 500, 1500), max_tokens=16)
# the shapes the two phases run the kernels at: GPT-2 774M attention at
# s1024, and Llama-1B decode (8 kv heads x 4 query heads, d_head 64, page 64)
KERNELS = dict(flash=(2, 20, 1024, 64),
               ragged=dict(batch=16, kv_heads=8, group=4, head_dim=64,
                           page=64, pages_per_seq=32),
               # the sorted dispatch of a 1,024-token prefill chunk in the two
               # document cells: the experts of all the cell's layers stacked
               grouped=dict(
                   kimi_vl_a3b=dict(tokens=1024, top_k=6, experts=64, layers=8,
                                    d_model=2048, d_ff=1408),
                   mixtral_8x7b=dict(tokens=1024, top_k=2, experts=8, layers=4,
                                     d_model=4096, d_ff=14336),
                   # a 2,048-token chunk of trinity-large-preview: 32 of the
                   # router's 256 experts held, the other slots past the rows
                   trinity_large_preview=dict(tokens=2048, top_k=4, experts=32, of=256,
                                              layers=4, d_model=3072, d_ff=3072)),
               # a row's recurrent state of granite-4.0-h-micro, 96 slots
               state_update=dict(layers=3, slots=96, heads=64, head_dim=64, d_state=128),
               # the gated delta rule's state of solar-open2-250b, 32 slots
               kda_update=dict(layers=3, slots=32, heads=64, head_dim=128),
               # ... and its chunked scan over a 2,048-token chunk of the prefill
               kda_scan=dict(tokens=2048, tail=1024, heads=64, head_dim=128, chunk=64),
               # ... and the mixer's elementwise work on either side of that scan
               kda_mixer=dict(tokens=2048, heads=64, head_dim=128, d_conv=4, d_model=4096))


class SmokeFailure(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(phase: str, **facts) -> None:
    print(json.dumps({"phase": phase, **facts}, default=str), flush=True)


# ------------------------------------------------- inside the chip worker


def probe_sync_primitive() -> dict:
    """Does `block_until_ready` wait for the device? Dispatch a chain of
    matmuls, then time the dispatch, the wait, and a host fetch after it."""
    import jax
    import jax.numpy as jnp

    x = jnp.ones((4096, 4096), jnp.bfloat16)

    @jax.jit
    def chain(x):
        return jax.lax.fori_loop(
            0, 200, lambda _, a: (a @ x) * jnp.bfloat16(1e-4), x)

    float(chain(x)[0, 0])  # compile the chain and the fetch
    t0 = time.perf_counter()
    y = chain(x)
    t1 = time.perf_counter()
    y.block_until_ready()
    t2 = time.perf_counter()
    float(y[0, 0])
    t3 = time.perf_counter()
    return {"dispatch_s": t1 - t0, "block_until_ready_s": t2 - t1,
            "fetch_after_s": t3 - t2,
            # it blocks if the wait dwarfs the dispatch and leaves the
            # fetch nothing to wait for
            "blocks": (t2 - t1) > 10 * (t1 - t0) and (t3 - t2) < (t2 - t1)}


def compare_grouped_matmul(shapes: dict, interpret: bool = False) -> dict:
    """`ops.grouped_matmul`'s kernel against `jax.lax.ragged_dot` and the
    float32 product, bf16, for each model of `shapes` in both directions (gate
    and up: [M, D] x [L*E, D, F]; down: [M, F] x [L*E, F, D]) with one layer's
    groups filled: sizes as a router draws them, and all rows in one group.
    A model with `of` holds `experts` of the `of` its router draws among: the
    slots of the absent ones lie past the groups' rows, the kernel takes no
    step for them (`rows_past="skip"`) and only the groups' rows are compared.
    The error is in roundings: bfloat16 spacings at the float32 product's size
    (at no less than 1/64 of the output's rms: below it the float32 sums' own
    order shows), so a correctly rounded output is at 0.5. Milliseconds a call
    are of 20 calls in a row: `PERF.md` quotes them, no benchmark reads them."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.grouped_matmul import grouped_matmul_kernel

    def ms_a_call(fn, *args, calls=20):
        fn(*args).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(calls):
            y = fn(*args)
        y.block_until_ready()
        return (time.perf_counter() - t0) / calls * 1e3

    def roundings(got, want32):
        a = jnp.abs(want32)
        floor = jnp.sqrt(jnp.mean(want32 * want32)) / 64
        spacing = jnp.exp2(jnp.floor(jnp.log2(jnp.maximum(a, floor))) - 7)
        return float((jnp.abs(got.astype(jnp.float32) - want32) / spacing).max())

    ragged = jax.jit(jax.lax.ragged_dot)
    exact = jax.jit(lambda a, b, s: jax.lax.ragged_dot(
        a, b, s, preferred_element_type=jnp.float32))
    out = {}
    for model, sh in shapes.items():
        E, L, M = sh["experts"], sh["layers"], sh["tokens"] * sh["top_k"]
        of, past = sh.get("of", E), "skip" if "of" in sh else "zero"
        kernel = jax.jit(lambda a, b, s, E=E, past=past: grouped_matmul_kernel(
            a, b, s, E, past, interpret=interpret))
        keys = jax.random.split(jax.random.PRNGKey(len(out)), 5)
        # a router's draw: the top k of softmaxed normal logits with an uneven bias
        logits = jax.random.normal(keys[0], (sh["tokens"], of)) + jax.random.normal(keys[1], (of,))
        routed = jnp.bincount(jax.lax.top_k(logits, sh["top_k"])[1].reshape(-1), length=of)[:E]
        cases = {"routed": routed.astype(jnp.int32),
                 "one_group": jnp.zeros((E,), jnp.int32).at[E // 3].set(M)}
        for proj, (K, N) in {"gate": (sh["d_model"], sh["d_ff"]),
                             "down": (sh["d_ff"], sh["d_model"])}.items():
            lhs = jax.random.normal(keys[2], (M, K), jnp.bfloat16)
            rhs = jax.random.normal(keys[3], (L * E, K, N), jnp.bfloat16)
            for case, sizes in cases.items():
                stacked = jnp.zeros((L, E), jnp.int32).at[L // 2].set(sizes).reshape(-1)
                held = int(sizes.sum())    # the rows that belong to a group
                got, want = kernel(lhs, rhs, stacked)[:held], ragged(lhs, rhs, stacked)[:held]
                want32 = exact(lhs, rhs, stacked)[:held]
                err = roundings(got, want32)
                out[f"grouped_{model}_{proj}_{case}"] = {
                    "shape": [M, K, N, L * E], "largest_group": int(sizes.max()),
                    "max_abs_err": err, "tol": 1.0, "ragged_dot_err": roundings(want, want32),
                    "equal_share": float((got == want).mean()),
                    "ok": bool(jnp.isfinite(got.astype(jnp.float32)).all()) and err <= 1.0,
                    "kernel_ms": ms_a_call(kernel, lhs, rhs, stacked),
                    "ragged_dot_ms": ms_a_call(ragged, lhs, rhs, stacked)}
            del lhs, rhs
    return out


def compare_state_update(shape: dict, interpret: bool = False) -> dict:
    """`ops.ssm_state_update`'s Pallas kernel against its `jax.numpy` form on
    a state of several layers and many slots, both updated where they lie,
    over steps whose live rows are scattered and change: a third of the slots,
    others released and taken again (a taken slot starts from a fresh state,
    as an insert leaves it), one row in the last slot, none, all. After every
    step the WHOLE state leaf against leaf: the stepped layer's live rows
    within float32 rounding, its other rows and every other layer bit for
    bit; y within rounding on the live rows and 0 elsewhere."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu import ops

    L, R, H, P, N = (shape[k] for k in ("layers", "slots", "heads", "head_dim", "d_state"))
    rng = np.random.default_rng(0)
    f32 = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    states = {impl: f32(L, R, H, P, N) for impl in ("kernel",)}
    states["reference"] = states["kernel"] + 0
    A = -jnp.asarray(rng.uniform(1.0, 16.0, (H,)), jnp.float32)
    step = {impl: jax.jit(functools.partial(ops.ssm_state_update, impl=impl,
                                            **({"interpret": interpret}
                                               if impl == "kernel" else {})),
                          donate_argnums=(0,))
            for impl in states}
    third = rng.permutation(R)[:R // 3]
    again = np.concatenate([third[:len(third) // 2], rng.permutation(R)[:R // 4]])
    lives = [third, np.unique(again), np.asarray([R - 1]), np.asarray([], int), np.arange(R)]
    out, before = {"shape": dict(shape), "steps": []}, set()
    for rows in lives:
        live = np.zeros(R, bool)
        live[rows] = True
        taken = sorted(set(rows.tolist()) - before)
        before = set(rows.tolist())
        layer = jnp.int32(L // 2)
        x, dt, B, C = f32(R, H, P), jnp.abs(f32(R, H)) * 0.05, f32(R, N), f32(R, N)
        fresh, ys = f32(len(taken), H, P, N), {}
        for impl in states:
            s = states[impl].at[L // 2, jnp.asarray(taken, jnp.int32)].set(fresh)
            states[impl], ys[impl] = step[impl](s, layer, x, dt, A, B, C, live=jnp.asarray(live))
        got, want = (np.asarray(states[i]) for i in ("kernel", "reference"))
        y_got, y_want = (np.asarray(ys[i]) for i in ("kernel", "reference"))
        dead = np.ones((L, R), bool)
        dead[L // 2, live] = False
        tol = 8 * float(np.finfo(np.float32).eps)
        out["steps"].append({
            "live": int(live.sum()), "taken": len(taken),
            "state_rel_err": float(np.abs(got - want).max() / np.abs(want).max()),
            "y_rel_err": float(np.abs(y_got - y_want).max() / max(np.abs(y_want).max(), 1.0)),
            "others_bit_equal": bool((got[dead] == want[dead]).all()),
            "dead_y_zero": bool((y_got[~live] == 0).all())})
    out["max_abs_err"] = max(max(s["state_rel_err"], s["y_rel_err"]) for s in out["steps"])
    out["tol"] = N * tol   # y sums N products in another order
    out["ok"] = bool(out["max_abs_err"] <= out["tol"]
                     and all(s["others_bit_equal"] and s["dead_y_zero"] for s in out["steps"]))
    return out


def compare_kda_update(shape: dict, interpret: bool = False) -> dict:
    """`ops.kda_state_update`'s Pallas kernel against its `jax.numpy` form, as
    `compare_state_update`: a state [layers, slots, heads, head_dim, head_dim]
    of the gated delta rule, both updated where they lie, over steps whose live
    rows are scattered and change, log decays down to -5 and beta up to 2. After
    every step the WHOLE state: the stepped layer's live rows within float32
    rounding, its other rows and every other layer bit for bit; o within
    rounding on the live rows and 0 elsewhere."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu import ops

    L, R, H, D = (shape[k] for k in ("layers", "slots", "heads", "head_dim"))
    rng = np.random.default_rng(0)
    f32 = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    states = {"kernel": f32(L, R, H, D, D)}
    states["reference"] = states["kernel"] + 0
    step = {impl: jax.jit(functools.partial(ops.kda_state_update, impl=impl,
                                            **({"interpret": interpret}
                                               if impl == "kernel" else {})),
                          donate_argnums=(0,))
            for impl in states}
    third = rng.permutation(R)[:R // 3]
    again = np.concatenate([third[:len(third) // 2], rng.permutation(R)[:R // 4]])
    lives = [third, np.unique(again), np.asarray([R - 1]), np.asarray([], int), np.arange(R)]
    out, before = {"shape": dict(shape), "steps": []}, set()
    for rows in lives:
        live = np.zeros(R, bool)
        live[rows] = True
        taken = sorted(set(rows.tolist()) - before)
        before = set(rows.tolist())
        layer = jnp.int32(L // 2)
        q, k, v = unit(f32(R, H, D)) * D ** -0.5, unit(f32(R, H, D)), f32(R, H, D)
        g = -jnp.asarray(rng.uniform(0.0, 5.0, (R, H, D)), jnp.float32)
        beta = jnp.asarray(rng.uniform(0.0, 2.0, (R, H)), jnp.float32)
        fresh, os_ = f32(len(taken), H, D, D), {}
        for impl in states:
            s = states[impl].at[L // 2, jnp.asarray(taken, jnp.int32)].set(fresh)
            states[impl], os_[impl] = step[impl](s, layer, q, k, v, g, beta,
                                                 live=jnp.asarray(live))
        got, want = (np.asarray(states[i]) for i in ("kernel", "reference"))
        o_got, o_want = (np.asarray(os_[i]) for i in ("kernel", "reference"))
        dead = np.ones((L, R), bool)
        dead[L // 2, live] = False
        out["steps"].append({
            "live": int(live.sum()), "taken": len(taken),
            "state_rel_err": float(np.abs(got - want).max() / np.abs(want).max()),
            "o_rel_err": float(np.abs(o_got - o_want).max() / max(np.abs(o_want).max(), 1.0)),
            "others_bit_equal": bool((got[dead] == want[dead]).all()),
            "dead_o_zero": bool((o_got[~live] == 0).all())})
    out["max_abs_err"] = max(max(s["state_rel_err"], s["o_rel_err"]) for s in out["steps"])
    out["tol"] = D * 8 * float(np.finfo(np.float32).eps)   # sums of D products, twice
    out["ok"] = bool(out["max_abs_err"] <= out["tol"]
                     and all(s["others_bit_equal"] and s["dead_o_zero"] for s in out["steps"]))
    return out


def compare_kda_scan(shape: dict, interpret: bool = False) -> dict:
    """`ops.kda_chunk_scan`'s Pallas launch against its XLA form at a prefill
    chunk's shape: outputs and the state after `tokens` positions from zeros,
    then carried on over a `tail` of positions that ends in padding (g = 0,
    beta = 0), log decays down to -5 and beta up to 2. Both timed (not
    under `interpret`): the XLA form whole and by its parts (the decayed Gram
    matrices, the rest of the state-free preparation with its triangular
    solves, the scan over chunks), the launch whole (the op, as the model
    calls it) and alone on [T, H D] operands."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import ssm

    T, tail, H, D, Q = (shape[k] for k in ("tokens", "tail", "heads", "head_dim", "chunk"))
    rng = np.random.default_rng(0)
    f32 = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731

    def inputs(n, real):
        g = -jnp.asarray(rng.uniform(0.0, 0.3, (n, H, D)) * (rng.random((n, H, 1)) < 0.9)
                         + 5.0 * (rng.random((n, H, D)) < 0.02), jnp.float32)
        beta = jnp.asarray(np.where(rng.random((n, H)) < 0.3, 2.0 - 1e-3 * rng.random((n, H)),
                                    rng.uniform(0.0, 2.0, (n, H))), jnp.float32)
        live = (jnp.arange(n) < real)
        return (unit(f32(n, H, D)) * D ** -0.5, unit(f32(n, H, D)), f32(n, H, D),
                jnp.where(live[:, None, None], g, 0.0), jnp.where(live[:, None], beta, 0.0))

    forms = {
        "launch": jax.jit(lambda *a: ssm._kda_chunk_scan_pallas(*a, Q, ssm.KDA_SUB, interpret)),
        "xla": jax.jit(lambda *a: ssm._kda_chunk_scan_xla(*a, chunk=Q, sub=ssm.KDA_SUB))}
    first, second = inputs(T, T), inputs(tail, tail - Q - Q // 3)
    zeros = jnp.zeros((H, D, D), jnp.float32)
    got, want = forms["launch"](*first, zeros), forms["xla"](*first, zeros)
    got2, want2 = forms["launch"](*second, got[1]), forms["xla"](*second, want[1])
    rel = lambda a, b: float(jnp.abs(a - b).max() / jnp.abs(b).max())  # noqa: E731
    out = {"shape": dict(shape),
           "o_rel_err": rel(got[0], want[0]), "state_rel_err": rel(got[1], want[1]),
           "carried_o_rel_err": rel(got2[0], want2[0]),
           "carried_state_rel_err": rel(got2[1], want2[1]),
           "finite": bool(all(jnp.isfinite(x).all() for x in (*got, *got2)))}
    out["max_abs_err"] = max(v for k, v in out.items() if k.endswith("_rel_err"))
    out["tol"] = 2e-5   # tests/test_solar_open2.py's bound against the recurrence
    out["ok"] = out["finite"] and out["max_abs_err"] <= out["tol"]
    if interpret:
        return out

    def ms(fn, *a, n=10):
        jax.block_until_ready(fn(*a))
        t0 = time.perf_counter()
        for _ in range(n):
            r = fn(*a)
        jax.block_until_ready(r)
        return (time.perf_counter() - t0) / n * 1e3

    chunks = lambda t: t.reshape(T // Q, Q, *t.shape[1:])  # noqa: E731
    q, k, v, g, beta = (chunks(t) for t in first)
    G = jnp.cumsum(g, axis=1)
    grams_fn = jax.jit(lambda q, k, G: jax.lax.map(
        jax.vmap(lambda one: ssm._decayed_grams(jnp.stack(one[:2]), one[1], one[2],
                                                ssm.KDA_SUB)),
        tuple(t.reshape(T // Q // 8, 8, *t.shape[1:]) for t in (q, k, G))))
    grams = grams_fn(q, k, G).reshape(T // Q, 2, H, Q, Q)
    prepare = jax.jit(functools.partial(ssm._kda_prepare, sub=ssm.KDA_SUB))
    flat = tuple(t.reshape(T, -1) for t in first[:4])
    launch = jax.jit(functools.partial(ssm._kda_scan_launch, chunk=Q, interpret=False))
    out["ms"] = {"xla": ms(forms["xla"], *first, zeros),
                 "xla_grams": ms(grams_fn, q, k, G),
                 "xla_prepare": ms(prepare, q, k, v, G, beta, grams),
                 "launch": ms(forms["launch"], *first, zeros),
                 "launch_alone": ms(launch, *flat, first[4], zeros)}
    return out


def compare_kda_mixer(shape: dict, interpret: bool = False) -> dict:
    """The KDA mixer's three launches around its scan (`kda_conv`,
    `kda_split`, `kda_gate_norm`) against their XLA forms (`ops.causal_conv`
    of a float32 copy, and the bodies of `transformer.kda_split` and
    `kda_gated_norm` that the CPU and the decode step run) at a prefill chunk's
    shape, the projection and the tail bfloat16, the tail not zero. Both
    timed (not under `interpret`): each piece as the model calls it, on [T, H,
    D] operands (alone, such an operand or result is re-laid from or to the
    launches' [T, H D]; inside a program the reshapes cancel), each side of
    the scan whole with its product (`in_qkv`, the convolutions and the split
    before it; the gated norm and `out_proj` after it), and each launch alone
    on [T, H D] operands."""
    from unittest import mock

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import solar_open2_config, transformer
    from ray_tpu.ops import ssm

    T, H, D, K, E = (shape[k] for k in ("tokens", "heads", "head_dim", "d_conv", "d_model"))
    cfg = solar_open2_config("tiny", d_model=E, dtype=jnp.bfloat16, ssm=transformer.KDAConfig(
        n_heads=H, d_head=D, d_conv=K))
    rng = np.random.default_rng(0)
    arr = lambda *s, dt=jnp.bfloat16, by=1.0: jnp.asarray(  # noqa: E731
        by * rng.standard_normal(s), dt)
    x, tail = arr(T, E), arr(K - 1, 3 * H * D)
    p = {"in_qkv": arr(E, 3 * H * D, by=E ** -0.5), "conv_w": arr(K, 3 * H * D, by=0.5),
         "norm": arr(D, by=0.1) + 1, "out_proj": arr(H * D, E, by=0.02)}
    o, gate = arr(T, H, D, dt=jnp.float32), arr(T, H, D, dt=jnp.float32, by=3.0)

    def sides(launch: bool) -> dict:
        """The jitted pieces, traced as the launches (`interpret` as given) or
        as the XLA forms, and each one's operands and result."""
        kw = dict(interpret=interpret and launch)

        def conv(qkv, tail, w):
            return transformer.kda_conv(qkv, tail, w, cfg, **kw)

        def split(conved):
            return transformer.kda_split(conved, cfg, **kw)

        def gate_norm(o, gate, p):  # what out_proj multiplies
            return transformer.kda_gated_norm(o, gate, p["norm"], cfg, **kw)

        def before_scan(x, tail, p):
            return split(conv(x @ p["in_qkv"], tail, p["conv_w"]))

        def after_scan(o, gate, p):
            return gate_norm(o, gate, p) @ p["out_proj"]

        chooses = ssm.kda_mixer_in_kernel if launch else lambda *a: False
        with mock.patch.object(ssm, "kda_mixer_in_kernel", chooses):
            conved = jax.jit(conv)(qkv, tail, p["conv_w"])
            made = {}
            for fn, a in ((conv, (qkv, tail, p["conv_w"])), (split, (conved,)),
                          (gate_norm, (o, gate, p)), (before_scan, (x, tail, p)),
                          (after_scan, (o, gate, p))):
                jitted = jax.jit(fn)
                made[fn.__name__] = (jitted, a, jitted(*a))
            made["jaxpr"] = str(jax.make_jaxpr(lambda: (before_scan(x, tail, p),
                                                        after_scan(o, gate, p)))())
        return made

    qkv = jax.jit(lambda x, p: x @ p["in_qkv"])(x, p)
    forms = {"launch": sides(True), "xla": sides(False)}
    names = [n for n in forms["xla"] if n != "jaxpr"]
    f32 = lambda t: jnp.asarray(t, jnp.float32)  # noqa: E731

    def rel(name):
        got, want = (jax.tree.leaves(forms[f][name][2]) for f in ("launch", "xla"))
        return max(float(jnp.abs(f32(g) - f32(w)).max() / jnp.abs(f32(w)).max())
                   for g, w in zip(got, want))

    out = {"shape": dict(shape), "rel_err": {name: rel(name) for name in names},
           "launched": {form: re.findall(r"name=(kda_conv|kda_split|kda_gate_norm)\b",
                                         forms[form].pop("jaxpr")) for form in forms}}
    # float32 sums on both sides: the order of a sum and a fused multiply-add.
    # The gated norm's result is rounded to bfloat16 (an ulp: 2 ** -8), and
    # behind a product so is what the launch reads: XLA hands its own consumer
    # the product's float32 sums unrounded (`xla_allow_excess_precision`)
    out["tol"] = {"conv": 1e-5, "split": 1e-5, "gate_norm": 2 ** -7,
                  "before_scan": 2 ** -6, "after_scan": 2 ** -6}
    out["max_abs_err"] = max(out["rel_err"].values())
    out["ok"] = bool(all(out["rel_err"][n] <= out["tol"][n] for n in names)
                     and out["launched"] == {
                         "launch": ["kda_conv", "kda_split", "kda_gate_norm"], "xla": []})
    if interpret:
        return out

    def ms(fn, *a, n=10):
        jax.block_until_ready(fn(*a))
        t0 = time.perf_counter()
        for _ in range(n):
            r = fn(*a)
        jax.block_until_ready(r)
        return (time.perf_counter() - t0) / n * 1e3

    out["ms"] = {form: {name: ms(fn, *a) for name, (fn, a, _) in forms[form].items()}
                 for form in forms}
    conved = forms["launch"]["conv"][2]
    out["ms"]["launch_alone"] = {
        "conv": ms(jax.jit(functools.partial(ssm.kda_conv_launch, interpret=False)),
                   qkv, tail, p["conv_w"]),
        "split": ms(jax.jit(functools.partial(ssm.kda_split_launch, interpret=False)), conved),
        "gate_norm": ms(jax.jit(functools.partial(
            ssm.kda_gate_norm_launch, eps=cfg.norm_eps, dtype=cfg.dtype, interpret=False)),
            o.reshape(T, -1), gate.reshape(T, -1), p["norm"])}
    out["ms"]["in_qkv"] = ms(jax.jit(lambda x, p: x @ p["in_qkv"]), x, p)
    out["ms"]["out_proj"] = ms(jax.jit(lambda y, p: y @ p["out_proj"]),
                               forms["launch"]["gate_norm"][2], p)
    return out


def compare_kernels(flash, ragged, grouped=None, state_update=None, kda_update=None,
                    kda_scan=None, kda_mixer=None, interpret: bool = False) -> dict:
    """The Pallas kernels against their pure-JAX references at the given
    shapes, bf16. `interpret` is for the CPU rehearsal only."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops.flash_attention import flash_attention
    from ray_tpu.ops.ragged_paged_attention import ragged_decode_attention
    from ray_tpu.parallel import reference_attention

    def f32(x):
        return x.astype(jnp.float32)

    def report(got, want):
        # two bf16 ulps at the reference's largest magnitude
        tol = 2 * float(jnp.finfo(jnp.bfloat16).eps) * float(jnp.abs(f32(want)).max())
        err = float(jnp.abs(f32(got) - f32(want)).max())
        return {"max_abs_err": err, "tol": tol,
                "ok": bool(jnp.isfinite(f32(got)).all()) and err <= tol}

    rng = np.random.default_rng(0)
    B, H, T, D = flash
    q, k, v, w = (jnp.asarray(rng.standard_normal((B, H, T, D)), jnp.bfloat16)
                  for _ in range(4))
    def flash_fn(q, k, v):  # the block sizes the kernels choose, as ops.attention runs them
        return flash_attention(q, k, v, True, None, None, None, interpret)

    def ref_fn(q, k, v):  # reference takes [B, T, H, D]
        qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
        return reference_attention(qt, kt, vt, causal=True).transpose(0, 2, 1, 3)

    def grads(fn):
        return jax.jit(jax.grad(lambda q, k, v: (f32(fn(q, k, v)) * f32(w)).sum(),
                                argnums=(0, 1, 2)))(q, k, v)

    out = {"flash_shape": list(flash),
           "flash_fwd": report(jax.jit(flash_fn)(q, k, v), jax.jit(ref_fn)(q, k, v))}
    for name, got, want in zip("qkv", grads(flash_fn), grads(ref_fn)):
        out[f"flash_d{name}"] = report(got, want)

    Bs, Hkv, G, Dh, P, nb = (ragged[k] for k in (
        "batch", "kv_heads", "group", "head_dim", "page", "pages_per_seq"))
    n_pages = Bs * nb + 1
    qd = jnp.asarray(rng.standard_normal((Bs, Hkv, G, Dh)), jnp.bfloat16)
    kp, vp = (jnp.asarray(rng.standard_normal((n_pages, P, Hkv, Dh)), jnp.bfloat16)
              for _ in range(2))
    table = jnp.asarray(1 + rng.permutation(n_pages - 1).reshape(Bs, nb), jnp.int32)
    pos = jnp.asarray(rng.integers(0, nb * P, (Bs,)), jnp.int32)
    got = jax.jit(lambda *a: ragged_decode_attention(
        *a, impl="kernel", interpret=interpret))(qd, kp, vp, table, pos)
    want = jax.jit(lambda *a: ragged_decode_attention(
        *a, impl="reference"))(qd, kp, vp, table, pos)
    out["ragged_shape"] = dict(ragged)
    out["ragged"] = {**report(got, want), "bit_equal": bool((got == want).all())}
    out.update(compare_grouped_matmul(grouped or {}, interpret))
    if state_update:
        out["state_update"] = compare_state_update(state_update, interpret)
    if kda_update:
        out["kda_update"] = compare_kda_update(kda_update, interpret)
    if kda_scan:
        out["kda_scan"] = compare_kda_scan(kda_scan, interpret)
    if kda_mixer:
        out["kda_mixer"] = compare_kda_mixer(kda_mixer, interpret)
    return out


def run_train_steps(cfg, mesh, batches, *, lr: float, seed: int, report) -> dict:
    """`train/spmd.py`'s step on `mesh`: init sharded, compile apart from
    run, one AdamW step per batch. Returns what the worker saw."""
    import jax
    import optax

    from ray_tpu.models import transformer
    from ray_tpu.train.spmd import init_opt_state, init_sharded, make_train_step

    axes = transformer.logical_axes(cfg)
    opt = optax.adamw(lr)
    step, _, batch_sharding = make_train_step(
        lambda p, tokens: transformer.loss_fn(p, tokens, cfg), axes, mesh, opt)
    t0 = time.perf_counter()
    params = init_sharded(lambda key: transformer.init(key, cfg), axes, mesh,
                          jax.random.PRNGKey(seed))
    opt_state = init_opt_state(opt, params)
    leaves = jax.tree.leaves(params)
    jax.block_until_ready(leaves)
    init_s = time.perf_counter() - t0
    devices = list(mesh.devices.flat)
    held = {d.id: 0 for d in devices}  # bytes of params + optimizer state
    for x in jax.tree.leaves((params, opt_state)):
        for shard in x.addressable_shards:
            held[shard.device.id] += shard.data.nbytes
    facts = {
        "mesh": {a: n for a, n in mesh.shape.items() if n > 1},
        "params": sum(x.size for x in leaves),
        "param_shard_devices": sorted(
            {s.device.id for x in leaves for s in x.addressable_shards}),
        "state_bytes_per_device": [held[d.id] for d in devices],
        "bytes_in_use_after_init": [
            (d.memory_stats() or {}).get("bytes_in_use") for d in devices],
        "init_s": init_s,
    }
    first = jax.device_put(batches[0], batch_sharding)
    t0 = time.perf_counter()
    compiled = step.lower(params, opt_state, first).compile()
    facts["compile_s"] = time.perf_counter() - t0
    # asserted from the program that runs below, not assumed
    facts["tpu_custom_call"] = "tpu_custom_call" in compiled.as_text()
    losses = []
    t0 = time.perf_counter()
    for i, tokens in enumerate(batches):
        params, opt_state, loss = compiled(
            params, opt_state, jax.device_put(tokens, batch_sharding))
        losses.append(float(loss))
        report({"step": i, "loss": losses[-1]})
    facts["run_s"] = time.perf_counter() - t0
    facts["steps"] = len(losses)
    facts["losses"] = losses
    facts["peak_bytes_in_use"] = [
        (d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    return facts


def train_loop(config: dict) -> None:
    """`train_loop_per_worker` of the smoke's JaxTrainer: runs in the worker
    the GCS bound the chip(s) to."""
    import jax
    import numpy as np

    from ray_tpu import models, train
    from ray_tpu._private import accelerators
    from ray_tpu.parallel import MeshSpec

    accelerators.compile_cache_counts()  # count from before the first compile
    facts = {"device": accelerators.device_report(),
             "worker_chips": accelerators.current_worker_chips()}
    if config.get("kernels"):
        facts["sync_primitive"] = probe_sync_primitive()
        facts["kernels"] = compare_kernels(**config["kernels"])
    cfg = getattr(models, config["family"] + "_config")(
        config["size"], **config["model_kwargs"])
    batches = [np.asarray(b["tokens"], np.int32) for b in
               train.get_dataset_shard("train").iter_batches(
                   batch_size=config["batch"])]
    facts["runs"] = []
    for mesh_axes in config["meshes"]:
        spec = MeshSpec(**mesh_axes)
        facts["runs"].append(run_train_steps(
            cfg, spec.build(jax.devices()[:spec.size()]), batches,
            lr=config["lr"], seed=config["seed"], report=train.report))
    facts["compile_cache"] = accelerators.compile_cache_counts()
    train.report({"facts": facts})


def tp_decode_compare(spec: dict, seed: int) -> dict:
    """In one process that holds every chip: greedy tokens of the unsharded
    engine against the engine sharded over a one-axis mesh of all devices."""
    import jax
    import numpy as np

    from ray_tpu import models
    from ray_tpu._private import accelerators
    from ray_tpu.llm import SamplingParams, TPUEngine
    from ray_tpu.models import transformer

    cfg = getattr(models, spec["family"] + "_config")(
        spec["model_id"], **spec["model_kwargs"])
    params = transformer.init(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, 256, n).tolist() for n in spec["prompt_tokens"]]
    sp = SamplingParams(max_tokens=spec["max_tokens"], temperature=0.0)
    out = {"device": accelerators.device_report()}
    # process-wide, because the engine traces in its own thread: at the
    # TPU's default precision a float32 matmul multiplies in bf16, and the
    # two engines round differently (Pallas kernel against XLA, partial
    # sums in another order) — enough to flip a greedy argmax
    jax.config.update("jax_default_matmul_precision", spec["matmul_precision"])
    try:
        for name, mesh in (("unsharded", None),
                           ("tp", jax.sharding.Mesh(np.array(jax.devices()), ("tp",)))):
            engine = TPUEngine(cfg, params, mesh=mesh, **spec["engine_kwargs"])
            try:
                out[name] = {"tokens": [engine.generate(p, sp) for p in prompts],
                             "decode_attn": engine.stats()["decode_attn"]}
            finally:
                engine.shutdown()
    finally:
        jax.config.update("jax_default_matmul_precision", None)
    return out


# ------------------------------------------------------- in the driver


def make_token_dataset(n_batches: int, batch: int, seq: int, vocab: int, seed: int):
    """`n_batches` copies of ONE batch of seeded random token rows
    [batch, seq + 1], made by `ray_tpu.data` map tasks: the loss on a
    repeated batch has to fall."""
    import ray_tpu.data as rdata

    def tokens(rows: dict) -> dict:
        import numpy as np

        return {"tokens": np.stack([
            np.random.default_rng(seed * 100003 + int(i) % batch).integers(
                0, vocab, seq + 1, dtype=np.int32) for i in rows["id"]])}

    return rdata.range(n_batches * batch, parallelism=n_batches).map_batches(
        tokens, batch_size=batch)


def chip_worker(chips: int):
    """One train worker that holds `chips` chips."""
    from ray_tpu import train

    return train.ScalingConfig(num_workers=1, use_tpu=True,
                               resources_per_worker={"TPU": chips})


def run_train_phase(spec: dict, scaling, *, seed: int, kernels: dict | None) -> dict:
    """JaxTrainer over `scaling`'s one worker; returns that worker's own
    account of what it ran and on what."""
    from ray_tpu import models, train

    cfg = getattr(models, spec["family"] + "_config")(
        spec["size"], **spec["model_kwargs"])
    trainer = train.JaxTrainer(
        train_loop,
        train_loop_config={**spec, "seed": seed, "kernels": kernels},
        scaling_config=scaling,
        run_config=train.RunConfig(name="chip_smoke_train"),
        datasets={"train": make_token_dataset(
            spec["steps"], spec["batch"], spec["seq"], cfg.vocab_size, seed)},
    )
    t0 = time.perf_counter()
    result = trainer.fit()
    facts = result.metrics["facts"]
    facts["phase_s"] = time.perf_counter() - t0
    return facts


def check_worker_device(facts: dict, chips: int, who: str) -> None:
    dev = facts["device"]
    check(dev["platform"] == "tpu",
          f"{who} computes on {dev['platform']!r}, not a TPU: it was given no chip")
    check(dev["count"] == chips, f"{who} sees {dev['count']} devices, wants {chips}")


def check_kernels(kernels: dict) -> None:
    for name, r in kernels.items():
        if isinstance(r, dict) and "ok" in r:
            check(r["ok"], f"kernel {name} disagrees with its reference: "
                           f"max|err| {r['max_abs_err']} > tol {r['tol']}")


def check_train_run(run: dict, *, min_steps: int, want_kernel: bool) -> None:
    losses = run["losses"]
    check(len(losses) >= min_steps, f"train took {len(losses)} steps, wants {min_steps}")
    check(all(x == x and abs(x) != float("inf") for x in losses),
          f"train loss not finite: {losses}")
    check(len(losses) < 2 or losses[-1] < losses[0],
          f"loss on a repeated batch did not fall: {losses}")
    if want_kernel:
        check(run["tpu_custom_call"],
              "the compiled train step holds no tpu_custom_call: flash "
              "attention gave way to the reference")


def http_post(url: str, body: dict, timeout: float = 300.0):
    """POST JSON; a streamed (SSE) answer comes back as its list of chunks."""
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        if not body.get("stream"):
            return json.loads(resp.read())
        chunks = []
        for line in resp:
            line = line.strip()
            if line == b"data: [DONE]":
                break
            if line.startswith(b"data: "):
                chunks.append(json.loads(line[6:]))
        return chunks


def post_all_at_once(url: str, bodies: list[dict]) -> list:
    """Every body in flight at the same time, one thread each; the answers in
    order. Any failed or hung request fails the run."""
    answers: list = [None] * len(bodies)
    errors: list = []

    def client(i):
        try:
            answers[i] = http_post(url, bodies[i])
        except Exception as e:  # noqa: BLE001 — reported below, fails the run
            errors.append(f"request {i}: {type(e).__name__}: {e}")

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(bodies))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600.0)
    check(not any(t.is_alive() for t in threads), "a request never returned")
    check(not errors, f"requests failed: {errors}")
    return answers


def answer_token_ids(answer) -> list:
    if isinstance(answer, list):  # streamed chunks; the last one only closes
        return [t for c in answer for t in c["choices"][0].get("token_ids", [])]
    return answer["choices"][0]["token_ids"]


def make_prompt(n_tokens: int, rng) -> str:
    """ASCII text that the byte tokenizer turns into exactly n_tokens (BOS
    included)."""
    return "".join(chr(c) for c in rng.integers(97, 123, n_tokens - 1))


def deploy_llm(llm_config, *, replicas: int, timeout_s: float):
    """serve.run + the HTTP proxy; returns (base_url, handle) once every
    replica passed a health probe, or raises at the deadline."""
    from ray_tpu import serve
    from ray_tpu.llm import build_openai_app

    serve.start(http_port=0)
    serve.run(build_openai_app(llm_config), name="llm")
    deadline = time.monotonic() + timeout_s
    while True:
        health = [h for st in serve.status().values()
                  for h in st["replica_health"].values()]
        if len(health) == replicas and all(h == "healthy" for h in health):
            break
        check(time.monotonic() < deadline,
              f"{replicas} LLM replica(s) not healthy after {timeout_s:.0f}s "
              f"(replica_health: {health}): a replica that is given no chip "
              "cannot start")
        time.sleep(0.5)
    host, port = serve.http_address()
    return f"http://{host}:{port}"


def replica_stats() -> list[dict]:
    """`TPUEngine.stats()` of every replica of the app, each asked directly."""
    import ray_tpu
    from ray_tpu.actor import ActorHandle
    from ray_tpu.serve.api import _get_controller

    table = ray_tpu.get(_get_controller().get_routing_table.remote(-1))
    ids = [rid for d in table["deployments"].values() for rid in d["replicas"]]
    return ray_tpu.get([ActorHandle(rid).handle_request.remote(
        "engine_stats", (), {}) for rid in ids], timeout=120.0)


def llm_config_for(spec: dict, **overrides):
    from ray_tpu.llm import LLMConfig, ModelLoadingConfig

    return LLMConfig(
        model_family=spec["family"],
        model_loading_config=ModelLoadingConfig(model_id=spec["model_id"]),
        model_kwargs=spec["model_kwargs"], engine_kwargs=spec["engine_kwargs"],
        **overrides)


def run_serve_phase(spec: dict, *, seed: int, ready_timeout_s: float = 900.0,
                    **config_overrides) -> dict:
    """One replica behind the HTTP proxy: three sequential requests (one per
    prompt length), then six in flight at once, one of them streamed."""
    import numpy as np

    t0 = time.perf_counter()
    base = deploy_llm(llm_config_for(spec, **config_overrides), replicas=1,
                      timeout_s=ready_timeout_s)
    ready_s = time.perf_counter() - t0
    rng = np.random.default_rng(seed)
    short, mid, long_ = (make_prompt(n, rng) for n in spec["prompt_tokens"])
    short2, mid2 = (make_prompt(n, rng) for n in spec["prompt_tokens"][:2])
    url = base + "/v1/completions"
    n = spec["max_tokens"]

    def body(prompt, **extra):
        return {"prompt": prompt, "max_tokens": n, "temperature": 0.0, **extra}

    t0 = time.perf_counter()
    sequential = [http_post(url, body(p)) for p in (short, mid, long_)]
    sequential_s = time.perf_counter() - t0
    before = http_post(base + "/v1/stats", {})

    wave = [body(short), body(short, stream=True), body(mid), body(long_),
            body(short2), body(mid2)]
    t0 = time.perf_counter()
    answers = post_all_at_once(url, wave)
    concurrent_s = time.perf_counter() - t0
    stats = http_post(base + "/v1/stats", {})

    ids = [answer_token_ids(a) for a in sequential + answers]
    steps = stats["decode_steps"] - before["decode_steps"]
    slot_steps = (stats["decode_occupancy"] * stats["decode_steps"]
                  - before["decode_occupancy"] * before["decode_steps"])
    return {
        "requests": len(ids), "concurrent": len(wave),
        "streamed": sum(1 for b in wave if b.get("stream")),
        "prompt_tokens": [a["usage"]["prompt_tokens"] for a in sequential],
        "tokens_asked": n, "tokens_returned": [len(x) for x in ids],
        "usage_completion_tokens": [
            a["usage"]["completion_tokens"] for a in sequential + answers
            if isinstance(a, dict)],
        # the same greedy request three times: alone, in the wave, streamed
        "same_request_token_ids": [ids[0], ids[3], ids[4]],
        "concurrent_decode_occupancy": slot_steps / steps if steps else 0.0,
        "engine": stats, "ready_s": ready_s, "sequential_s": sequential_s,
        "concurrent_s": concurrent_s,
    }


def check_serve(facts: dict, spec: dict) -> None:
    stats = facts["engine"]
    check_worker_device(stats, 1, "the serve replica")
    check(stats["decode_attn"] == "ragged_kernel",
          f"decode attention ran as {stats['decode_attn']!r}, not the Pallas "
          "ragged kernel")
    n = facts["tokens_asked"]
    check(all(t == n for t in facts["tokens_returned"]),
          f"requests asked for {n} tokens and got {facts['tokens_returned']}")
    check(all(t == n for t in facts["usage_completion_tokens"]),
          f"usage reports {facts['usage_completion_tokens']} tokens, asked {n}")
    a, b, c = facts["same_request_token_ids"]
    check(a == b == c, f"one greedy request, three answers: {a} / {b} / {c}")
    check(facts["prompt_tokens"] == list(spec["prompt_tokens"]),
          f"prompts tokenized to {facts['prompt_tokens']}")
    check(facts["concurrent_decode_occupancy"] > 1.0,
          "no decode step held more than one request: continuous batching "
          "did not engage")
    check(stats["free_slots"] == stats["max_slots"]
          and stats["free_pages"] == stats["num_pages"] - 1,
          f"slots or pages not reclaimed: {stats}")


def run_replicas_phase(spec: dict, *, replicas: int, seed: int,
                       ready_timeout_s: float = 900.0, **config_overrides) -> dict:
    """`replicas` one-chip replicas behind the router: waves of distinct
    requests over HTTP until every replica has decoded, then each replica's
    own account of its device."""
    import numpy as np

    cfg = llm_config_for(spec, deployment_config={"num_replicas": replicas},
                         **config_overrides)
    base = deploy_llm(cfg, replicas=replicas, timeout_s=ready_timeout_s)
    rng = np.random.default_rng(seed)
    sent = 0
    for _ in range(8):
        answers = post_all_at_once(base + "/v1/completions", [
            {"prompt": make_prompt(spec["prompt_tokens"][0], rng),
             "max_tokens": spec["max_tokens"], "temperature": 0.0}
            for _ in range(2 * replicas)])
        check(all(len(answer_token_ids(a)) == spec["max_tokens"] for a in answers),
              "a replica returned fewer tokens than asked")
        sent += len(answers)
        stats = replica_stats()
        if all(s["decode_steps"] > 0 for s in stats):
            break
    return {"requests": sent, "replicas": [
        {k: s[k] for k in ("device", "worker_chips", "decode_attn",
                           "decode_steps", "device_memory")} for s in stats]}


def wait_chips_free(chips: int, timeout_s: float = 120.0) -> None:
    """A chip returns to the pool when the worker that held it is gone; the
    next phase's worker must not race the last one's exit for it."""
    import ray_tpu

    deadline = time.monotonic() + timeout_s
    while ray_tpu.available_resources().get("TPU", 0) < chips:
        check(time.monotonic() < deadline,
              f"{chips} chip(s) not free again after {timeout_s:.0f}s: "
              f"{ray_tpu.available_resources()}")
        time.sleep(0.2)


def shm_leftovers() -> set:
    return set(glob.glob("/dev/shm/rtpu_*"))


def smoke_one_chip(seed: int) -> dict:
    from ray_tpu import serve

    train = run_train_phase(TRAIN, chip_worker(1), seed=seed, kernels=KERNELS)
    say("kernels", device=train["device"], **train["kernels"])
    say("sync_primitive", **train["sync_primitive"])
    say("train", model=f"{TRAIN['family']}-{TRAIN['size']}", batch=TRAIN["batch"],
        seq=TRAIN["seq"], worker_chips=train["worker_chips"],
        compile_cache=train["compile_cache"], phase_s=train["phase_s"],
        **train["runs"][0])
    check_worker_device(train, 1, "the train worker")
    check(train["sync_primitive"]["blocks"],
          f"block_until_ready does not wait: {train['sync_primitive']}")
    check_kernels(train["kernels"])
    check_train_run(train["runs"][0], min_steps=5, want_kernel=True)

    wait_chips_free(1)  # the train worker has exited before the replica starts
    t0 = time.perf_counter()
    try:
        served = run_serve_phase(SERVE, seed=seed)
    finally:
        serve.shutdown()
    engine = served.pop("engine")
    say("serve", model=f"{SERVE['family']}-{SERVE['model_id']}",
        phase_s=time.perf_counter() - t0, **served,
        **{k: engine[k] for k in (
            "device", "worker_chips", "decode_attn", "page_size",
            "max_slots", "decode_steps", "device_memory", "compile_cache")})
    check_serve({**served, "engine": engine}, SERVE)
    check(train["device"] == engine["device"],
          f"train worker and replica disagree: {train['device']} / {engine['device']}")
    return train["device"]


def smoke_four_chips(seed: int) -> dict:
    import jax.numpy as jnp

    import ray_tpu
    from ray_tpu import serve

    # float32 at full matmul precision for this comparison alone: greedy
    # argmax over 128k nearly uniform random-weight logits does not survive
    # the bf16 rounding that differs between the two engines. (The second
    # prompt is long enough for the flash prefill kernel, which a sharded
    # engine runs per shard.)
    tp_spec = {**SERVE, "prompt_tokens": (48, 1100), "max_tokens": 8,
               "matmul_precision": "highest",
               "model_kwargs": {**SERVE["model_kwargs"], "dtype": jnp.float32}}
    tp = ray_tpu.get(ray_tpu.remote(num_tpus=4)(tp_decode_compare).remote(
        tp_spec, seed), timeout=1800.0)
    say("tp_decode_vs_unsharded", model=f"{SERVE['family']}-{SERVE['model_id']}", **tp)
    check_worker_device(tp, 4, "the tensor-parallel decode task")
    check(tp["tp"]["tokens"] == tp["unsharded"]["tokens"],
          f"tensor-parallel decode diverged: {tp['tp']['tokens']} vs "
          f"{tp['unsharded']['tokens']}")
    check(all(len(t) == tp_spec["max_tokens"] for t in tp["tp"]["tokens"]),
          f"tensor-parallel decode returned {tp['tp']['tokens']}")

    wait_chips_free(4)
    # 774M divides by fsdp=4 (d_model 1280); its vocab 50257 is odd, so no tp
    spec = {**TRAIN, "steps": 2, "meshes": [{"fsdp": 4}, {}]}
    train = run_train_phase(spec, chip_worker(4), seed=seed, kernels=None)
    sharded, single = train["runs"]
    say("train_fsdp4_vs_one_device", model=f"{TRAIN['family']}-{TRAIN['size']}",
        batch=TRAIN["batch"], seq=TRAIN["seq"], device=train["device"],
        worker_chips=train["worker_chips"], phase_s=train["phase_s"],
        sharded=sharded, one_device=single)
    check_worker_device(train, 4, "the train worker")
    check(len(sharded["param_shard_devices"]) == 4,
          f"parameters live on devices {sharded['param_shard_devices']}, not on four")
    # f32 params + two Adam moments, a quarter on each device: code that has
    # only seen one chip tends to leave the state on device 0
    for held in (sharded["state_bytes_per_device"], sharded["bytes_in_use_after_init"]):
        check(all(held) and max(held) < 1.5 * min(held)
              and min(held) > 4 * sharded["params"] * 3 / 4 / 2,
              f"parameters and optimizer state are not spread over the four "
              f"devices: {held} bytes")
    for run in (sharded, single):
        check_train_run(run, min_steps=2, want_kernel=True)
    d = abs(sharded["losses"][0] - single["losses"][0])
    check(d <= 1e-2, f"first-step loss differs by {d}: fsdp=4 "
                     f"{sharded['losses'][0]} vs one device {single['losses'][0]}")

    wait_chips_free(4)
    try:
        reps = run_replicas_phase(SERVE, replicas=4, seed=seed)
    finally:
        serve.shutdown()
    say("four_replicas", model=f"{SERVE['family']}-{SERVE['model_id']}", **reps)
    check(len(reps["replicas"]) == 4, f"{len(reps['replicas'])} replicas answered")
    for r in reps["replicas"]:
        check_worker_device(r, 1, "a serve replica")
        check(r["decode_steps"] > 0, f"a replica never decoded: {r}")
    chips = sorted(tuple(r["worker_chips"]) for r in reps["replicas"])
    check(len(set(chips)) == 4, f"replicas share chips: {chips}")
    return train["device"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import ray_tpu
    from ray_tpu._private import accelerators

    found = accelerators.detect_num_tpu_chips()
    if found < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s) and this host "
              f"exposes {found} (no /dev/accel<N> or /dev/vfio/<N>). There is "
              "no CPU mode.", file=sys.stderr)
        return 2

    # one cache for this driver's session: every worker inherits the variable
    accelerators.export_compile_cache_env()
    shm_before = shm_leftovers()
    t0 = time.perf_counter()
    ray_tpu.init(num_tpus=args.chips)
    try:
        from ray_tpu._private.api import _get_worker

        say("session", chips=args.chips, seed=args.seed,
            object_store=type(_get_worker().store).__name__,
            compile_cache_dir=os.environ[accelerators.COMPILE_CACHE_ENV])
        device = (smoke_one_chip if args.chips == 1 else smoke_four_chips)(args.seed)
    finally:
        ray_tpu.shutdown()
    left = shm_leftovers() - shm_before
    check(not left, f"/dev/shm objects left after shutdown: {sorted(left)}")
    say("done", total_s=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
