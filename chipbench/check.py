"""The comparison that decides `correct`: the program at published widths on
the chip against the configuration's plain reference (chipbench/reference/),
from the same weights, on a seeded sample, after the measured window.

Tolerances live in the configuration file (`check`), each with its reason.
Every check also runs a control: the program once more from operands rounded
to the precision of 8 bits (`coarse`: 3 bits of mantissa, as float8_e4m3 has,
where bfloat16 has 7) against the same reference. The control has to FAIL the tolerance, or the
tolerance could not tell a lower precision from the stated one, and
`correct` is false.
"""

from __future__ import annotations

import importlib

import numpy as np

from chipbench import harness


def _reference(conf: dict):
    return importlib.import_module(f"chipbench.reference.{conf['reference']}")


def _rel(a, b) -> float:
    """|a - b|_2 / |b - mean(b)|_2 in float64 on the host."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b - b.mean()), 1e-30))


def coarse_program(donate: bool):
    """The jitted rounding of every floating leaf to 3 bits of mantissa, the
    precision of float8_e4m3 (bfloat16 has 7), by integer arithmetic on the
    bits: a conversion to float8 and back is excess precision to XLA, which
    dropped the pair on the TPU and left the control equal to the run."""
    import jax
    import jax.numpy as jnp

    def one(x):
        if not jnp.issubdtype(x.dtype, jnp.floating):
            return x
        uint = {2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]
        drop = jnp.finfo(x.dtype).nmant - 3
        bits = jax.lax.bitcast_convert_type(x, uint)
        bits = (bits + uint(1 << (drop - 1))) & uint(~((1 << drop) - 1) & (2 ** (8 * x.dtype.itemsize) - 1))
        return jax.lax.bitcast_convert_type(bits, x.dtype)

    return jax.jit(lambda p: jax.tree.map(one, p),
                   donate_argnums=(0,) if donate else ())


def coarse(params, donate: bool = False):
    """`params` with operands of 8 bits, on the device; in place with `donate`."""
    return coarse_program(donate)(params)


def _leaf(tree, path: str):
    for key in path.split("/"):
        tree = tree[key]
    return tree


def _with_leaves(tree, paths: list, leaves: list):
    """`tree` with the leaves at `paths` replaced (dicts rebuilt on the way)."""
    out = dict(tree)
    for path, leaf in zip(paths, leaves):
        keys, node = path.split("/"), out
        for key in keys[:-1]:
            node[key] = dict(node[key])
            node = node[key]
        node[keys[-1]] = leaf
    return out


def train_check(conf: dict, cfg, params, mesh, loss_fn, batch_sharding,
                seed: int) -> dict:
    """Logits, loss and the gradient of the configuration's named leaves on
    one seeded sequence per data shard: the program's forward and
    value_and_grad (bf16 compute, the flash kernel at this length) against
    the float32 reference."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import transformer

    ref, tol, sizes = _reference(conf), conf["check"], conf["sizes"]
    n = max(1, mesh.size)
    rng = np.random.default_rng(harness.rng_seed(seed, 0xC4EC))
    tokens = rng.integers(0, sizes["vocab_size"], (n, tol["sample_tokens"] + 1),
                          dtype=np.int32)
    batch = jax.device_put(tokens, batch_sharding)
    paths = tol["grad_leaves"] + tol.get("grad_leaves_recorded", [])

    def program(p, b):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            logits, _ = transformer.forward(p, b[:, :-1], cfg)
            loss, grads = jax.value_and_grad(loss_fn)(p, b)
        return logits[0], loss, [_leaf(grads, path) for path in paths]

    def reference(p, b):
        def mean_loss(leaves):
            q = _with_leaves(p, paths, leaves)
            return jnp.mean(jax.vmap(
                lambda t: ref.loss(q, t, sizes, remat=True))(b))

        loss, grads = jax.value_and_grad(mean_loss)([_leaf(p, x) for x in paths])
        return ref.forward(p, b[0, :-1], sizes), loss, grads

    run = jax.jit(program)
    want = jax.device_get(jax.jit(reference)(params, batch))

    def compare(got) -> dict:
        errs = {p: _rel(g, w) for p, g, w in zip(paths, got[2], want[2])}
        judged = [errs[p] for p in tol["grad_leaves"]]
        r = {"logits_rel_err": _rel(got[0], want[0]),
             "logits_max_abs_err": float(np.abs(
                 np.asarray(got[0], np.float64) - want[0]).max()),
             "loss": float(got[1]), "loss_abs_err": abs(float(got[1]) - float(want[1])),
             "grad_rel_err": errs}
        r["logits_ok"] = bool(np.isfinite(r["logits_rel_err"])
                              and r["logits_rel_err"] <= tol["logits_rel_tol"])
        r["loss_ok"] = bool(r["loss_abs_err"] <= tol["loss_abs_tol"])
        r["grads_ok"] = bool(all(np.isfinite(e) and e <= tol["grad_rel_tol"]
                                 for e in judged))
        return r

    out = {"sample_tokens": int(tokens.shape[1] - 1), "sequences": int(n),
           "loss_ref": float(want[1]), **compare(jax.device_get(run(params, batch)))}
    # the control: operands of 8 bits have to fail the comparison the run
    # passes. On the chip it is the logits that tell them apart (4.6 % against
    # 1-2 %); the gradients of the named leaves read under their tolerance
    # from either, and the loss of random weights is ln(vocabulary) whatever
    # they are (PERF.md section 2)
    out["control"] = compare(jax.device_get(run(coarse(params), batch)))
    out["control_fails"] = not (out["control"]["logits_ok"] and out["control"]["loss_ok"]
                                and out["control"]["grads_ok"])
    out["ok"] = bool(out["logits_ok"] and out["loss_ok"] and out["grads_ok"]
                     and out["control_fails"])
    return out


def serve_check(conf: dict, seed: int, prompt_ids: list, served_ids: list,
                on_chip: bool = True) -> dict:
    """Runs in a task that holds the chip, after the replica has gone: the
    same weights from `seed`; the engine's own step functions
    (`decoding.prefill`, then `decode_step_paged_ragged` through a paged
    cache, fed the tokens the served path returned) against the reference's
    full forward over prompt + served tokens, EVERY position; and every
    served token against the reference's logits.

    The comparison: every position within `logits_rel_tol` AND the median
    over the positions within the tighter `logits_median_tol` (one position
    in a hundred reads two to four times the median from rounding alone, so
    the two are held apart).

    The program's router runs in bfloat16 on activations that already differ
    from the float32 ones, so a token whose router margin is within that
    noise is legitimately routed the other way. The reference is therefore
    allowed to take the other side of a tie — only where its own float32
    margin is under `router_tie`, one (layer, token) at a time in order of
    margin, kept when it brings the reference closer to the program — until
    the comparison holds and every served token is within `served_gap_tol`
    of the reference's best, no tie is left to try, or `max_tie_seconds` pass."""
    import time

    import jax.numpy as jnp

    from chipbench import program
    from ray_tpu._private import accelerators
    from ray_tpu.llm.engine import bucket_for
    from ray_tpu.models import decoding, decoding_paged as dp

    if on_chip:
        accelerators.require_tpu()
    ref, tol, sizes = _reference(conf), conf["check"], conf["sizes"]
    engine = conf["engine"]
    cfg = program.transformer_config(conf["program"])
    params = program.init_params(cfg, seed)
    P, n, k = engine["page_size"], len(prompt_ids), len(served_ids)
    bucket = bucket_for(n, max(engine.get("min_bucket", 32), P), engine["max_len"])
    pages_per_seq = -(-engine["max_len"] // P)
    need = (n + k) // P + 1
    bound = 1
    while bound < need:
        bound *= 2

    def program_logits(p) -> np.ndarray:
        """[k, V]: the logits that follow the prompt and each served token."""
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n] = prompt_ids
        logits0, kv = decoding.prefill(p, jnp.asarray(padded), jnp.int32(n), cfg)
        state = dp.init_paged_state(cfg, engine["max_slots"], engine["max_len"],
                                    max(need, bucket // P) + 2, P)
        row = np.zeros((pages_per_seq,), np.int32)
        row[:max(need, bucket // P)] = 1 + np.arange(max(need, bucket // P))
        state = dp.insert_sequence_paged(state, 0, kv, jnp.int32(n),
                                         jnp.int32(served_ids[0]), jnp.asarray(row), cfg)
        got = [np.asarray(logits0)]
        for tok in served_ids[1:]:
            state, logits = dp.decode_step_paged_ragged(
                p, state, cfg, min(bound, pages_per_seq), on_chip)
            got.append(np.asarray(logits[0]))
            state = decoding.commit_tokens(
                state, jnp.full((engine["max_slots"],), tok, jnp.int32))
        return np.stack(got)

    def errors(got, want) -> list:
        return [_rel(g, w) for g, w in zip(got, want)]

    def within(per_pos) -> bool:
        """The comparison itself, of the run and of the control alike."""
        return bool(np.all(np.isfinite(per_pos))
                    and max(per_pos) <= tol["logits_rel_tol"]
                    and np.median(per_pos) <= tol["logits_median_tol"])

    def served_gap(want) -> np.ndarray:
        """How far under the reference's best logit each served token lies,
        in standard deviations of that position's logits: greedy decoding
        has to take the reference's best token, or one within rounding."""
        return ((want.max(-1) - want[np.arange(k), np.asarray(served_ids)])
                / want.std(-1))

    def settled(per_pos, want) -> bool:
        return within(per_pos) and bool(
            (served_gap(want) <= tol["served_gap_tol"]).all())

    got = program_logits(params)
    context = jnp.asarray(list(prompt_ids) + list(served_ids[:-1]), jnp.int32)
    t0 = time.perf_counter()
    swaps = np.zeros((sizes["n_layers"], n + k - 1), bool)
    want_all, margin = ref.forward(params, context, sizes)
    want, margin = np.asarray(want_all)[n - 1:], np.asarray(margin)
    per_pos = errors(got, want)
    first = {"logits_rel_err": per_pos, "near_ties": int((margin < tol["router_tie"]).sum()),
             "router_margin_min": float(margin.min())}
    tried, taken = set(), []
    while (not settled(per_pos, want)
           and time.perf_counter() - t0 < tol["max_tie_seconds"]):
        ties = [(float(margin[l, t]), int(l), int(t))
                for l, t in zip(*np.nonzero(margin < tol["router_tie"]))
                if (int(l), int(t)) not in tried]
        if not ties:
            break
        m, l, t = min(ties)
        tried.add((l, t))
        trial = swaps.copy()
        trial[l, t] = True
        w_all, mg = ref.forward(params, context, sizes, trial)
        w = np.asarray(w_all)[n - 1:]
        e = errors(got, w)
        if sum(e) < sum(per_pos):
            swaps, want, margin, per_pos = trial, w, np.asarray(mg), e
            taken.append({"layer": l, "token": t, "margin": m})
    gap = served_gap(want)
    out = {"prompt_tokens": n, "positions": k, "logits_rel_err": per_pos,
           "logits_rel_err_median": float(np.median(per_pos)),
           "served_gap_in_logit_std": gap.tolist(),
           "before_ties": first, "ties_tried": len(tried), "ties_taken": taken,
           "reference_s": time.perf_counter() - t0,
           "device": accelerators.device_report()}
    out["positions_within_tol"] = int(sum(e <= tol["logits_rel_tol"] for e in per_pos))
    out["served_within_tol"] = int((gap <= tol["served_gap_tol"]).sum())
    # the control: the same steps from operands of 8 bits, against the same
    # reference (the weights are rounded in place: two copies do not fit)
    ctl = errors(program_logits(coarse(params, donate=True)), want)
    out["control"] = {"logits_rel_err": ctl, "logits_rel_err_median": float(np.median(ctl)),
                      "positions_within_tol": int(sum(e <= tol["logits_rel_tol"] for e in ctl))}
    out["control_fails"] = not within(ctl)
    out["ok"] = bool(settled(per_pos, want)
                     and out["control_fails"])
    return out
