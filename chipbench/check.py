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
import typing

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


class _State(typing.NamedTuple):
    """A reference the search holds or tries."""
    per_pos: list            # the k positions' errors against the program
    depth: np.ndarray        # [L, T]: the routing it was computed under
    want: np.ndarray         # [k, V]: its logits at the served positions
    margin: np.ndarray       # [L, T, 2]: its own router margins
    out: np.ndarray          # `judged`: every judged number over its tolerance


def _better(state: _State, than: _State) -> bool:
    """Whether the reference of `state` is closer to the program than that
    of `than`: what is most out of the comparison's rules is less out, or
    nothing is further out and the errors' sum is lower. On the sum alone a
    trial that brought the one position that was out in, and lifted the
    others a little, was refused."""
    a, b = max(state.out.max(), 1.0), max(than.out.max(), 1.0)
    return bool(a < b or (a == b and sum(state.per_pos) < sum(than.per_pos)))


def tie_search(forward, got, n: int, n_layers: int, tol: dict, judged) -> dict:
    """The reference that `got` [k, V] (the program's logits at the k served
    positions, which follow context tokens n-1 ... n+k-2) is judged against.

    `forward(depth)` is the reference over the context: (logits [T, V],
    margin [L, T, 2], see reference/mixtral.py `_route`) with the (layer,
    token) pairs marked in `depth` [L, T] routed the other way. The search
    takes the other side of a tie ONLY where the reference's own float32
    margin to that expert, under the routing held so far, is below
    `router_tie`, and keeps a trial only when it brings the reference closer
    (`_better`). `judged(per_pos, want)` gives every number the comparison
    judges over its tolerance, the k positions first: all within 1, it
    holds. Until then, no candidate left, or `max_tie_seconds`:

    1. the WORST position's own token, all layers jointly and from scratch:
       every routing (as routed, the first, the second expert left out) of
       that token, layer by layer, each layer's margins read under the
       choices made for the layers before it. A flip there reads 20-100 % at
       that position and one elsewhere a few %, so this is where a position
       that is out comes in; a flip in one layer moves the next layer's
       margins, so the right one may show, or hide, only under another;
    2. then single (layer, token, depth) ties, those at or before the worst
       position first (a later token cannot move it), in order of margin;
       a flip held may be undone by the same rule; when none is left and a
       flip was taken since a candidate was refused, the refused ones are
       candidates again: every later layer's input has changed.

    In order of margin over all tokens, each tried once and kept on the sum
    (PR 23), flips at prompt tokens that lowered the sum a little were taken
    first and led where no single flip brought the last position in (PERF.md
    section 6, PR 26)."""
    import time

    k, tie = len(got), tol["router_tie"]
    deadline = time.perf_counter() + tol["max_tie_seconds"]
    log = {"ties_tried": 0, "ties_reopened": 0, "joint_trials": 0, "ties_taken": []}

    def run(depth):
        w_all, mg = forward(depth)
        w = np.asarray(w_all)[n - 1:]
        per_pos = [_rel(g, x) for g, x in zip(got, w)]
        return _State(per_pos, depth, w, np.asarray(mg), judged(per_pos, w))

    def joint(token):
        """The best routing of `token` over all layers, as (state, flips)."""
        best = [None, None]

        def descend(layer, state, flips):
            if best[0] is None or _better(state, best[0]):
                best[:] = state, flips
            for l in range(layer, n_layers):
                for d in (1, 2):
                    m = float(state.margin[l, token, d - 1])
                    if m < tie and time.perf_counter() < deadline:
                        trial = state.depth.copy()
                        trial[l, token] = d
                        log["joint_trials"] += 1
                        descend(l + 1, run(trial), flips + [(l, d, m)])

        if cur.depth[:, token].any():
            base = cur.depth.copy()
            base[:, token] = 0
            log["joint_trials"] += 1
            descend(0, run(base), [])
        else:
            descend(0, cur, [])
        return best

    def take(state, flips, token, **how):
        worst = int(np.argmax(cur.out[:k]))
        for l, d, m in flips:
            log["ties_taken"].append({
                "layer": l, "token": token, "margin": m, "depth": d, **how,
                "position": worst, "err": [cur.per_pos[worst], state.per_pos[worst]],
                "out": [float(cur.out.max()), float(state.out.max())],
                "sum": [sum(cur.per_pos), sum(state.per_pos)]})
        return state

    cur = run(np.zeros((n_layers, n + k - 1), np.int8))
    first = {"logits_rel_err": cur.per_pos,
             "near_ties": int((cur.margin[..., 0] < tie).sum()),
             "router_margin_min": float(cur.margin.min())}
    exhausted, refused, seen, stale = set(), set(), set(), False
    while cur.out.max() > 1.0 and time.perf_counter() < deadline:
        tw = n - 1 + int(np.argmax(cur.out[:k]))
        if tw not in exhausted:
            exhausted.add(tw)
            state, flips = joint(tw)
            if _better(state, cur):
                cur = take(state, flips, tw, joint=len(flips))
                # what comes before `tw` is as it was: causal
                exhausted, stale = {t for t in exhausted if t <= tw}, True
            continue
        l_, t_, d_ = np.nonzero(cur.margin < tie)
        ties = [(bool(t > tw), float(cur.margin[l, t, d]), int(l), int(t), int(d) + 1)
                for l, t, d in zip(l_, t_, d_)
                if cur.depth[l, t] != d + 1 and t not in exhausted]
        # a flip held is undone by the same rule, at the margin it was taken at
        ties += [(bool(t > tw), float(cur.margin[l, t, cur.depth[l, t] - 1]), int(l), int(t), 0)
                 for l, t in zip(*np.nonzero(cur.depth)) if t not in exhausted]
        ties = [c for c in ties if c[2:] not in refused]
        if not ties:
            if not stale:
                break
            refused, stale = set(), False
            continue
        _, m, l, t, d = min(ties)
        trial = cur.depth.copy()
        trial[l, t] = d
        log["ties_tried"] += 1
        log["ties_reopened"] += (l, t, d) in seen
        state = run(trial)
        if _better(state, cur):
            cur = take(state, [(l, d, m)], t, joint=0, reopened=(l, t, d) in seen)
            exhausted, stale = {x for x in exhausted if x < t}, True
        else:
            refused.add((l, t, d))
        seen.add((l, t, d))
    return {"per_pos": cur.per_pos, "want": cur.want, "before_ties": first, **log}


FAULTS = ("expert_down_swapped", "rope_theta_1e4", "kv_page_zeroed")


def _swap_down(down):
    """Experts 0 and 1 of layer 1 change places; applied twice, nothing."""
    return down.at[1, 0].set(down[1, 1]).at[1, 1].set(down[1, 0])


def serve_check(conf: dict, seed: int, prompt_ids: list, served_ids: list | None,
                on_chip: bool = True, fault: str | None = None) -> dict:
    """Runs in a task that holds the chip, after the replica has gone: the
    same weights from `seed`; the engine's own step functions
    (`decoding.prefill`, then `decode_step_paged_ragged` through a paged
    cache, fed the tokens the served path returned) against the reference's
    full forward over prompt + served tokens, EVERY position; and every
    served token against the reference's logits. A served answer that EOS
    cut short is compared at the positions it has.

    The comparison: every position within `logits_rel_tol` AND the median
    over the positions within the tighter `logits_median_tol` (one position
    in a hundred reads two to four times the median from rounding alone, so
    the two are held apart).

    The program's router runs in bfloat16 on activations that already differ
    from the float32 ones, so a token whose router margin is within that
    noise is legitimately routed the other way. The reference is therefore
    allowed to take the other side of a tie (`tie_search`) until the
    comparison holds and every served token is within `served_gap_tol` of
    the reference's best.

    For chipbench/check_sweep.py and the tests, never for a run:
    `served_ids=None` takes the greedy tokens of the program's own steps (up
    to `positions`, or EOS), and `fault` (one of FAULTS) runs the program
    side from a deliberately wrong tree, which has to read `ok: false`."""
    import dataclasses
    import time

    import jax
    import jax.numpy as jnp

    from chipbench import program
    from ray_tpu._private import accelerators
    from ray_tpu.llm.engine import bucket_for
    from ray_tpu.llm.tokenizer import load_tokenizer
    from ray_tpu.models import decoding, decoding_paged as dp

    if on_chip:
        accelerators.require_tpu()
    if fault is not None and fault not in FAULTS:
        raise harness.BenchError(f"no fault {fault!r} (has: {FAULTS})")
    ref, tol, sizes = _reference(conf), conf["check"], conf["sizes"]
    engine = conf["engine"]
    cfg = program.transformer_config(conf["program"])
    params = program.init_params(cfg, seed)
    eos = load_tokenizer(conf.get("tokenizer", "byte")).eos_token_id
    P, n = engine["page_size"], len(prompt_ids)
    k = tol["positions"] if served_ids is None else len(served_ids)
    bucket = bucket_for(n, max(engine.get("min_bucket", 32), P), engine["max_len"])
    pages_per_seq = -(-engine["max_len"] // P)
    need = (n + k) // P + 1
    bound = 1
    while bound < need:
        bound *= 2

    def program_logits(p, cfg, broken: bool = False) -> tuple:
        """([k, V]: the logits that follow the prompt and each served token,
        the served tokens: as given, or the greedy ones of these steps)."""
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n] = prompt_ids
        logits, kv = decoding.prefill(p, jnp.asarray(padded), jnp.int32(n), cfg)
        logits = np.asarray(logits)
        state = dp.init_paged_state(cfg, engine["max_slots"], engine["max_len"],
                                    max(need, bucket // P) + 2, P)
        row = np.zeros((pages_per_seq,), np.int32)
        row[:max(need, bucket // P)] = 1 + np.arange(max(need, bucket // P))
        got, ids = [], []
        while len(ids) < k:
            tok = int(np.argmax(logits)) if served_ids is None else served_ids[len(ids)]
            if served_ids is None and tok == eos:
                break
            got.append(logits)
            ids.append(tok)
            if len(ids) == k:
                break
            if len(ids) == 1:
                state = dp.insert_sequence_paged(state, 0, kv, jnp.int32(n),
                                                 jnp.int32(tok), jnp.asarray(row), cfg)
                if broken:  # the page that holds the prompt's last tokens
                    page = int(row[(n - 1) // P])
                    state = {**state, "kp": state["kp"].at[:, page].set(0),
                             "vp": state["vp"].at[:, page].set(0)}
            else:
                state = decoding.commit_tokens(
                    state, jnp.full((engine["max_slots"],), tok, jnp.int32))
            state, step = dp.decode_step_paged_ragged(
                p, state, cfg, min(bound, pages_per_seq), on_chip)
            logits = np.asarray(step[0])
        return np.stack(got) if got else np.zeros((0, 0)), ids

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

    def judged(per_pos, want) -> np.ndarray:
        """Every number `settled` judges over its tolerance, for the search to
        work on whichever is most out: the k positions (the logits' error or
        the served token's gap, whichever is further out), then the median."""
        e = np.asarray(per_pos)
        if not np.all(np.isfinite(e)):
            return np.full(k + 1, np.inf)
        return np.append(np.maximum(e / tol["logits_rel_tol"],
                                    served_gap(want) / tol["served_gap_tol"]),
                         np.median(e) / tol["logits_median_tol"])

    if fault == "expert_down_swapped":  # in place: two copies do not fit
        swap = jax.jit(_swap_down, donate_argnums=(0,))
        broken = _with_leaves(params, ["layers/mlp/down"],
                              [swap(params["layers"]["mlp"]["down"])])
        got, served_ids = program_logits(broken, cfg)
        params = _with_leaves(broken, ["layers/mlp/down"],
                              [swap(broken["layers"]["mlp"]["down"])])
        del broken
    elif fault == "rope_theta_1e4":
        got, served_ids = program_logits(
            params, dataclasses.replace(cfg, rope_theta=1e4))
    else:
        got, served_ids = program_logits(params, cfg, fault == "kv_page_zeroed")
    k = len(served_ids)
    if k < 1:
        raise harness.BenchError("the sample's answer is empty: nothing to compare")
    context = jnp.asarray(list(prompt_ids) + list(served_ids[:-1]), jnp.int32)
    t0 = time.perf_counter()
    found = tie_search(lambda depth: ref.forward(params, context, sizes, depth),
                       got, n, sizes["n_layers"], tol, judged)
    per_pos, want = found.pop("per_pos"), found.pop("want")
    gap = served_gap(want)
    out = {"prompt_tokens": n, "positions": k, "served_ids": [int(t) for t in served_ids],
           "logits_rel_err": per_pos,
           "logits_rel_err_median": float(np.median(per_pos)),
           "served_gap_in_logit_std": gap.tolist(), **found,
           "reference_s": time.perf_counter() - t0,
           "device": accelerators.device_report()}
    out["positions_within_tol"] = int(sum(e <= tol["logits_rel_tol"] for e in per_pos))
    out["served_within_tol"] = int((gap <= tol["served_gap_tol"]).sum())
    # the control: the same steps from operands of 8 bits, against the same
    # reference (the weights are rounded in place: two copies do not fit)
    ctl = [_rel(g, w) for g, w in zip(program_logits(coarse(params, donate=True), cfg)[0], want)]
    out["control"] = {"logits_rel_err": ctl, "logits_rel_err_median": float(np.median(ctl)),
                      "positions_within_tol": int(sum(e <= tol["logits_rel_tol"] for e in ctl))}
    out["control_fails"] = not within(ctl)
    out["ok"] = bool(settled(per_pos, want)
                     and out["control_fails"])
    return out
