"""Faults for a configuration of the `solar_open2` family (gated delta-rule
linear-attention layers beside one gated attention layer without positions,
expert layers that hold a SHARE of the experts in every layer), by hand on the
chip:

    python3 chipbench/solar_faults.py --config <name> [--faults a,b,...]
        --seeds <first> <count> [--tie-seconds <s>] [--out <file>]

`check.serve_check` (its `FAULTS` are the benchmark's and name trees that every
configuration has) with a deliberately wrong PROGRAM side against the same
reference, from the same weights. For each seed the sound program's greedy
tokens are taken once; then every fault's logits at those tokens.

- `delta_left_out`: plain gated linear attention, S_t = Diag(exp(g)) S + beta k
  v^T: no `- beta k (k^T S)`, in the prefill's scan and in every step;
- `decay_per_head`: a head's channel gates replaced by their mean;
- `beta_not_doubled`: beta = sigmoid(.), in (0, 1);
- `qk_l2norm_left_out`: q and k go into the recurrence as convolved;
- `out_gate_left_out`: the KDA layers' output goes to W_o without its sigmoid gate;
- `gqa_gate_left_out`: the attention layer's heads go to W_o without sigmoid(x W_g);
- `weights_over_held`: a held slot's weight is normalised over the slots this
  chip holds, not over all the token's chosen experts;
- `absent_expert_wrapped`: an absent expert's slot is computed by the held
  expert `e mod held` instead of being left out;
- `state_bfloat16`: the recurrent state rounded to bfloat16's 7 bits of
  mantissa wherever it is written (the prefill, the insert, every step).

Two faults are of the ENGINE's chunked prefill, which `serve_check`'s own
program side (one unchunked prefill) does not run: `state_not_carried_between_
chunks` and `conv_tail_dropped_between_chunks`. For those the SERVED tokens are
the wrong ones: the prompt is prefilled here in the engine's chunks
(`decoding.prefill`, then `prefill_with_prefix` behind the pages, the carried
state or tails zeroed on the way), the row goes live by `activate_slot` and is
decoded greedily, and `serve_check` is given those tokens as a run would give
it the replica's: the sound program's logits agree with the reference, and the
served tokens are not the reference's best (`served_gap_in_logit_std`).
`chunks_sound` is the same path with nothing spoiled and has to read ok.

Every fault has to read `ok: false` on every seed; the readings, and the
faults the comparison does NOT tell, are recorded in the configuration file's
`check.faults`. One process that holds the chip; not part of a run.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


CODE_FAULTS = ("delta_left_out", "decay_per_head", "beta_not_doubled", "qk_l2norm_left_out",
               "out_gate_left_out", "gqa_gate_left_out", "weights_over_held",
               "absent_expert_wrapped", "state_bfloat16")
CHUNK_FAULTS = {"state_not_carried_between_chunks": "ssm",
                "conv_tail_dropped_between_chunks": "conv"}
FAULTS = CODE_FAULTS + tuple(CHUNK_FAULTS)


def _plain_gated_linear(q, k, v, g, beta, state=None):
    """The recurrence WITHOUT the delta term, token by token."""
    import jax
    import jax.numpy as jnp

    def step(S, at):
        q_t, k_t, v_t, g_t, b_t = at
        S = jnp.exp(g_t)[:, :, None] * S + (b_t[:, None] * k_t)[:, :, None] * v_t[:, None, :]
        return S, jnp.einsum("hk,hkv->hv", q_t, S)

    f32 = jnp.float32
    S0 = jnp.zeros(k.shape[1:] + v.shape[-1:], f32) if state is None else state.astype(f32)
    S, o = jax.lax.scan(step, S0, tuple(a.astype(f32) for a in (q, k, v, g, beta)))
    return o, S


def _patches(fault: str, conf: dict) -> list:
    """(module, attribute, the wrong function) of a planted fault."""
    import jax
    import jax.numpy as jnp

    from chipbench.ssm_faults import round_bfloat16
    from ray_tpu import ops
    from ray_tpu.models import decoding, decoding_paged as dp, transformer
    from ray_tpu.ops import moe

    def everywhere(name, wrong):
        return [(m, name, wrong) for m in (transformer, decoding, dp) if hasattr(m, name)]

    scan, update = ops.kda_chunk_scan, ops.kda_state_update
    if fault == "delta_left_out":
        def wrong_scan(q, k, v, g, beta, *, chunk, sub=None, state=None):
            return _plain_gated_linear(q, k, v, g, beta, state)

        def wrong_update(state, layer, q, k, v, g, beta, *, live=None, **kw):
            rows = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
            o, new = jax.vmap(lambda s, *at: _plain_gated_linear(
                *(a[None] for a in at), s))(rows, q, k, v, g, beta)
            if live is not None:
                new = jnp.where(live[:, None, None, None], new, rows)
            return jax.lax.dynamic_update_index_in_dim(state, new, layer, 0), o[:, 0]
        return [(ops, "kda_chunk_scan", wrong_scan), (ops, "kda_state_update", wrong_update)]
    if fault in ("decay_per_head", "beta_not_doubled", "out_gate_left_out"):
        sound = transformer.kda_project

        def wrong(x, p, cfg):
            qkv, g, beta, gate = sound(x, p, cfg)
            if fault == "decay_per_head":
                g = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
            elif fault == "beta_not_doubled":
                beta = beta / 2
            else:  # sigmoid(.) = 1
                gate = jnp.full_like(gate, 1e4)
            return qkv, g, beta, gate
        return everywhere("kda_project", wrong)
    if fault == "qk_l2norm_left_out":
        def wrong(qkv, cfg):
            s = cfg.ssm
            qkv = jax.nn.silu(qkv).astype(jnp.float32)
            q, k, v = (qkv[..., i * s.d_inner:(i + 1) * s.d_inner].reshape(
                *qkv.shape[:-1], s.n_heads, s.d_head) for i in range(3))
            return q * s.d_head ** -0.5, k, v
        return everywhere("kda_split", wrong)
    if fault == "gqa_gate_left_out":
        return everywhere("attn_gated", lambda out, x, p, cfg: out)
    if fault == "weights_over_held":
        sound, share = ops.sigmoid_topk, conf["sizes"]["experts_held"]

        def wrong(router_logits, select_bias, *, k, scale=1.0):
            idx, w, aux = sound(router_logits, select_bias, k=k, scale=scale)
            mine = (idx >= share[0]) & (idx <= share[-1])
            here = jnp.sum(jnp.where(mine, w, 0.0), axis=-1, keepdims=True)
            return idx, w / (here + 1e-20) * scale, aux
        return [(ops, "sigmoid_topk", wrong)]
    if fault == "absent_expert_wrapped":
        def wrong(expert_idx, first, held):
            return (expert_idx - first) % held, jnp.ones(expert_idx.shape, bool)
        return [(moe, "held_slots", wrong), (ops, "held_slots", wrong)]
    if fault == "state_bfloat16":
        def wrong_scan(*a, **kw):
            o, state = scan(*a, **kw)
            return o, round_bfloat16(state)

        def wrong_update(state, layer, *a, **kw):
            state, o = update(state, layer, *a, **kw)
            rows = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
            return jax.lax.dynamic_update_index_in_dim(
                state, round_bfloat16(rows), layer, 0), o
        return [(ops, "kda_chunk_scan", wrong_scan), (ops, "kda_state_update", wrong_update)]
    raise ValueError(f"no planted fault {fault!r}")


def _steps():
    from ray_tpu.models import decoding, decoding_paged as dp

    return (decoding.prefill, dp.decode_step_paged_ragged, dp.prefill_with_prefix,
            dp.insert_sequence_paged, dp.activate_slot)


@contextlib.contextmanager
def planted(fault: str, conf: dict):
    """`fault` in the program that `check.serve_check` builds and drives. The
    jitted steps are traced anew inside and after: a trace of the sound code
    would be found again by its arguments."""
    patches = _patches(fault, conf)
    kept = [(holder, name, getattr(holder, name)) for holder, name, _ in patches]
    for holder, name, wrong in patches:
        setattr(holder, name, wrong)
    for step in _steps():
        step.clear_cache()
    try:
        yield
    finally:
        for holder, name, sound in kept:
            setattr(holder, name, sound)
        for step in _steps():
            step.clear_cache()


def served_by_chunks(conf: dict, seed: int, prompt_ids: list, k: int,
                     dropped: str | None = None, on_chip: bool = True) -> list:
    """The greedy tokens of a row prefilled in the ENGINE's chunks by hand
    (its `prefill_chunk`, the tail in its bucket, the prefix gathered out of
    the pages) and decoded through the cache; `dropped` ("ssm" or "conv")
    zeroes that part of what one chunk hands the next."""
    import jax.numpy as jnp
    import numpy as np

    from chipbench import program
    from ray_tpu.llm.engine import bucket_for
    from ray_tpu.models import decoding, decoding_paged as dp

    engine = conf["engine"]
    cfg = program.transformer_config(conf["program"])
    params = program.init_params(cfg, seed)
    P, chunk, n = engine["page_size"], engine["prefill_chunk"], len(prompt_ids)
    floor = max(engine.get("min_bucket", 32), P)
    pages_per_seq = -(-engine["max_len"] // P)
    need = (n + k) // P + 1 + chunk // P
    state = dp.init_paged_state(cfg, engine["max_slots"], engine["max_len"], need + 2, P)
    row = np.zeros((pages_per_seq,), np.int32)
    row[:need] = 1 + np.arange(need)
    carried = None
    for done in range(0, n, chunk):
        span = prompt_ids[done:done + chunk]
        bucket = bucket_for(len(span), floor, chunk)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :len(span)] = span
        if done == 0:
            logits, kv = decoding.prefill(params, jnp.asarray(padded), jnp.int32(len(span)), cfg)
        else:
            npad = 1
            while npad < done // P:
                npad *= 2
            ids = np.zeros((npad,), np.int32)
            ids[:done // P] = row[:done // P]
            pk, pv = dp.gather_prefix_pages(state["kp"], state["vp"], jnp.asarray(ids))
            if dropped:
                carried = {**carried, dropped: jnp.zeros_like(carried[dropped])}
            logits, kv = dp.prefill_with_prefix(
                params, jnp.asarray(padded), pk, pv, jnp.int32(done), jnp.int32(len(span)),
                cfg, row_state=carried, kernel=on_chip)
        kv.pop("expert_counts", None)
        carried = {name: kv[name] for name in ("ssm", "conv")}
        state = dp.write_kv_pages(
            state, kv, jnp.asarray(row[done // P:(done + bucket) // P]))
    bound = 1
    while bound < need:
        bound *= 2
    ids = [int(np.argmax(np.asarray(logits)))]
    state = dp.activate_slot(state, 0, jnp.asarray(row), jnp.int32(n), jnp.int32(ids[0]),
                             None, carried)
    while len(ids) < k:
        state, step = dp.decode_step_paged_ragged(params, state, cfg,
                                                  min(bound, pages_per_seq), on_chip)
        state.pop("expert_counts", None)
        ids.append(int(np.argmax(np.asarray(step[0]))))
        state = decoding.commit_tokens(
            state, jnp.full((engine["max_slots"],), ids[-1], jnp.int32))
    return ids


def sweep(conf: dict, seeds: list, faults: list, on_chip: bool = True,
          tie_seconds: float | None = None):
    """One row a seed and fault: the greedy tokens of the sound program's own
    steps (once a seed), then the wrong program's logits at those tokens
    against the reference; for a fault of the chunked prefill, the sound
    program's logits at the wrong path's tokens. `tie_seconds`: the longest a
    FAULT's tie search may take (a wrong program keeps the search going until
    its time is up; None: the file's `max_tie_seconds`, which the sound run
    always has)."""
    from chipbench import check, check_sweep

    wrong_conf = conf if tie_seconds is None else {
        **conf, "check": {**conf["check"], "max_tie_seconds": tie_seconds}}
    for fault in faults:
        if fault not in FAULTS + ("chunks_sound",):
            raise ValueError(f"no fault {fault!r} (has: {FAULTS + ('chunks_sound',)})")
    for seed in seeds:
        prompt = check_sweep.sample_prompt(conf, seed)
        sound = check.serve_check(conf, seed, prompt, None, on_chip)
        for fault in faults:
            if fault in CHUNK_FAULTS or fault == "chunks_sound":
                served = served_by_chunks(conf, seed, prompt, len(sound["served_ids"]),
                                          CHUNK_FAULTS.get(fault), on_chip)
                v = check.serve_check(conf if fault == "chunks_sound" else wrong_conf,
                                      seed, prompt, served, on_chip)
            else:
                with planted(fault, conf):
                    v = check.serve_check(wrong_conf, seed, prompt, sound["served_ids"],
                                          on_chip)
            yield {"seed": seed, "fault": fault, "ok": v["ok"],
                   "sound_ok": sound["ok"], "sound_median": sound["logits_rel_err_median"],
                   "sound_largest": max(sound["logits_rel_err"]),
                   "logits_rel_err_median": v["logits_rel_err_median"],
                   "logits_rel_err_largest": max(v["logits_rel_err"]),
                   "served_gap_max": max(v["served_gap_in_logit_std"]),
                   "served_same": sum(a == b for a, b in zip(v["served_ids"],
                                                             sound["served_ids"])),
                   "control_fails": v["control_fails"], "reference_s": v["reference_s"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--faults", default=",".join(FAULTS))
    ap.add_argument("--seeds", required=True, nargs=2, type=int, metavar=("FIRST", "COUNT"))
    ap.add_argument("--tie-seconds", type=float, default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from chipbench import harness
    from ray_tpu._private import accelerators

    if accelerators.detect_num_tpu_chips() < 1:
        print("chipbench.solar_faults: no TPU chip here", file=sys.stderr)
        return 2
    harness.prepare_env()
    conf = harness.load_json(harness.BENCH_DIR, "configs", args.config + ".json")
    rows = []
    for row in sweep(conf, range(args.seeds[0], args.seeds[0] + args.seeds[1]),
                     args.faults.split(","), tie_seconds=args.tie_seconds):
        rows.append(row)
        line = json.dumps(row)
        print(line, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
            with open(args.out, "a") as f:
                f.write(line + "\n")
    wrong = [r for r in rows if r["ok"] != (r["fault"] == "chunks_sound")]
    return 0 if not wrong else 1


if __name__ == "__main__":
    sys.exit(main())
