"""Faults for a configuration with state-space layers, by hand on the chip:

    python3 chipbench/ssm_faults.py --config <name> --fault <name> [<name> ...] | all
        --seeds <first> <count> [--out <file>]

`check.serve_check` (its `FAULTS` are the benchmark's and name trees that
every configuration has) with a deliberately wrong PROGRAM side against the
same reference. Five build the program from a wrong configuration: one of the
scalar multipliers left out (`embedding_multiplier_left_out`,
`residual_multiplier_left_out`, `logits_scaling_left_out`), the attention
scores scaled by head_dim ** -0.5 in place of the model's multiplier
(`attention_scale_rsqrt`), rope on the attention layers of a model that has no
positions (`rope_applied`). The others plant wrong code in the program's
modules for the length of the run: `pad_advances_state` (the prefill's
padding past the prompt's length runs through the recurrence and the
convolution's tail), `state_not_inserted` (the decode steps start from a slot's
old state: zeros), `conv_tail_dropped` (the insert leaves the convolution's
tail zero), `gate_after_norm` (the mixer's norm before its gate),
`decay_left_out` (exp(dt A) = 1), `d_skip_left_out` (no D x), and
`ssm_state_bfloat16` (the recurrent state rounded to bfloat16's 7 bits of
mantissa wherever it is written: by the prefill, the insert and every step).

The sound program's greedy tokens come from one sound check a seed; every
fault then runs at those tokens, at the configuration's own `max_slots` (the
state a run's check builds: one live row in a state sized for all of them).
Every seed should read `ok: false`; the readings, and the faults the
comparison does NOT tell, are recorded in the configuration file's
`check.faults`. One process that holds the chip; not part of a run.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


CONFIG_FAULTS = {
    "embedding_multiplier_left_out": dict(embedding_multiplier=1.0),
    "residual_multiplier_left_out": dict(residual_multiplier=1.0),
    "logits_scaling_left_out": dict(logits_scaling=1.0),
    "attention_scale_rsqrt": dict(attention_multiplier=None),
    "rope_applied": dict(pos="rope"),
}
CODE_FAULTS = ("pad_advances_state", "state_not_inserted", "conv_tail_dropped",
               "gate_after_norm", "decay_left_out", "d_skip_left_out", "ssm_state_bfloat16")
FAULTS = CODE_FAULTS + tuple(CONFIG_FAULTS)


def broken_config(cfg, fault: str):
    """The program's configuration with `fault` in it."""
    return dataclasses.replace(cfg, **CONFIG_FAULTS[fault])


def round_bfloat16(x):
    """float32 `x` rounded to bfloat16's precision and kept float32, by
    integer arithmetic on the bits (a conversion there and back is excess
    precision to XLA: check.coarse_program)."""
    import jax
    import jax.numpy as jnp

    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    bits = (bits + jnp.uint32(0x8000)) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _patches(fault: str) -> list:
    """(module, attribute, the wrong function) of a planted fault."""
    import jax
    import jax.numpy as jnp

    from ray_tpu import ops
    from ray_tpu.models import decoding, decoding_paged as dp, transformer

    def everywhere(name, wrong):
        return [(m, name, wrong) for m in (transformer, decoding, dp) if hasattr(m, name)]

    if fault == "pad_advances_state":
        sound = transformer.mamba_mixer
        return everywhere("mamba_mixer", lambda x, p, cfg, length=None, state=None, tail=None:
                          sound(x, p, cfg, None, state, tail))
    if fault in ("state_not_inserted", "conv_tail_dropped"):
        sound, lost = dp._set_row_state, "ssm" if fault == "state_not_inserted" else "conv"

        def wrong(state, slot, row_state):
            return sound(state, slot, None if row_state is None else {
                **row_state, lost: jnp.zeros_like(row_state[lost])})
        return [(dp, "_set_row_state", wrong)]
    if fault == "gate_after_norm":
        def wrong(y, x, z, p, cfg):
            y = y + p["D"].astype(jnp.float32)[:, None] * x.astype(jnp.float32)
            y = ops.rms_norm(y.reshape(*y.shape[:-2], cfg.ssm.d_inner), p["norm"],
                             eps=cfg.norm_eps) * jax.nn.silu(z.astype(jnp.float32))
            return y.astype(cfg.dtype) @ p["out_proj"].astype(cfg.dtype)
        return everywhere("mixer_out", wrong)
    if fault == "d_skip_left_out":
        sound = transformer.mixer_out
        return everywhere("mixer_out", lambda y, x, z, p, cfg: sound(
            y, x, z, {**p, "D": jnp.zeros_like(p["D"])}, cfg))
    scan, update = ops.ssm_chunk_scan, ops.ssm_state_update
    if fault == "decay_left_out":
        return [(ops, "ssm_chunk_scan", lambda x, dt, A, *a, **kw: scan(x, dt, 0 * A, *a, **kw)),
                (ops, "ssm_state_update", lambda s, l, x, dt, A, *a, **kw: update(
                    s, l, x, dt, 0 * A, *a, **kw))]
    if fault == "ssm_state_bfloat16":
        def wrong_scan(*a, **kw):
            y, state = scan(*a, **kw)
            return y, round_bfloat16(state)

        def wrong_update(state, layer, *a, **kw):
            state, y = update(state, layer, *a, **kw)
            rows = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
            return jax.lax.dynamic_update_index_in_dim(
                state, round_bfloat16(rows), layer, 0), y
        return [(ops, "ssm_chunk_scan", wrong_scan), (ops, "ssm_state_update", wrong_update)]
    raise ValueError(f"no planted fault {fault!r}")


@contextlib.contextmanager
def planted(fault: str):
    """`fault` in the program that `check.serve_check` builds and drives. The
    jitted steps are traced anew inside and after: a trace of the sound code
    would be found again by its arguments."""
    from chipbench import program
    from ray_tpu.models import decoding, decoding_paged as dp

    if fault in CONFIG_FAULTS:
        sound = program.transformer_config
        patches = [(program, "transformer_config",
                    lambda prog: broken_config(sound(prog), fault))]
    else:
        patches = _patches(fault)
    steps = (decoding.prefill, dp.decode_step_paged_ragged, dp.prefill_with_prefix,
             dp.insert_sequence_paged, dp.activate_slot)
    kept = [(holder, name, getattr(holder, name)) for holder, name, _ in patches]
    for holder, name, wrong in patches:
        setattr(holder, name, wrong)
    for step in steps:
        step.clear_cache()
    try:
        yield
    finally:
        for holder, name, sound in kept:
            setattr(holder, name, sound)
        for step in steps:
            step.clear_cache()


def sweep(conf: dict, seeds: list, faults: list, on_chip: bool = True):
    """One row a seed and fault: the greedy tokens of the sound program's own
    steps (one sound check a seed), then the wrong program's logits at those
    tokens against the reference."""
    from chipbench import check, check_sweep

    unknown = [f for f in faults if f not in FAULTS]
    if unknown:
        raise ValueError(f"no fault {unknown} (has: {FAULTS})")
    for seed in seeds:
        prompt = check_sweep.sample_prompt(conf, seed)
        sound = check.serve_check(conf, seed, prompt, None, on_chip)
        for fault in faults:
            with planted(fault):
                v = check.serve_check(conf, seed, prompt, sound["served_ids"], on_chip)
            yield {"seed": seed, "fault": fault, "ok": v["ok"],
                   "logits_rel_err": v["logits_rel_err"],
                   "logits_rel_err_median": v["logits_rel_err_median"],
                   "sound_median": sound["logits_rel_err_median"],
                   "sound_largest": max(sound["logits_rel_err"]),
                   "served_gap_max": max(v["served_gap_in_logit_std"]),
                   "reference_s": v["reference_s"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--fault", required=True, nargs="+")
    ap.add_argument("--seeds", required=True, nargs=2, type=int, metavar=("FIRST", "COUNT"))
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from chipbench import harness
    from ray_tpu._private import accelerators

    if accelerators.detect_num_tpu_chips() < 1:
        print("chipbench.ssm_faults: no TPU chip here", file=sys.stderr)
        return 2
    harness.prepare_env()
    conf = harness.load_json(harness.BENCH_DIR, "configs", args.config + ".json")
    faults = list(FAULTS) if args.fault == ["all"] else args.fault
    rows = []
    for row in sweep(conf, range(args.seeds[0], args.seeds[0] + args.seeds[1]), faults):
        rows.append(row)
        line = json.dumps(row)
        print(line, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0 if not any(r["ok"] for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
