"""Four faults for a configuration whose stack is run several times, by hand
on the chip:

    python3 chipbench/loop_faults.py --config <name>
        --fault planes_shared|one_pass|pass_norm_left_out|sandwich_left_out
        --seeds <first> <count> [--out <file>]

`check.serve_check` (its `FAULTS` are the benchmark's and name trees that
every configuration has) with a deliberately wrong PROGRAM side against the
same reference. `one_pass` builds it from a wrong configuration: the stack
run once (n_passes 1: a cache of a plane a layer). Three plant wrong code in
the program's modules for the length of the run: `planes_shared` has every
pass of the decode step read and write pass 0's planes (the cache indexed by
layer alone), `pass_norm_left_out` applies the final norm after the last
pass only, `sandwich_left_out` adds the sublayers' outputs to the residual
without their norms (the weights keep them: the reference reads them). Every seed has to read `ok: false`; the readings are recorded in
the configuration file's `check.faults`. One process that holds the chip;
not part of a run.
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


FAULTS = ("planes_shared", "one_pass", "pass_norm_left_out", "sandwich_left_out")


def broken_config(cfg, fault: str):
    """The program's configuration with `fault` in it."""
    if fault == "one_pass":
        return dataclasses.replace(cfg, n_passes=1)
    raise ValueError(f"no configuration for the fault {fault!r}")


def _shared_planes(sound):
    """`scan_layers` as the decode step calls it, its planes' first pages
    (`bases`, plane * num_pages) folded onto pass 0's."""
    def wrong(block, carry, params, cfg, *per_layer, **kw):
        if isinstance(carry, tuple):         # the decode step's: (h, gates, kp, vp)
            bases, *rest = per_layer
            pool = carry[2]                  # flat: [planes * num_pages, ...]
            per_layer = (bases % (pool.shape[0] // cfg.n_passes), *rest)
        return sound(block, carry, params, cfg, *per_layer, **kw)
    return wrong


def _last_norm_only(sound):
    """`close_pass` that norms (and gates) the last pass's output alone."""
    import jax.numpy as jnp

    def wrong(h, gates, t, params, cfg):
        closed, gates = sound(h, gates, t, params, cfg)
        return jnp.where(t == cfg.n_passes - 1, closed, h), gates
    return wrong


@contextlib.contextmanager
def planted(fault: str):
    """`fault` in the program that `check.serve_check` builds and drives. The
    jitted steps are traced anew inside and after: a trace of the sound code
    would be found again by its arguments."""
    from chipbench import program
    from ray_tpu.models import decoding, decoding_paged as dp

    if fault == "planes_shared":
        patches = [(dp, "scan_layers", _shared_planes(dp.scan_layers))]
    elif fault == "pass_norm_left_out":
        wrong = _last_norm_only(dp.close_pass)
        patches = [(dp, "close_pass", wrong), (decoding, "close_pass", wrong)]
    elif fault == "sandwich_left_out":
        def wrong(h, delta, layer_p, post, cfg):
            return h + delta
        patches = [(dp, "_residual", wrong), (decoding, "_residual", wrong)]
    else:
        sound = program.transformer_config
        patches = [(program, "transformer_config",
                    lambda prog: broken_config(sound(prog), fault))]
    steps = (decoding.prefill, dp.decode_step_paged_ragged, dp.prefill_with_prefix)
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


def sweep(conf: dict, seeds: list, fault: str, on_chip: bool = True):
    """One row a seed: the greedy tokens of the sound program's own steps,
    then the wrong program's logits at those tokens against the reference."""
    from chipbench import check, check_sweep

    if fault not in FAULTS:
        raise ValueError(f"no fault {fault!r} (has: {FAULTS})")
    for seed in seeds:
        prompt = check_sweep.sample_prompt(conf, seed)
        served = check.serve_check(conf, seed, prompt, None, on_chip)["served_ids"]
        with planted(fault):
            v = check.serve_check(conf, seed, prompt, served, on_chip)
        yield {"seed": seed, "fault": fault, "ok": v["ok"],
               "logits_rel_err": v["logits_rel_err"],
               "logits_rel_err_median": v["logits_rel_err_median"],
               "served_gap_max": max(v["served_gap_in_logit_std"]),
               "reference_s": v["reference_s"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--fault", required=True)
    ap.add_argument("--seeds", required=True, nargs=2, type=int, metavar=("FIRST", "COUNT"))
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from chipbench import harness
    from ray_tpu._private import accelerators

    if accelerators.detect_num_tpu_chips() < 1:
        print("chipbench.loop_faults: no TPU chip here", file=sys.stderr)
        return 2
    harness.prepare_env()
    conf = harness.load_json(harness.BENCH_DIR, "configs", args.config + ".json")
    rows = []
    for row in sweep(conf, range(args.seeds[0], args.seeds[0] + args.seeds[1]), args.fault):
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
