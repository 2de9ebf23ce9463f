"""Three faults for a configuration with window layers, by hand on the chip:

    python3 chipbench/window_faults.py --config <name>
        --fault window_ignored|yarn_left_out|window_page_zeroed
        --seeds <first> <count> [--out <file>]

`check.serve_check` (its `FAULTS` are the benchmark's and name trees that
every configuration has) with a deliberately wrong PROGRAM side against the
same reference. Two build it from a wrong configuration: `window_ignored`
gives the window layers a window as long as the longest row (their mask cuts
nothing off; their rope stays the plain one), `yarn_left_out` gives the full
layers the plain rope. `window_page_zeroed` is `check.FAULTS`'
`kv_page_zeroed` for the window layers' pool, which that one leaves alone:
after the insert, the page of the row's ring that holds the prompt's last
tokens is zeroed in `wkp` / `wvp`. Every seed has to read `ok: false`; the
readings are recorded in the configuration file's `check.faults`. One
process that holds the chip; not part of a run.
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


FAULTS = ("window_ignored", "yarn_left_out", "window_page_zeroed")


def broken_config(cfg, fault: str):
    """The program's configuration with `fault` in it."""
    if fault == "window_ignored":
        return dataclasses.replace(cfg, window=cfg.max_seq_len)
    if fault == "yarn_left_out":
        return dataclasses.replace(cfg, yarn=None)
    raise ValueError(f"no configuration for the fault {fault!r}")


def zero_window_page(state, slot, length):
    """`state` with the window pool's page that holds position length - 1 of
    row `slot` zeroed: the slot of its ring that the logical page lies in."""
    P = state["wkp"].shape[2]
    page = state["wblock"][slot, ((length - 1) // P) % state["wring"][slot]]
    return {**state, "wkp": state["wkp"].at[:, page].set(0),
            "wvp": state["wvp"].at[:, page].set(0)}


@contextlib.contextmanager
def planted(fault: str):
    """`fault` in the program that `check.serve_check` builds and drives."""
    from chipbench import program
    from ray_tpu.models import decoding_paged as dp

    if fault == "window_page_zeroed":
        holder, name, sound = dp, "insert_sequence_paged", dp.insert_sequence_paged

        def wrong(state, slot, kv, length, *rest, **kw):
            return zero_window_page(sound(state, slot, kv, length, *rest, **kw),
                                    slot, length)
    else:
        holder, name, sound = program, "transformer_config", program.transformer_config

        def wrong(prog):
            return broken_config(sound(prog), fault)
    setattr(holder, name, wrong)
    try:
        yield
    finally:
        setattr(holder, name, sound)


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
        print("chipbench.window_faults: no TPU chip here", file=sys.stderr)
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
