"""The serve check alone over many seeds, on the chip: the evidence that a
sound tree reads `correct: true` whatever the seed, and a broken one false.

    python3 chipbench/check_sweep.py --config mixtral-8x7b --seeds <first> <count>
        [--seeds <first> <count> ...] [--fault <name>] [--out <file>]

One process that holds the chip. For each seed: the weights, the sample
prompt of a run with that `--seed`, the greedy tokens of the program's own
steps, `check.serve_check` (the tie search, the control). One JSON line a
seed, then a summary line; `--out` also appends them to a file. `--fault`
(one of `check.FAULTS`) runs the program side from a deliberately wrong
tree: every seed then has to read `ok: false`. Exits 0 when every seed read
what it has to (and, without a fault, the control failed in every one), 1
when not, 2 without a chip. Not part of a run; the tolerances in the
configuration's `check` are argued from its lines.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def sample_prompt(conf: dict, seed: int) -> list:
    """The token ids of the request a run with `--seed` makes its comparison
    on (kinds/serve.py draws the same text)."""
    import numpy as np

    from chipbench import harness, traffic as gen
    from ray_tpu.llm.tokenizer import load_tokenizer

    text = gen.text(conf["check"]["sample_tokens"],
                    np.random.default_rng(harness.rng_seed(seed, 0xC4EC)))
    return load_tokenizer(conf.get("tokenizer", "byte")).encode(text)


def sweep(conf: dict, seeds: list, fault: str | None = None, on_chip: bool = True):
    """One row a seed: what `serve_check` found, less the bulky parts."""
    from chipbench import check

    for seed in seeds:
        t0 = time.perf_counter()
        v = check.serve_check(conf, seed, sample_prompt(conf, seed), None, on_chip, fault)
        yield {"seed": seed, "fault": fault, "ok": v["ok"], "positions": v["positions"],
               "logits_rel_err": v["logits_rel_err"],
               "logits_rel_err_median": v["logits_rel_err_median"],
               "before_ties": v["before_ties"],
               "served_gap_max": max(v["served_gap_in_logit_std"]),
               "ties_tried": v["ties_tried"], "ties_reopened": v["ties_reopened"],
               "joint_trials": v["joint_trials"], "ties_taken": v["ties_taken"],
               "reference_s": v["reference_s"],
               "control": v["control"]["logits_rel_err"],
               "control_median": v["control"]["logits_rel_err_median"],
               "control_fails": v["control_fails"],
               "seconds": time.perf_counter() - t0}


def summary(rows: list, fault: str | None) -> dict:
    """What PERF.md and the configuration's `reason` quote."""
    return {"summary": True, "fault": fault, "seeds": len(rows),
            "not_ok": [r["seed"] for r in rows if not r["ok"]],
            "control_passes": [r["seed"] for r in rows if not r["control_fails"]],
            "largest_position": max(max(r["logits_rel_err"]) for r in rows),
            "smallest_largest_position": min(max(r["logits_rel_err"]) for r in rows),
            "largest_median": max(r["logits_rel_err_median"] for r in rows),
            "smallest_median": min(r["logits_rel_err_median"] for r in rows),
            "largest_served_gap": max(r["served_gap_max"] for r in rows),
            "longest_search_s": max(r["reference_s"] for r in rows),
            "searched": sum(1 for r in rows if r["ties_tried"] + r["joint_trials"]),
            "most_trials": max(r["ties_tried"] + r["joint_trials"] for r in rows),
            "most_taken": max(len(r["ties_taken"]) for r in rows),
            "control_smallest_position": min(min(r["control"]) for r in rows),
            "control_smallest_median": min(r["control_median"] for r in rows)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", required=True, nargs=2, type=int, action="append",
                    metavar=("FIRST", "COUNT"))
    ap.add_argument("--fault", default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from chipbench import check, harness
    from ray_tpu._private import accelerators

    if args.fault is not None and args.fault not in check.FAULTS:
        print(f"chipbench.check_sweep: no fault {args.fault!r} "
              f"(has: {check.FAULTS})", file=sys.stderr)
        return 2
    files = {c["name"]: c["file"] for c in
             harness.load_json(harness.ROOT, "BENCHMARK.json")["configs"]}
    if args.config not in files:
        print(f"chipbench.check_sweep: no config {args.config!r} in BENCHMARK.json "
              f"(has: {sorted(files)})", file=sys.stderr)
        return 2
    if accelerators.detect_num_tpu_chips() < 1:
        print("chipbench.check_sweep: no TPU chip here", file=sys.stderr)
        return 2
    harness.prepare_env()
    conf = harness.load_json(harness.ROOT, files[args.config])
    seeds = [first + i for first, count in args.seeds for i in range(count)]

    def emit(row: dict) -> None:
        line = json.dumps(row)
        print(line, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
            with open(args.out, "a") as f:
                f.write(line + "\n")

    rows = []
    for row in sweep(conf, seeds, args.fault):
        rows.append(row)
        emit(row)
    total = summary(rows, args.fault)
    emit(total)
    if args.fault:
        return 0 if len(total["not_ok"]) == len(rows) else 1
    return 0 if not total["not_ok"] and not total["control_passes"] else 1


if __name__ == "__main__":
    sys.exit(main())
