"""Run one cell of BENCHMARK.json once; the last line printed is its result.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

`--trace 0` prints the cell's end-to-end metrics, `--trace 1` its per-layer
metrics (a run of its own: tracing slows the host). Exits non-zero and prints
no result when the host has fewer TPU chips than the cell asks for, when JAX
in the chip's worker finds another backend, when the program is missing, or
when the run cannot give what was asked of it (a traced run without a trace).
This process never touches JAX's backend: a chip belongs to one process, the
worker the runtime binds it to.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default=None,
                    help="directory for this run's files (default: "
                         ".chipbench_out/<workload>/ in the checkout)")
    args = ap.parse_args(argv)

    from chipbench import harness

    try:
        cell = harness.resolve_cell(args.workload)
    except harness.BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    if args.seconds is None:
        args.seconds = float(cell["run_seconds"])

    from ray_tpu._private import accelerators

    found = accelerators.detect_num_tpu_chips()
    if found < cell["chips"]:
        print(f"chipbench: {args.workload} needs {cell['chips']} TPU chip(s) and "
              f"this host exposes {found}. There is no CPU mode.", file=sys.stderr)
        return 2

    out_dir = os.path.abspath(args.out or os.path.join(
        ROOT, ".chipbench_out", args.workload))
    os.makedirs(out_dir, exist_ok=True)
    harness.prepare_env()

    runner = harness.kind_runner(cell["traffic_file"]["kind"])
    try:
        result = runner.run(cell, args, out_dir, T_START)
    except harness.BenchError as e:  # no result line: the reason and a code
        print(f"chipbench: {e}", file=sys.stderr, flush=True)
        return 4
    harness.peaks_for(result["device"]["kind"])
    if (result["device"]["platform"] != "tpu"
            or result["device"]["count"] != cell["chips"]):
        print(f"chipbench: the cell ran on {result['device']}", file=sys.stderr)
        return 3
    if not result["correct"]:  # the reason, at the end of this run's errors
        facts = result["facts"]
        print(f"chipbench: correct is false: {result['failed']} of "
              f"{result['attempted']} operations failed, "
              f"{facts.get('compiles_in_window')} compilations in the window, "
              f"check {facts.get('check')}", file=sys.stderr, flush=True)
    if args.trace:
        metrics = harness.read_layer_metrics(cell, result["facts"])
    else:
        metrics = harness.end_to_end_metrics(cell, result["end_to_end"])
    line = {"correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"], "metrics": metrics,
            "device": result["device"]}
    if args.trace and result.get("breakdown"):
        line["breakdown"] = result["breakdown"]
    with open(os.path.join(out_dir, "result.json"), "w") as f:
        json.dump(line, f)
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
