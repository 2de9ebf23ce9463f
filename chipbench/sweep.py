"""Find the highest rate a serve cell sustains, once, by a sweep on the chip:

    python3 chipbench/sweep.py --workload <name> --rates 2,3,4 --seconds 25

One replica, one warm-up, then the cell's traffic mix at each rate in turn,
drained between rates. Prints one JSON line per rate; the builder reads them
and writes `rate_rps` into the traffic file as a number. Not part of a run.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu._private import accelerators

    from chipbench import harness, traffic as gen
    from chipbench.kinds import serve as kind

    cell = harness.resolve_cell(args.workload)
    if accelerators.detect_num_tpu_chips() < cell["chips"]:
        print("chipbench.sweep: no TPU chip here", file=sys.stderr)
        return 2
    harness.prepare_env()
    conf, mix = cell["config_file"], cell["traffic_file"]
    t0 = time.time()
    ray_tpu.init(num_tpus=cell["chips"])
    try:
        host, port = kind.deploy(kind.llm_config(conf, args.seed, None), 1100.0)
        client = gen.Client(host, port)
        print(json.dumps({"ready_s": time.time() - t0}), flush=True)
        kind.warm_up(client, mix["warmup"], args.seed, mix.get("warmup_wave", 0))
        print(json.dumps({"warm_s": time.time() - t0}), flush=True)
        for rate in (float(r) for r in args.rates.split(",")):
            s0 = client.post("/v1/stats", {})["answer"]
            requests = gen.schedule({**mix, "rate_rps": rate}, args.seed, args.seconds)
            records, window_s = gen.open_loop(client, requests, args.seconds)
            stuck = [r for r in records if r["status"] != "ok"]
            if stuck:
                print(json.dumps({"rate_rps": rate, "not_ok": stuck[:40],
                                  **gen.summarize(records, window_s)}), flush=True)
            s1 = gen.Client(host, port, timeout_s=30.0).post("/v1/stats", {})["answer"]
            out = gen.summarize(records, window_s)
            drained_s = max(r.get("done_s", 0.0) for r in records) - window_s
            steps = s1["decode_steps"] - s0["decode_steps"]
            print(json.dumps({
                "rate_rps": rate, **out, "drain_after_window_s": drained_s,
                "decode_steps": steps,
                "compiles": s1["compile_cache"]["requests"] - s0["compile_cache"]["requests"],
                "engine": {k: s1.get(k) for k in ("free_slots", "free_pages", "waiting",
                                                  "active", "decode_occupancy")},
                "memory": s1["device_memory"]}), flush=True)
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
