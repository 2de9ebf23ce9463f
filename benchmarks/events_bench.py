"""Cluster event plane overhead: actor churn with events on vs off.

The control-plane event log (ISSUE 19) hangs emission sites off the GCS's
hottest actor paths — _create_actor, dispatch, _on_task_done,
_on_worker_death, _kill_actor — plus DEBUG lease-grant events on every
lease cycle. The budget is ≤5% on control-plane-bound work; this bench
measures it the same way dag_bench measures instrumentation overhead:
alternating on/off rounds (interleaving cancels the scheduling drift of a
small shared box, which otherwise swamps a ≤5% effect), pooling per-cycle
samples, comparing medians.

The enabled flag (`RayConfig.cluster_events`, env
RAY_TPU_CLUSTER_EVENTS) is read once at GCS construction, so unlike the
DAG bench each round is its own session: set the env, reset the config
cache, init, churn, shutdown. A churn cycle = create a batch of actors,
round-trip a ping through each, kill them all — every phase of the actor
lifecycle state machine, which is exactly where the emit sites live.

JSON on stdout + rows merged into MICROBENCH.json like the other benches.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

BATCH = 8          # actors per churn cycle (== worker pool capacity)
CYCLES = 12        # churn cycles per round
ROUNDS = 4         # on/off round pairs


def _churn_round(cycles: int = CYCLES, batch: int = BATCH):
    """One session's per-cycle wall times for create→ping→kill churn."""
    import ray_tpu

    ray_tpu.init(num_cpus=2 * batch, num_workers=batch, max_workers=batch)

    @ray_tpu.remote
    class Churn:
        def ping(self):
            return 1

    samples = []
    try:
        # warmup cycle: worker pool spin-up + import costs stay out of the
        # measured samples
        warm = [Churn.remote() for _ in range(batch)]
        ray_tpu.get([a.ping.remote() for a in warm], timeout=120)
        for a in warm:
            ray_tpu.kill(a)
        for _ in range(cycles):
            t0 = time.perf_counter()
            actors = [Churn.remote() for _ in range(batch)]
            ray_tpu.get([a.ping.remote() for a in actors], timeout=120)
            for a in actors:
                ray_tpu.kill(a)
            samples.append(time.perf_counter() - t0)
    finally:
        ray_tpu.shutdown()
    return samples


def bench_events_overhead(rounds: int = ROUNDS) -> dict:
    from ray_tpu._private import events as cluster_events
    from ray_tpu._private.ray_config import RayConfig

    knob = "RAY_TPU_CLUSTER_EVENTS"
    saved = os.environ.get(knob)
    samples = {"on": [], "off": []}
    try:
        for _ in range(rounds):
            for mode in ("on", "off"):
                if mode == "off":
                    os.environ[knob] = "0"
                else:
                    # FORCE the default-on setting (pop any ambient
                    # override): a shell exporting RAY_TPU_CLUSTER_EVENTS=0
                    # must not turn the A/B comparison into off-vs-off
                    os.environ.pop(knob, None)
                RayConfig.reset()
                cluster_events.reset()
                samples[mode].extend(_churn_round())
    finally:
        if saved is None:
            os.environ.pop(knob, None)
        else:
            os.environ[knob] = saved
        RayConfig.reset()
        cluster_events.reset()

    on_ms = statistics.median(samples["on"]) * 1e3
    off_ms = statistics.median(samples["off"]) * 1e3
    return {
        "events_churn_batch": BATCH,
        "events_churn_cycles": len(samples["on"]),
        "events_churn_cycle_on_ms": round(on_ms, 2),
        "events_churn_cycle_off_ms": round(off_ms, 2),
        # the ≤5% acceptance budget from ISSUE 19
        "events_plane_overhead_pct": round(
            (on_ms - off_ms) / off_ms * 100.0, 2),
    }


def main():
    results = bench_events_overhead()
    print(json.dumps(results))
    assert results["events_plane_overhead_pct"] <= 5.0, (
        f"event plane costs {results['events_plane_overhead_pct']}% on "
        f"actor churn (budget 5%)")
    from ray_tpu._private.ray_perf import merge_microbench

    rows = [
        {"name": "events_churn_cycle_on", "ops_per_s": None, "value": None,
         "us_per_op": results["events_churn_cycle_on_ms"] * 1e3},
        {"name": "events_churn_cycle_off", "ops_per_s": None, "value": None,
         "us_per_op": results["events_churn_cycle_off_ms"] * 1e3},
        {"name": "events_plane_overhead_pct", "ops_per_s": None,
         "value": results["events_plane_overhead_pct"], "us_per_op": None},
    ]
    merge_microbench(os.path.join(os.path.dirname(__file__), "..",
                                  "MICROBENCH.json"), rows)


if __name__ == "__main__":
    main()
