"""Compiled-DAG step latency: channel plane vs the `.remote()` chain.

The channel execution plane provisions per-actor exec loops over
mutable-shm channels at compile time, so a steady-state step is one
channel write + one channel read with intermediates flowing actor→actor —
no task submission, no GCS, no object store (ROADMAP: ≥5× over the
equivalent `.remote()` chain on a 4-actor pipeline; the tier-1 test
asserts a loose ≥2× to absorb CI noise, this bench tracks the real
number).

Measures, on the same 4 actors:
- `.remote()` chain: one submit per stage per step, get() at the end;
- compiled sync: execute().result() per step (step LATENCY);
- compiled pipelined: max_inflight overlapped executions (step THROUGHPUT).

Instrumentation overhead (ISSUE 4): the channel hot path now carries
always-on per-phase histograms plus every-Nth-step span sampling
(`RayConfig.dag_metrics` / `dag_span_sample_every`). The knobs are stamped
into the exec-loop plans at COMPILE time, so the bench A/B-tests them in
ONE session by recompiling per round, alternating instrumented (default
settings) and uninstrumented rounds — interleaving cancels the scheduling
drift of a small shared box, which otherwise swamps a ≤5% effect. The
pooled median-step delta is reported as
`dag_instrumentation_overhead_pct` (budget ≤5%).

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

N_STAGES = 4
WARMUP = 25
STEPS = 400


def _measure_channel(actors, n_steps, warmup, with_pipelined=True):
    """(step seconds list, pipelined_us) for the channel plane on live
    actors. The overhead-baseline session skips the pipelined sweep — only
    the median sync step feeds the comparison."""
    import ray_tpu  # noqa: F401 — session already up
    from ray_tpu.dag import InputNode

    with InputNode() as inp:
        node = inp
        for a in actors:
            node = a.work.bind(node)
    compiled = node.experimental_compile(max_inflight_executions=8)
    assert compiled.uses_channels, compiled.fallback_reason
    for i in range(warmup):
        compiled.execute(i).result(timeout=120)
    chan_steps = []
    for i in range(n_steps):
        t0 = time.perf_counter()
        compiled.execute(i).result(timeout=120)
        chan_steps.append(time.perf_counter() - t0)
    piped_us = None
    if with_pipelined:
        # pipelined throughput: overlapped in-flight executions
        t0 = time.perf_counter()
        futs = [compiled.execute_async(i) for i in range(n_steps)]
        for f in futs:
            f.result(timeout=120)
        piped_us = (time.perf_counter() - t0) / n_steps * 1e6
    compiled.teardown()
    return chan_steps, piped_us


def _alternating_overhead(actors, steps_per_round=100, warmup=10,
                          rounds=4):
    """Pooled step samples for instrumented-vs-uninstrumented, interleaved
    round-robin in one session (compile → measure → teardown per round)."""
    from ray_tpu._private.ray_config import RayConfig

    knobs = ("RAY_TPU_DAG_METRICS", "RAY_TPU_DAG_SPAN_SAMPLE_EVERY")
    saved = {k: os.environ.get(k) for k in knobs}
    samples = {"on": [], "off": []}
    try:
        for _ in range(rounds):
            for mode in ("on", "off"):
                if mode == "off":
                    os.environ["RAY_TPU_DAG_METRICS"] = "0"
                    os.environ["RAY_TPU_DAG_SPAN_SAMPLE_EVERY"] = "0"
                else:
                    # FORCE default instrumentation settings (pop any
                    # ambient override): a shell that exports
                    # RAY_TPU_DAG_METRICS=0 must not turn the A/B
                    # comparison into off-vs-off
                    for k in knobs:
                        os.environ.pop(k, None)
                RayConfig.reset()
                steps, _ = _measure_channel(actors, steps_per_round, warmup,
                                            with_pipelined=False)
                samples[mode].extend(steps)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        RayConfig.reset()
    return samples


def bench_dag(n_steps: int = STEPS, warmup: int = WARMUP) -> dict:
    # step latency is reported as the per-step MEDIAN (scheduling tails
    # on small hosts make means noisy); means ride along for reference
    import ray_tpu

    ray_tpu.init(num_cpus=16, num_workers=N_STAGES, max_workers=8)

    @ray_tpu.remote
    class Stage:
        def __init__(self, bias):
            self.bias = bias

        def work(self, x):
            return x + self.bias

    try:
        actors = [Stage.remote(1) for _ in range(N_STAGES)]
        for a in actors:
            a.__ray_ready__()

        # ---- baseline: the equivalent .remote() chain, one step at a time
        def chain_step(x):
            ref = x
            for a in actors:
                ref = a.work.remote(ref)
            return ray_tpu.get(ref, timeout=120)

        for i in range(warmup):
            chain_step(i)
        remote_steps = []
        for i in range(n_steps):
            t0 = time.perf_counter()
            chain_step(i)
            remote_steps.append(time.perf_counter() - t0)

        # ---- channel plane at default instrumentation (headline numbers)
        chan_steps, piped_us = _measure_channel(actors, n_steps, warmup)

        # ---- instrumentation overhead: interleaved A/B rounds
        ab = _alternating_overhead(actors)
    finally:
        ray_tpu.shutdown()

    remote_us = statistics.median(remote_steps) * 1e6
    chan_us = statistics.median(chan_steps) * 1e6
    instr_us = statistics.median(ab["on"]) * 1e6
    bare_us = statistics.median(ab["off"]) * 1e6
    return {
        "dag_stages": N_STAGES,
        "dag_steps": n_steps,
        "dag_remote_chain_step_us": round(remote_us, 1),
        "dag_channel_step_us": round(chan_us, 1),
        "dag_remote_chain_step_mean_us": round(
            sum(remote_steps) / n_steps * 1e6, 1),
        "dag_channel_step_mean_us": round(
            sum(chan_steps) / n_steps * 1e6, 1),
        "dag_channel_pipelined_step_us": round(piped_us, 1),
        "dag_channel_speedup": round(remote_us / chan_us, 2),
        "dag_channel_pipelined_speedup": round(remote_us / piped_us, 2),
        # instrumented (default sampling) vs uninstrumented channel step,
        # pooled over interleaved rounds: the ≤5% budget from ISSUE 4
        "dag_channel_step_instrumented_us": round(instr_us, 1),
        "dag_channel_step_uninstrumented_us": round(bare_us, 1),
        "dag_instrumentation_overhead_pct": round(
            (instr_us - bare_us) / bare_us * 100.0, 2),
    }


def main():
    results = bench_dag()
    print(json.dumps(results))
    from ray_tpu._private.ray_perf import merge_microbench

    rows = [
        {"name": "dag_remote_chain_step", "ops_per_s": None, "value": None,
         "us_per_op": results["dag_remote_chain_step_us"]},
        {"name": "dag_channel_step", "ops_per_s": None, "value": None,
         "us_per_op": results["dag_channel_step_us"]},
        {"name": "dag_channel_pipelined_step", "ops_per_s": None,
         "value": None,
         "us_per_op": results["dag_channel_pipelined_step_us"]},
        {"name": "dag_channel_speedup", "ops_per_s": None,
         "value": results["dag_channel_speedup"], "us_per_op": None},
        {"name": "dag_channel_step_uninstrumented", "ops_per_s": None,
         "value": None,
         "us_per_op": results["dag_channel_step_uninstrumented_us"]},
        {"name": "dag_instrumentation_overhead_pct", "ops_per_s": None,
         "value": results["dag_instrumentation_overhead_pct"],
         "us_per_op": None},
    ]
    merge_microbench(os.path.join(os.path.dirname(__file__), "..",
                                  "MICROBENCH.json"), rows)


if __name__ == "__main__":
    main()
