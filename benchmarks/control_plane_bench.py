"""Control-plane ceilings: what the single GCS process sustains.

VERDICT round-3 item 9 / round-4 item 4: publish measured ceilings
(actors, concurrent placement groups, virtual nodes, deep task queue) at
the reference envelope so the next scaling fix is data-driven.
Reference envelope (release/benchmarks/README.md): many_actors 10k+,
many_pgs 1k, many_nodes 250 (multi-node, 2k virtual here); deep queue 1M
queued tasks drained.

Method on the 1-core box: batched creation, recording the per-step rate
SERIES (first/min/last) so a mid-run knee is visible in the artifact, plus
an end-to-end liveness probe at peak scale. Results land in
MICROBENCH.json.
"""

from __future__ import annotations

import json
import os
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def bench_actors(max_actors: int = 10_000, step: int = 500) -> dict:
    import ray_tpu

    ray_tpu.init(num_cpus=4, num_workers=2, max_workers=4)

    @ray_tpu.remote
    class A:
        def ping(self):
            return 1

    # actors beyond worker capacity queue as pending — the ceiling here is
    # GCS bookkeeping (registration + state machine), matching the
    # reference's many_actors envelope semantics
    handles = []
    rates = []
    out: dict = {}
    try:
        while len(handles) < max_actors:
            t0 = time.perf_counter()
            handles.extend(A.remote() for _ in range(step))
            dt = time.perf_counter() - t0
            rates.append(step / dt)
        # liveness under load: one round trip through the first actors
        t0 = time.perf_counter()
        assert ray_tpu.get(handles[0].ping.remote(), timeout=120) == 1
        ping_ms = (time.perf_counter() - t0) * 1e3
        out = {
            "actors_registered": len(handles),
            "actor_submit_per_s_first": round(rates[0], 1),
            "actor_submit_per_s_min": round(min(rates), 1),
            "actor_submit_per_s_last": round(rates[-1], 1),
            "actor_ping_ms_at_peak": round(ping_ms, 1),
        }
    finally:
        ray_tpu.shutdown()
    return out


def bench_pgs(max_pgs: int = 1200, step: int = 100) -> dict:
    import ray_tpu

    ray_tpu.init(num_cpus=10_000, num_workers=0, max_workers=1)
    pgs = []
    rates = []
    out: dict = {}
    try:
        while len(pgs) < max_pgs:
            t0 = time.perf_counter()
            for _ in range(step):
                pgs.append(ray_tpu.util.placement_group(
                    [{"CPU": 1.0}], strategy="PACK"))
            dt = time.perf_counter() - t0
            rates.append(step / dt)
        ray_tpu.get(pgs[-1].ready(), timeout=120)
        t0 = time.perf_counter()
        for pg in pgs[: step]:
            ray_tpu.util.remove_placement_group(pg)
        removal_rate = step / (time.perf_counter() - t0)
        out = {
            "pgs_created": len(pgs),
            "pg_create_per_s_first": round(rates[0], 1),
            "pg_create_per_s_min": round(min(rates), 1),
            "pg_create_per_s_last": round(rates[-1], 1),
            "pg_remove_per_s": round(removal_rate, 1),
        }
    finally:
        ray_tpu.shutdown()
    return out


def bench_nodes(max_nodes: int = 2000, step: int = 200) -> dict:
    import ray_tpu
    from ray_tpu.cluster_utils import Cluster

    ray_tpu.init(num_cpus=2, num_workers=0, max_workers=1)
    cluster = Cluster(initialize_head=False)
    rates = []
    out: dict = {}
    n = 0
    try:
        while n < max_nodes:
            t0 = time.perf_counter()
            for _ in range(step):
                cluster.add_node(num_cpus=4.0)
                n += 1
            rates.append(step / (time.perf_counter() - t0))
        from ray_tpu._private.api import _get_worker

        t0 = time.perf_counter()
        nodes = _get_worker().list_nodes()
        list_ms = (time.perf_counter() - t0) * 1e3
        out = {
            # excludes the head node: virtual nodes this bench added
            "nodes_added": len(nodes) - 1,
            "node_add_per_s_first": round(rates[0], 1),
            "node_add_per_s_min": round(min(rates), 1),
            "node_add_per_s_last": round(rates[-1], 1),
            "list_nodes_ms_at_peak": round(list_ms, 1),
        }
    finally:
        ray_tpu.shutdown()
    return out


def bench_deep_queue(n_deep: int = 1_000_000, chunk: int = 100_000) -> dict:
    """Submit n_deep tasks behind blocked workers, then drain them all.

    Reference envelope: 1M queued tasks (release/benchmarks/README.md:29).
    Records the submit-rate SERIES per chunk (a knee from per-event queue
    scans or memory pressure shows up as first>>last) plus the drain rate
    and peak RSS.
    """
    os.environ.setdefault("RAY_TPU_DIRECT_DISPATCH", "0")
    import resource
    import tempfile
    import ray_tpu

    ray_tpu.init(num_cpus=2, num_workers=2, max_workers=2)

    @ray_tpu.remote
    def blocker(path):
        import time as _t
        open(path, "w").close()
        while not os.path.exists(path + ".go"):
            _t.sleep(0.05)
        return "unblocked"

    @ray_tpu.remote
    def noop():
        return 0

    d = tempfile.mkdtemp(prefix="cpbench")
    marks = [os.path.join(d, f"b{i}") for i in range(2)]
    blockers = [blocker.remote(m) for m in marks]
    deadline = time.time() + 30
    while not all(os.path.exists(m) for m in marks):
        if time.time() > deadline:
            raise RuntimeError("blockers never started")
        time.sleep(0.05)

    refs = []
    rates = []
    out: dict = {}
    try:
        while len(refs) < n_deep:
            t0 = time.perf_counter()
            refs.extend(noop.remote() for _ in range(chunk))
            rates.append(chunk / (time.perf_counter() - t0))
        rss_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024
        t1 = time.perf_counter()
        for m in marks:
            open(m + ".go", "w").close()
        ray_tpu.get(blockers, timeout=1200)
        ray_tpu.get(refs, timeout=1200)
        drain_rate = n_deep / (time.perf_counter() - t1)
        out = {
            "deep_queue_tasks": len(refs),
            "deep_submit_per_s_first": round(rates[0], 1),
            "deep_submit_per_s_min": round(min(rates), 1),
            "deep_submit_per_s_last": round(rates[-1], 1),
            "deep_drain_per_s": round(drain_rate, 1),
            "deep_queue_driver_rss_mb": round(rss_mb, 1),
        }
    finally:
        ray_tpu.shutdown()
    return out


def main():
    results = {}
    results.update(bench_actors())
    results.update(bench_pgs())
    results.update(bench_nodes())
    results.update(bench_deep_queue())
    print(json.dumps(results))
    from ray_tpu._private.ray_perf import merge_microbench

    rows = [{"name": f"ceiling_{k}", "ops_per_s": None, "value": v,
             "us_per_op": None} for k, v in results.items()]
    merge_microbench(os.path.join(os.path.dirname(__file__), "..",
                                  "MICROBENCH.json"), rows)


if __name__ == "__main__":
    main()
