"""Object-store backend shootout: native shm arena vs file-per-object.

The arena (cpp/shm_store.cc) is the default object plane as of the flip in
ray_tpu/_private/object_store.py; this bench keeps the decision honest by
recording, for BOTH backends:

  - put/get latency medians at 1 KiB / 64 KiB / 4 MiB
  - sustained put throughput over a 10k-object run
  - tmpfs inode count after that run (the arena must hold O(1) segments
    while the file backend burns one inode per object)

Rows land in MICROBENCH.json as `object_store_*_{arena,file}` like the
other benches. Store-level measurement (no session) so the numbers isolate
the storage plane from GCS/serialization costs.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

SIZES = {"1KiB": 1 << 10, "64KiB": 1 << 16, "4MiB": 4 << 20}
ITERS = {"1KiB": 2000, "64KiB": 500, "4MiB": 50}
SUSTAINED_N = 10_000
SUSTAINED_SIZE = 16 << 10


def _make_store(backend: str, ns: str):
    if backend == "arena":
        from ray_tpu._private.shm_arena import ArenaStore

        # room for every latency-phase object plus the sustained run, so
        # eviction/spill cost never pollutes the latency medians
        return ArenaStore(ns, capacity=2 << 30)
    from ray_tpu._private.object_store import ShmObjectStore

    return ShmObjectStore(ns)


def _tmpfs_inodes(ns: str) -> int:
    prefix = f"rtpu_{ns}_"
    return sum(1 for n in os.listdir("/dev/shm") if n.startswith(prefix))


def bench_backend(backend: str) -> dict:
    ns = f"osbench{backend}"
    store = _make_store(backend, ns)
    out: dict = {}
    try:
        for tag, size in SIZES.items():
            payload = os.urandom(size)
            n = ITERS[tag]
            puts = []
            for i in range(n):
                oid = f"{tag}{i:08d}".lower()
                t0 = time.perf_counter()
                store.put_parts(oid, [payload], size)
                puts.append(time.perf_counter() - t0)
            gets = []
            for i in range(n):
                oid = f"{tag}{i:08d}".lower()
                t0 = time.perf_counter()
                obj = store.get(oid)
                assert obj.buf[:8] == payload[:8]
                if hasattr(obj, "release"):
                    obj.release()
                gets.append(time.perf_counter() - t0)
            out[f"put_{tag}"] = statistics.median(puts) * 1e6
            out[f"get_{tag}"] = statistics.median(gets) * 1e6
        # sustained put: 10k distinct objects back to back; the inode row
        # is the DELTA this run added to tmpfs (arena: 0 — objects land
        # inside the one pre-existing segment; file: one per object)
        payload = os.urandom(SUSTAINED_SIZE)
        inodes_before = _tmpfs_inodes(ns)
        t0 = time.perf_counter()
        for i in range(SUSTAINED_N):
            store.put_parts(f"sus{i:08d}", [payload], SUSTAINED_SIZE)
        dt = time.perf_counter() - t0
        out["sustained_put_per_s"] = SUSTAINED_N / dt
        out["sustained_put_mib_per_s"] = SUSTAINED_N * SUSTAINED_SIZE / dt / (1 << 20)
        out["tmpfs_inodes_10k"] = _tmpfs_inodes(ns) - inodes_before
    finally:
        store.cleanup_session()
    return out


def main():
    results: dict = {}
    for backend in ("file", "arena"):
        for k, v in bench_backend(backend).items():
            results[f"object_store_{k}_{backend}"] = round(v, 2)
    print(json.dumps(results, indent=1))
    from ray_tpu._private.ray_perf import merge_microbench

    rows = []
    for name, v in results.items():
        if "_per_s" in name:
            rows.append({"name": name, "ops_per_s": v, "value": None,
                         "us_per_op": None})
        elif name.startswith(("object_store_put_", "object_store_get_")):
            rows.append({"name": name, "ops_per_s": None, "value": None,
                         "us_per_op": v})
        else:
            rows.append({"name": name, "ops_per_s": None, "value": v,
                         "us_per_op": None})
    merge_microbench(os.path.join(os.path.dirname(__file__), "..",
                                  "MICROBENCH.json"), rows)


if __name__ == "__main__":
    main()
