"""Data-plane fault-tolerance A/B: supervision overhead on a HEALTHY
pipeline (ISSUE 20 satellite).

(reference gate: Ray Data enables per-block retry + actor-pool
supervision unconditionally because its bookkeeping is noise next to
the work it protects — python/ray/data/_internal/execution/. Here: the
same streaming pipeline runs with ``data_fault_tolerance`` on and off,
INTERLEAVED on/off/on/off so drift hits both arms equally, and the
median overhead of the FT arm must stay ≤5%. The pipeline uses MANY
small blocks: FT bookkeeping is per-dispatch (probe ready refs, retain
inputs, attempt accounting), so block count is the axis it scales
with — and the drain must dwarf the one-off actor-pool spin-up whose
0.1-0.6s jitter would otherwise drown the signal.)

The FT arm pays for: per-ready-ref error probes (`_probe_ready`), the
retained-input ledger for in-flight re-dispatch, attempt/backoff
bookkeeping, and the pool liveness sweep. None of that should be
visible on a pipeline where nothing fails.

Merges the ``fault_tolerance`` section into DATA_BENCH.json via
``merge_artifact`` — data_train_bench's
``results`` section survives a rerun of this script and vice versa.

Exit status is the assertion: nonzero when overhead exceeds the bar
(override the bar with RAY_TPU_DATA_AB_MAX_OVERHEAD_PCT).
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TRIALS = int(os.environ.get("RAY_TPU_DATA_AB_TRIALS", "5"))
_ROWS = int(os.environ.get("RAY_TPU_DATA_AB_ROWS", "64000"))
_BLOCKS = int(os.environ.get("RAY_TPU_DATA_AB_BLOCKS", "64"))
_MAX_OVERHEAD_PCT = float(
    os.environ.get("RAY_TPU_DATA_AB_MAX_OVERHEAD_PCT", "5.0"))


def _udf():
    # closure so it pickles by value (workers cannot import __main__
    # reliably across spawn configs); batch-sized arithmetic keeps the
    # work real but the runtime dominated by dispatch + transfer — the
    # regime where FT bookkeeping overhead would actually show up
    def fn(batch):
        import numpy as _np

        v = _np.asarray(batch["id"], dtype=_np.float64)
        for _ in range(8):
            v = _np.sqrt(v * v + 1.0)
        return {"id": batch["id"], "v": v}

    return fn


def _run_once(ft_on: bool) -> float:
    """One full pipeline drain under the given FT setting; returns
    wall seconds. The executor reads RayConfig at execute() time, so an
    env flip + reset() retoggles without a cluster restart."""
    from ray_tpu import data as rd
    from ray_tpu._private.ray_config import RayConfig

    os.environ["RAY_TPU_DATA_FAULT_TOLERANCE"] = "1" if ft_on else "0"
    RayConfig.reset()
    try:
        ds = rd.range(_ROWS, parallelism=_BLOCKS).map_batches(
            _udf(), compute="actors", concurrency=2)
        t0 = time.perf_counter()
        rows = ds.take_all()
        dt = time.perf_counter() - t0
        assert len(rows) == _ROWS
        return dt
    finally:
        os.environ.pop("RAY_TPU_DATA_FAULT_TOLERANCE", None)
        RayConfig.reset()


def _measure() -> dict:
    import ray_tpu

    # keep worker processes warm across actor-pool generations: each
    # trial builds a fresh 2-actor pool, and cold worker spawns would
    # otherwise dominate the sub-second drains being compared
    os.environ.setdefault("RAY_TPU_WARM_POOL_SIZE", "4")
    ray_tpu.init(num_cpus=8, num_workers=4, max_workers=8)
    try:
        _run_once(True)   # warm both arms: imports, pool, page cache
        _run_once(False)
        on_s: list[float] = []
        off_s: list[float] = []
        for i in range(_TRIALS):
            # alternate which arm goes first so slow-drift (page cache,
            # thermal, background load) cannot favor one side
            order = (True, False) if i % 2 == 0 else (False, True)
            for ft in order:
                (on_s if ft else off_s).append(_run_once(ft))
        med_on = statistics.median(on_s)
        med_off = statistics.median(off_s)
        overhead_pct = (med_on - med_off) / med_off * 100.0
        return {
            "rows": _ROWS,
            "blocks": _BLOCKS,
            "trials": _TRIALS,
            "ft_on_median_s": round(med_on, 4),
            "ft_off_median_s": round(med_off, 4),
            "ft_on_s": [round(s, 4) for s in on_s],
            "ft_off_s": [round(s, 4) for s in off_s],
            "overhead_pct": round(overhead_pct, 2),
            "max_overhead_pct": _MAX_OVERHEAD_PCT,
            "overhead_ok": bool(overhead_pct <= _MAX_OVERHEAD_PCT),
        }
    finally:
        ray_tpu.shutdown()


def main() -> int:
    sys.path.insert(0, _ROOT)
    from ray_tpu.scripts._artifacts import merge_artifact

    out = _measure()
    path = merge_artifact("DATA_BENCH.json", "fault_tolerance", out)
    print(json.dumps(out))
    if not out["overhead_ok"]:
        print(f"FAIL: FT-on overhead {out['overhead_pct']}% exceeds "
              f"{_MAX_OVERHEAD_PCT}% bar ({path})", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
