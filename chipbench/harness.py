"""Resolution of a cell from files by name, the peaks table, the result line."""

from __future__ import annotations

import importlib
import json
import os
import statistics

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

# Published peaks of one chip, keyed by jax `device_kind`. Source: Google
# Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM, 16 GB).
# A kind that is not here is an error, never a default.
PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9, "source": "Google Cloud, TPU v5e"},
}


class BenchError(RuntimeError):
    """The run cannot give a result (no chip, unknown cell, unknown kind)."""


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise BenchError(
            f"no peaks on record for device_kind {device_kind!r}: add it to "
            "chipbench/harness.py PEAKS with its source, do not default")
    return PEAKS[device_kind]


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def resolve_cell(name: str, bench_dir: str = BENCH_DIR,
                 benchmark: dict | None = None) -> dict:
    """The cell `name` of BENCHMARK.json with its configuration, its traffic
    mix and the metrics it reports, each loaded from the file of that name."""
    benchmark = benchmark or load_json(os.path.dirname(bench_dir), "BENCHMARK.json")
    cells = {w["name"]: w for w in benchmark["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json "
                         f"(has: {sorted(cells)})")
    cell = dict(cells[name])
    configs = {c["name"]: c for c in benchmark["configs"]}
    if cell["config"] not in configs:
        raise BenchError(f"workload {name!r} names config {cell['config']!r}, "
                         "which BENCHMARK.json does not list")
    root = os.path.dirname(bench_dir)
    cell["config_file"] = load_json(root, configs[cell["config"]]["file"])
    cell["traffic_file"] = load_json(bench_dir, "traffic", cell["traffic"] + ".json")
    cell["end_to_end"] = [m for m in benchmark["end_to_end"] if _applies(m, name)]
    cell["per_layer"] = [m for m in benchmark["per_layer"] if _applies(m, name)]
    cell["run_seconds"] = benchmark["run_seconds"]
    return cell


def kind_runner(kind: str):
    """The runner of a traffic `kind`: chipbench/kinds/<kind>.py."""
    try:
        return importlib.import_module(f"chipbench.kinds.{kind}")
    except ModuleNotFoundError as e:
        if e.name != f"chipbench.kinds.{kind}":
            raise
        raise BenchError(f"no runner chipbench/kinds/{kind}.py for traffic "
                         f"kind {kind!r}") from e


def read_layer_metrics(cell: dict, facts: dict, bench_dir: str = BENCH_DIR) -> dict:
    """Every per-layer metric of the cell through its own reader
    (layer_metrics/<metric>.json names readers/<reader>.py). A reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in cell["per_layer"]:
        spec = load_json(bench_dir, "layer_metrics", m["name"] + ".json")
        reader = importlib.import_module(f"chipbench.readers.{spec['reader']}")
        value = reader.read(facts, spec.get("params", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def end_to_end_metrics(cell: dict, values: dict) -> dict:
    missing = [m["name"] for m in cell["end_to_end"] if values.get(m["name"]) is None]
    if missing:
        raise BenchError(f"the run gave no value for {missing}")
    return {m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in cell["end_to_end"]}


def quartile_spread(values: list) -> float:
    """(Q3 - Q1) / median, quartiles as `statistics.quantiles(n=4)` — the
    spread the bounds are set from."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def percentile(values: list, q: float) -> float:
    """Nearest-rank percentile (q in 0..100) of a non-empty list."""
    s = sorted(values)
    k = max(0, min(len(s) - 1, int(-(-q * len(s) // 100)) - 1))
    return s[k]


def prepare_env() -> None:
    """Before `ray_tpu.init`: workers import chipbench and the program from
    this checkout and keep every compiled program, small ones too, in the one
    fixed cache (JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache)."""
    from ray_tpu._private import accelerators

    os.environ["PYTHONPATH"] = os.pathsep.join(
        [ROOT] + [p for p in os.environ.get("PYTHONPATH", "").split(os.pathsep) if p])
    accelerators.export_compile_cache_env()
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def rng_seed(seed: int, *salt: int) -> list:
    """A numpy SeedSequence entropy list from a `--seed` of any size."""
    return [int(seed) & 0xFFFFFFFF, int(seed) >> 32, *salt]
