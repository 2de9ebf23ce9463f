"""chipbench — the on-chip benchmark of ray_tpu (BENCHMARK.json, PERF.md).

One command runs one cell once and prints one JSON line:

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file the harness finds by the name in BENCHMARK.json:
`configs/<config>.json`, `traffic/<traffic>.json`, `kinds/<kind>.py` (the
runner of a traffic `kind`), `layer_metrics/<metric>.json` and
`readers/<reader>.py`, `reference/<name>.py` (the plain reference a
configuration names). Nothing here branches on a cell's, a configuration's
or a metric's name. The yardstick (traffic generation, FLOP and byte
functions, the peaks table, the references, the trace reduction) lives here
and imports nothing from `bench.py` or `benchmarks/`.
"""
