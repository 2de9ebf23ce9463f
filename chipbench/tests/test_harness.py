"""The harness finds everything by name in files; nothing branches on a name."""

import glob
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from chipbench import harness

BENCH = harness.load_json(harness.ROOT, "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
WIDTH_KEYS = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$|_rank$|head_dim|expand|experts_per_tok")


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves_from_files_by_name(name):
    cell = harness.resolve_cell(name)
    assert cell["config_file"]["name"] == cell["config"]
    assert cell["traffic_file"]["kind"] in ("train", "serve")
    harness.kind_runner(cell["traffic_file"]["kind"])
    reported = {m["name"] for m in cell["end_to_end"]}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell["per_layer"], "every cell reports a per-layer metric"
    for m in cell["per_layer"]:
        assert m["moves"] in reported, (m["name"], m["moves"])
        spec = harness.load_json(harness.BENCH_DIR, "layer_metrics", m["name"] + ".json")
        assert set(spec) == {"reader", "params"}   # the rest is BENCHMARK.json's
        assert os.path.exists(os.path.join(
            harness.BENCH_DIR, "readers", spec["reader"] + ".py"))


def test_benchmark_json_meets_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    cells = len(BENCH["workloads"])
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(1, cells // 4)
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads"):
        assert len({x["name"] for x in BENCH[k]}) == len(BENCH[k])
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(set(metrics)) == len(metrics)
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert c["name"] in used and c["file"].startswith("chipbench/")
        assert not any(WIDTH_KEYS.search(k) for k in c["reduced"])
        assert harness.load_json(harness.ROOT, c["file"])["reduced"] == c["reduced"]
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_unknown_device_kind_is_an_error():
    assert harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(harness.BenchError):
        harness.peaks_for("TPU v9 imaginary")
    with pytest.raises(harness.BenchError):
        harness.resolve_cell("no.such-cell")


def test_a_new_config_traffic_and_metric_are_files_plus_an_entry(tmp_path):
    """A later PR adds files and BENCHMARK.json entries and edits nothing."""
    bench_dir = tmp_path / "chipbench"
    for sub in ("configs", "traffic", "layer_metrics"):
        shutil.copytree(os.path.join(harness.BENCH_DIR, sub), bench_dir / sub)
    conf = harness.load_json(harness.BENCH_DIR, "configs", "gpt2-large.json")
    conf["name"] = "gpt2-medium"
    (bench_dir / "configs" / "gpt2-medium.json").write_text(json.dumps(conf))
    mix = harness.load_json(harness.BENCH_DIR, "traffic", "train-b4-s1024.json")
    mix["batch"] = 16
    (bench_dir / "traffic" / "train-b16-s1024.json").write_text(json.dumps(mix))
    (bench_dir / "layer_metrics" / "first_block_s.json").write_text(json.dumps(
        {"reader": "fact", "params": {"path": "block_s.0"}}))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "gpt2-medium", "source": "x",
                             "file": "chipbench/configs/gpt2-medium.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "gpt2-medium.train-b16", "config": "gpt2-medium",
                               "traffic": "train-b16-s1024", "chips": 1, "why": "x"})
    bench["end_to_end"][0]["workloads"].append("gpt2-medium.train-b16")
    bench["per_layer"].append({"name": "first_block_s", "unit": "s", "better": "lower",
                               "source": "host_clock", "layer": "x",
                               "moves": "train_tok_s_per_chip",
                               "workloads": ["gpt2-medium.train-b16"]})
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    cell = harness.resolve_cell("gpt2-medium.train-b16", str(bench_dir), bench)
    assert cell["traffic_file"]["batch"] == 16
    assert cell["config_file"]["name"] == "gpt2-medium"
    got = harness.read_layer_metrics(cell, {"block_s": {"0": 1.25}}, str(bench_dir))
    assert got == {"first_block_s": {"value": 1.25, "unit": "s"}}
    # a reader that finds nothing to read leaves its metric out
    assert harness.read_layer_metrics(cell, {}, str(bench_dir)) == {}


def test_no_code_branches_on_a_cell_config_or_metric_name():
    names = {x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in BENCH[k]} | {w["traffic"] for w in BENCH["workloads"]}
    # the two metrics each runner computes under its own name are its output
    # keys, not branches; everything else must come from files
    allowed = {"setup_s", "train_tok_s_per_chip", "served_tok_s", "tpot_p50_ms"}
    sources = [p for p in glob.glob(os.path.join(harness.BENCH_DIR, "**", "*.py"),
                                    recursive=True) if os.sep + "tests" + os.sep not in p]
    assert len(sources) > 10
    for path in sources:
        text = open(path).read()
        for name in names - allowed:
            assert f'"{name}"' not in text and f"'{name}'" not in text, (path, name)


def test_without_a_tpu_the_run_exits_nonzero_and_prints_no_result():
    env = {k: v for k, v in os.environ.items() if k != "RAY_TPU_CHIPS"}
    p = subprocess.run(
        [sys.executable, os.path.join(harness.BENCH_DIR, "run.py"), "--workload",
         BENCH["workloads"][0]["name"], "--seed", "3", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=harness.ROOT, timeout=120)
    assert p.returncode != 0
    assert "metrics" not in p.stdout and "no CPU mode" in p.stderr


def test_bound_arithmetic():
    assert harness.quartile_spread([10, 10.1, 10.2, 10.3, 10.4, 10.5]) == pytest.approx(
        0.35 / 10.25, rel=1e-6)  # exclusive quartiles 10.075 and 10.425
    assert harness.percentile(list(range(1, 101)), 95) == 95
    assert harness.percentile([5.0], 95) == 5.0
