"""The CPU rehearsals of a serve cell and of the train cell read the
thirteen metrics of the set-up (PR 57): the replica's start by stage from
`stats0.setup`, the compile seconds by stage from `compile_cache`. No number
here is ever reported."""

import argparse
import os
import time

import pytest

from chipbench import harness
from chipbench.kinds import serve, train

import tiny

SERVE = {"ready_process_s.serve", "ready_backend_s.serve", "ready_weights_s.serve",
         "ready_engine_s.serve", "ready_runtime_s.serve", "setup_trace_s.serve",
         "setup_lower_s.serve", "setup_load_s.serve", "setup_cold_programs.serve"}
TRAIN = {"setup_trace_s.train", "setup_lower_s.train", "setup_load_s.train",
         "setup_cold_programs.train"}


@pytest.fixture
def workers_see_the_repo(monkeypatch):
    here = os.path.dirname(os.path.abspath(__file__))
    monkeypatch.setenv("PYTHONPATH", os.pathsep.join([harness.ROOT, here]))


def _read(names: set, facts: dict) -> dict:
    per_layer = [m for m in harness.load_json(harness.ROOT, "BENCHMARK.json")[
        "per_layer"] if m["name"] in names]
    assert {m["name"] for m in per_layer} == names
    return {k: v["value"] for k, v in harness.read_layer_metrics(
        {"per_layer": per_layer}, facts).items()}


def test_serve_rehearsal_reads_the_start_by_stage(tmp_path, workers_see_the_repo):
    cell = tiny.mixtral_cell()
    cell["traffic_file"]["classes"][0]["prompt"].update(median=20, min=8, max=30)
    cell["traffic_file"]["classes"][0]["output"].update(median=4, min=2, max=6)
    cell["traffic_file"]["warmup"] = [[10, 4], [20, 8]]
    args = argparse.Namespace(seed=2**31 + 57, seconds=1.5, trace=0)
    facts = serve.run(cell, args, str(tmp_path), time.time(), on_chip=False)["facts"]
    got = _read(SERVE, facts)
    assert set(got) == SERVE and all(v >= 0 for v in got.values())
    # the replica's process began after the benchmark's: what is not the
    # replica's own is the rest of `ready_s`, and the stages stay inside it
    inside = sum(got[f"ready_{k}_s.serve"] for k in (
        "process", "backend", "weights", "engine"))
    assert got["ready_runtime_s.serve"] == pytest.approx(facts["ready_s"] - inside)
    assert 0 < inside < facts["ready_s"] < facts["setup_s"]
    setup = facts["stats0"]["setup"]
    assert setup["seconds"]["to_first_request"] >= 0
    assert setup == facts["stats1"]["setup"]
    # the compile seconds lie inside the replica's life so far
    made = sum(got[f"setup_{k}_s.serve"] for k in ("trace", "lower", "load"))
    assert 0 < made < facts["setup_s"]
    # a program without the record: only the cache's count is left to read
    for key in ("stats0", "stats1"):
        facts[key] = {k: v for k, v in facts[key].items() if k != "setup"}
        facts[key]["compile_cache"] = {k: v for k, v in facts[key][
            "compile_cache"].items() if k not in ("seconds", "programs")}
    assert set(_read(SERVE, facts)) == {"setup_cold_programs.serve"}


def test_train_rehearsal_reads_the_compile_seconds(tmp_path, workers_see_the_repo):
    args = argparse.Namespace(seed=2**31 + 57, seconds=1.0, trace=0)
    facts = train.run(tiny.gpt2_cell(), args, str(tmp_path), time.time(),
                      on_chip=False)["facts"]
    got = _read(TRAIN, facts)
    assert set(got) == TRAIN and all(v >= 0 for v in got.values())
    assert 0 < sum(got[f"setup_{k}_s.train"] for k in ("trace", "lower", "load")) \
        < facts["setup_s"]
    step = [row for name, row in facts["compile_cache"]["programs"].items()
            if row["lowers"] and row["trace_s"] > 0]
    assert step
