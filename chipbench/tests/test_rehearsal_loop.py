"""The serve cell's CPU rehearsal reads the eight metrics of the engine
loop (PR 24) from the two `stats()` readings the runner already stores.
No number here is ever reported."""

import argparse
import os
import time

import pytest

from chipbench import harness
from chipbench.kinds import serve

import tiny

LOOP_METRICS = {
    "engine_host_pct.chat", "engine_host_pct.doc", "step_host_ms.chat",
    "step_device_wait_ms.chat", "engine_decode_pct.doc", "queue_wait_ms.chat",
    "queue_wait_ms.doc", "prefill_latency_ms.doc"}


@pytest.fixture
def workers_see_the_repo(monkeypatch):
    here = os.path.dirname(os.path.abspath(__file__))
    monkeypatch.setenv("PYTHONPATH", os.pathsep.join([harness.ROOT, here]))


def test_serve_rehearsal_reads_the_loop_metrics(tmp_path, workers_see_the_repo):
    cell = tiny.mixtral_cell()
    cell["traffic_file"]["classes"][0]["prompt"].update(median=20, min=8, max=30)
    cell["traffic_file"]["classes"][0]["output"].update(median=4, min=2, max=6)
    cell["traffic_file"]["warmup"] = [[10, 4], [20, 8], [30, 8]]
    args = argparse.Namespace(seed=2**31 + 11, seconds=2.0, trace=0)
    r = serve.run(cell, args, str(tmp_path), time.time(), on_chip=False)
    facts = r["facts"]
    assert r["correct"] and facts["compiles_in_window"] == 0
    per_layer = [m for m in harness.load_json(harness.ROOT, "BENCHMARK.json")[
        "per_layer"] if m["name"] in LOOP_METRICS]
    assert {m["name"] for m in per_layer} == LOOP_METRICS
    got = harness.read_layer_metrics({"per_layer": per_layer}, facts)
    assert set(got) == LOOP_METRICS
    assert 0 < got["engine_host_pct.chat"]["value"] <= 100
    assert 0 < got["engine_decode_pct.doc"]["value"] <= 100
    assert got["step_host_ms.chat"]["value"] > 0
    assert got["step_device_wait_ms.chat"]["value"] > 0
    assert got["queue_wait_ms.chat"]["value"] >= 0
    assert got["prefill_latency_ms.doc"]["value"] > 0
    loop0, loop1 = facts["stats0"]["loop"], facts["stats1"]["loop"]
    covered = sum(loop1["seconds"].values()) - sum(loop0["seconds"].values())
    assert covered == pytest.approx(loop1["thread_s"] - loop0["thread_s"], rel=0.01)
    # a program without the counters (the parent commit): every reader finds
    # nothing, raises nothing, and the line leaves the metrics out
    for key in ("stats0", "stats1"):
        facts[key] = {k: v for k, v in facts[key].items() if k != "loop"}
    assert harness.read_layer_metrics({"per_layer": per_layer}, facts) == {}
    # the compile log rides stats() into facts.json
    assert isinstance(facts["stats1"]["compile_cache"]["recent"], list)
