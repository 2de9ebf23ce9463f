"""Every kind of cell end to end on the CPU at a tiny size: the same runner,
the same files, host workers. Finds wrong paths, arguments and control flow
before a chip call; gives no number that is ever reported."""

import argparse
import json
import os
import time

import pytest

from chipbench import harness, traffic as gen
from chipbench.kinds import serve, train

import tiny


@pytest.fixture
def workers_see_the_repo(monkeypatch):
    here = os.path.dirname(os.path.abspath(__file__))
    monkeypatch.setenv("PYTHONPATH", os.pathsep.join([harness.ROOT, here]))


def test_train_cell_rehearsal(tmp_path, workers_see_the_repo):
    args = argparse.Namespace(seed=2**31 + 9, seconds=1.5, trace=0)
    r = train.run(tiny.gpt2_cell(), args, str(tmp_path), time.time(), on_chip=False)
    facts = r["facts"]
    assert r["correct"] and r["failed"] == 0 and r["attempted"] == facts["steps"] > 0
    assert facts["compiles_in_window"] == 0 and facts["check"]["ok"]
    blocks = json.load(open(tmp_path / "blocks.json"))
    assert len(blocks["block_s"]) * blocks["steps_per_block"] == facts["steps"]
    # the judged rate is every token over all of the window; the median of
    # the blocks stands beside it
    assert r["end_to_end"]["train_tok_s_per_chip"] == pytest.approx(
        facts["steps"] * 4 * 32 / facts["window_s"])
    assert facts["window_s"] >= sum(blocks["block_s"])
    tokens = blocks["steps_per_block"] * 4 * 32
    assert facts["block_median_tok_s_per_chip"] == pytest.approx(
        tokens / sorted(blocks["block_s"])[len(blocks["block_s"]) // 2], rel=0.2)
    assert facts["check"]["control_fails"] and not facts["check"]["control"]["logits_ok"]
    assert "pos_embed" in facts["check"]["grad_rel_err"]       # recorded, not judged
    assert 0 < facts["ready_s"] < r["end_to_end"]["setup_s"]
    cell = {"per_layer": [m for m in harness.load_json(
        harness.ROOT, "BENCHMARK.json")["per_layer"]
        if m["name"] in ("train_stall_pct", "mfu_pct", "input_wait_pct", "step_hbm_gb",
                         "block_tok_s_per_chip", "train_device_idle_pct")]}
    got = harness.read_layer_metrics(cell, facts)
    # no device trace on the CPU: its reader finds nothing and is left out
    assert set(got) == {"train_stall_pct", "mfu_pct", "input_wait_pct", "step_hbm_gb",
                        "block_tok_s_per_chip"}
    # against the MEDIAN block: a few fast blocks on a busy host read just under 0
    assert -5 < got["train_stall_pct"]["value"] < 100


@pytest.mark.parametrize("trace", [0, 1])
def test_serve_cell_rehearsal(tmp_path, workers_see_the_repo, monkeypatch, trace):
    cell = tiny.mixtral_cell()
    stats_calls, timers = [], []
    post, start = gen.Client.post, serve.TraceSlice.start
    monkeypatch.setattr(gen.Client, "post", lambda self, path, *a, **k: (
        stats_calls.append(path) if path == "/v1/stats" else None, post(self, path, *a, **k))[1])
    monkeypatch.setattr(serve.TraceSlice, "start", lambda self: (
        timers.append(self), start(self))[1])
    # the tiny warm-up covers the tiny mix: prompts of one bucket, short answers
    cell["traffic_file"]["classes"][0]["prompt"].update(median=20, min=8, max=30)
    cell["traffic_file"]["classes"][0]["output"].update(median=4, min=2, max=6)
    cell["traffic_file"]["warmup"] = [[10, 4], [20, 8], [30, 8]]
    args = argparse.Namespace(seed=2**31 + 9, seconds=2.0, trace=trace)
    r = serve.run(cell, args, str(tmp_path), time.time(), on_chip=False)
    facts = r["facts"]
    # an untraced run does what it did before there were slices: no marker
    # directory, no timer thread, the two readings around the window
    assert (len(stats_calls), len(timers)) == ((4, 1) if trace else (2, 0))
    if trace:
        # the timer, the hook, both readings at the slice's ends and the wait
        # for `done`; the CPU has no device plane, and only it is let off that
        assert facts["trace"] is None and "busy_s" not in r["device"]
        assert 0 < facts["trace_span_s"] < 2.0 and 0 <= facts["trace_stop_s"] < 60
        ends = [facts[k]["decode_steps"] for k in ("stats0", "stats_t0", "stats_t1", "stats1")]
        assert ends == sorted(ends) and ends[0] < ends[3]
    else:
        assert not {"trace", "stats_t0", "stats_t1", "trace_span_s"} & set(facts)
    assert not os.path.exists(tmp_path / "trace_ctl")
    assert facts["check"]["ok"] and facts["check"]["logits_rel_err_median"] < 1e-4
    assert facts["check"]["positions_within_tol"] == facts["check"]["positions"]
    assert facts["check"]["control_fails"]
    assert r["failed"] == 0 and r["attempted"] == 12
    assert facts["compiles_in_window"] == 0 and r["correct"]
    records = [json.loads(line) for line in open(tmp_path / "requests.jsonl")]
    assert len(records) == 12
    assert all(rec["status"] == "ok" and rec["first_s"] >= rec["sent_s"] >= rec["due_s"]
               for rec in records)
    assert r["end_to_end"]["ttft_p95_ms"] > 0 and r["end_to_end"]["tpot_p50_ms"] > 0
    in_window = sum(rec["prompt_tokens"] + rec["tokens_in_window"] for rec in records
                    if rec["first_s"] <= 2.0)
    # plus the part done of a prompt in progress when the window ended
    assert r["end_to_end"]["served_tok_s"] >= in_window / 2.0 * (1 - 1e-9)
    got = harness.read_layer_metrics(
        {"per_layer": [m for m in harness.load_json(harness.ROOT, "BENCHMARK.json")[
            "per_layer"] if m["name"] in ("decode_occupancy.chat", "decode_step_ms",
                                          "ready_s.serve", "gen_late_p95_ms")]}, facts)
    assert len(got) == 4 and got["decode_occupancy.chat"]["value"] >= 1.0
