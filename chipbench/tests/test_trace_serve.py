"""A traced serve run: the slice's timer and its wait against a stub hook,
the two errors a trace that cannot be used ends in, and the roofline readers
on a recorded slice (data/trace_rows_serve.json: the first device events of a
traced `mixtral-8x7b.chat-steady` slice on a v5e, as `trace_reduce.reduce_dir`
keeps them) with the kernel pushed below the ten largest ops by hand."""

import copy
import json
import os
import threading
import time

import pytest

from chipbench import harness, trace_reduce as tr
from chipbench.kinds import serve
from chipbench.readers import gqa_decode_roofline, kernel_roofline

HERE = os.path.dirname(os.path.abspath(__file__))
REC = json.load(open(os.path.join(HERE, "data", "trace_rows_serve.json")))
KERNEL = "ragged_paged_attention"
GQA = {"op": KERNEL, "work": "cache.context_tokens", "layers": 4, "heads": 32,
       "kv_heads": 8, "head_dim": 128}
LATENT = {"op": KERNEL, "cost": "latent_decode_attention_cost",
          "work": "cache.context_tokens", "stacks": [4],
          "args": {"heads": 16, "row_values": 576, "value_values": 512}}


def _stats(context_tokens):
    return {"cache": {"context_tokens": context_tokens},
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


def _facts(rows, **stats):
    ends = {"stats0": 0, "stats_t0": 1_000_000, "stats_t1": 3_000_000,
            "stats1": 9_000_000, **stats}
    return {"trace": tr.reduce_rows(rows), **{k: _stats(v) for k, v in ends.items()}}


def _pushed_down(rows):
    """The recorded rows with eleven hand-made ops after them, each longer
    than everything the kernel did: the kernel then ranks twelfth or lower."""
    rows = copy.deepcopy(rows)
    events = rows["devices"]["0"]
    end = max(s + d for _, s, d in events)
    spent = sum(d for n, _, d in events if n.split(".")[0] == KERNEL)
    for i in range(11):
        events.append([f"fusion.{9000 + i}", end, 2 * spent])
        end += 2 * spent
    return rows


def test_recorded_slice_marks_its_kernels():
    launches = [n for n in REC["kernels"] if n.split(".")[0] == KERNEL]
    assert launches and all(n in {e[0] for e in REC["devices"]["0"]} for n in launches)
    out = tr.reduce_rows(REC)
    by_hand = sum(d for n, _, d in REC["devices"]["0"] if n in launches)
    assert sum(out["kernel_calls"][n]["seconds"] for n in launches) == pytest.approx(
        by_hand / 1e9)
    assert out["kernel_s"] >= by_hand / 1e9
    assert out["device_events"] == len(REC["devices"]["0"])
    # a launch keeps its plain name in the list the ledger's breakdown is made from
    assert not [n for n, _ in out["breakdown"]["device_ops"] if n.startswith("pallas:")]


@pytest.mark.parametrize("reader,params", [(gqa_decode_roofline, GQA),
                                           (kernel_roofline, LATENT)])
def test_a_kernel_below_the_ten_largest_ops_keeps_its_metric(reader, params):
    rows = _pushed_down(REC)
    facts = _facts(rows)
    top = [n.split(":")[-1].split(".")[0] for n, _ in facts["trace"]["breakdown"]["device_ops"]]
    assert len(top) == 10 and KERNEL not in top
    value = reader.read(facts, params)
    assert value is not None and value > 0
    # the seconds are the kernel's own: rank does not move the reading
    assert value == pytest.approx(reader.read(_facts(REC), params))
    # None only where no op of the name ran
    assert reader.read(facts, {**params, "op": "ragged_latent_attention"}) is None
    assert reader.read({**facts, "trace": None}, params) is None


@pytest.mark.parametrize("reader,params", [(gqa_decode_roofline, GQA),
                                           (kernel_roofline, LATENT)])
def test_the_work_is_counted_over_the_slice_and_not_the_window(reader, params):
    base = reader.read(_facts(REC), params)
    # the window's readings move: nothing changes
    assert reader.read(_facts(REC, stats0=5, stats1=77_000_000), params) == base
    # the slice's readings move: the share follows the work
    assert reader.read(_facts(REC, stats_t1=5_000_000), params) == pytest.approx(2 * base)
    facts = _facts(REC)
    del facts["stats_t0"]
    assert reader.read(facts, params) is None
    # two readings that lie twice the traced seconds apart on the engine's own
    # clock (a replica with a queue answers late) count half their work
    facts = _facts(REC)
    facts["stats_t0"]["loop"] = {"thread_s": 100.0}
    facts["stats_t1"]["loop"] = {"thread_s": 100.0 + 2 * facts["trace"]["window_s"]}
    assert reader.read(facts, params) == pytest.approx(base / 2)


def test_a_serve_traffic_file_without_a_trace_group_fails_a_traced_run():
    with pytest.raises(harness.BenchError, match="`trace` group"):
        serve.trace_slice({"kind": "serve", "rate_rps": 1.0}, 40.0)
    assert serve.trace_slice({"trace": {"offset_s": 12.0, "seconds": 8.0}}, 40.0) == (12.0, 8.0)
    offset, length = serve.trace_slice({"trace": {"offset_s": 12.0, "seconds": 8.0}}, 2.0)
    assert offset + length < 2.0 and offset / length == pytest.approx(1.5)


def test_every_serve_traffic_file_names_its_slice():
    folder = os.path.join(harness.BENCH_DIR, "traffic")
    serve_files = [f for f in sorted(os.listdir(folder))
                   if harness.load_json(folder, f)["kind"] == "serve"]
    assert len(serve_files) >= 5
    for f in serve_files:
        offset, length = serve.trace_slice(harness.load_json(folder, f), 40.0)
        assert offset > 0 and length > 0 and offset + length <= 40.0, f


class StubClient:
    timeout_s = 5.0

    def __init__(self):
        self.paths = []

    def post(self, path, body, on_token=None):
        self.paths.append(path)
        return {"status": 200, "answer": {"decode_steps": len(self.paths)}}


def _stub_hook(ctl, stop_takes_s):
    """What `program._trace_on_request` does to the marker files, without a
    profiler; `stop_takes_s` None: `stop_trace` never returns."""
    def hook():
        while not os.path.exists(os.path.join(ctl, "start")):
            time.sleep(0.005)
        open(os.path.join(ctl, "started"), "w").close()
        while not os.path.exists(os.path.join(ctl, "stop")):
            time.sleep(0.005)
        if stop_takes_s is None:
            return
        time.sleep(stop_takes_s)
        with open(os.path.join(ctl, "done"), "w") as f:
            f.write(repr(time.time()))

    t = threading.Thread(target=hook, daemon=True)
    t.start()
    return t


def test_the_slice_reads_the_counters_at_its_two_ends(tmp_path):
    client = StubClient()
    hook = _stub_hook(str(tmp_path), 0.2)
    tracing = serve.TraceSlice(str(tmp_path), client, 0.1, 0.3)
    tracing.start()
    got = tracing.result()
    hook.join(5.0)
    assert not hook.is_alive() and not tracing.is_alive()
    assert client.paths == ["/v1/stats", "/v1/stats"]
    assert got["stats_t0"] == {"decode_steps": 1} and got["stats_t1"] == {"decode_steps": 2}
    assert 0 <= got["stats_t0_took_s"] < 0.3 and 0 <= got["stats_t1_took_s"] < 0.3
    assert 0.3 <= got["trace_span_s"] < 0.6 and 0.15 < got["trace_stop_s"] < 1.0


def test_a_late_answer_does_not_hold_the_slice_open(tmp_path):
    class SlowFirst(StubClient):
        timeout_s = 5.0

        def post(self, path, body, on_token=None):
            if not self.paths:
                self.paths.append(path)
                time.sleep(1.0)
                return {"status": 200, "answer": {"decode_steps": 0}}
            return super().post(path, body, on_token)

    hook = _stub_hook(str(tmp_path), 0.0)
    tracing = serve.TraceSlice(str(tmp_path), SlowFirst(), 0.05, 0.2)
    tracing.start()
    got = tracing.result()
    hook.join(5.0)
    assert 0.2 <= got["trace_span_s"] < 0.5 and got["stats_t0_took_s"] >= 1.0
    assert got["stats_t0"] == {"decode_steps": 0}


def test_a_stop_trace_that_never_returns_is_an_error(tmp_path, monkeypatch):
    monkeypatch.setattr(serve, "STOP_TRACE_LIMIT_S", 0.3)
    _stub_hook(str(tmp_path), None)
    tracing = serve.TraceSlice(str(tmp_path), StubClient(), 0.05, 0.1)
    tracing.start()
    with pytest.raises(harness.BenchError, match=r"stop_trace.*within 0 s.*slice: 0\.1 s.*shorten `trace.seconds`"):
        tracing.result()


def test_a_hook_that_never_starts_is_an_error(tmp_path, monkeypatch):
    monkeypatch.setattr(serve, "START_TRACE_LIMIT_S", 0.2)
    client = StubClient()
    tracing = serve.TraceSlice(str(tmp_path), client, 0.0, 0.1)
    tracing.start()
    with pytest.raises(harness.BenchError, match="did not start within 0 s") as e:
        tracing.result()
    assert "shorten" not in str(e.value) and client.paths == []
    assert os.path.exists(tmp_path / "start") and not os.path.exists(tmp_path / "stop")


def test_a_cut_or_missing_device_trace_is_an_error_on_the_chip(tmp_path, monkeypatch):
    out = tr.reduce_rows(REC)
    ctl = tmp_path / "trace_ctl"
    monkeypatch.setattr(tr, "reduce_dir", lambda *a, **k: out)
    ctl.mkdir()
    assert serve.reduce_trace(str(ctl), str(tmp_path), out["window_s"] / 0.81, True) is out
    assert not ctl.exists()
    with pytest.raises(harness.BenchError, match=(
            rf"cut short.*{out['device_events']:.0f} events.*shorten `trace.seconds`")):
        serve.reduce_trace(str(ctl), str(tmp_path), out["window_s"] / 0.79, True)
    monkeypatch.setattr(tr, "reduce_dir", lambda *a, **k: None)
    with pytest.raises(harness.BenchError, match="no device trace"):
        serve.reduce_trace(str(ctl), str(tmp_path), 8.0, True)
    # the CPU rehearsal has no device plane, and only it is let off
    assert serve.reduce_trace(str(ctl), str(tmp_path), 8.0, False) is None


def test_gaps_are_named_by_the_engines_spans_too():
    rows = {"devices": {"0": [["fusion.1", 0, 400_000], ["fusion.3", 1_000_000, 500_000],
                              ["fusion.4", 2_000_000, 100_000]]},
            "host": [["ray_tpu:engine:decode_wait", 500_000, 600_000],
                     ["ray_tpu:engine:dispatch:decode_step", 650_000, 100_000],
                     ["chipbench:window", 0, 3_000_000]]}
    gaps = dict(tr.reduce_rows(rows)["breakdown"]["idle_gaps"])
    # the innermost span open at each gap's middle (700 us; 1,750 us)
    assert gaps == {"engine:dispatch:decode_step": pytest.approx(600e-6),
                    "window": pytest.approx(500e-6)}
