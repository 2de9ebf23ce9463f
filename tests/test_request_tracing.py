"""End-to-end request tracing + phase attribution for the serve/PD plane.

ISSUE 11 tentpole coverage: zero-emit guard when sampling is off, a sampled
PD request yielding one span tree with named phases across ≥3 processes,
flight-recorder ring bounds, the dashboard /api/requests endpoint, GCS
server-side RPC latency histograms, chrome-trace per-request rows, and the
`ray_tpu trace` CLI.
"""

import json
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu._private import task_events
from ray_tpu.util import tracing


@pytest.fixture
def sampled_cluster(monkeypatch):
    """Serve cluster with every request span-sampled."""
    from ray_tpu._private.ray_config import RayConfig

    monkeypatch.setenv("RAY_TPU_SERVE_SPAN_SAMPLE_EVERY", "1")
    RayConfig.reset()
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=32, num_workers=2, max_workers=16)
    yield
    serve.shutdown()
    ray_tpu.shutdown()
    RayConfig.reset()


@pytest.fixture
def unsampled_cluster(monkeypatch):
    from ray_tpu._private.ray_config import RayConfig

    monkeypatch.setenv("RAY_TPU_SERVE_SPAN_SAMPLE_EVERY", "0")
    RayConfig.reset()
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=32, num_workers=2, max_workers=16)
    yield
    serve.shutdown()
    ray_tpu.shutdown()
    RayConfig.reset()


@serve.deployment
class _Echo:
    def __call__(self, request):
        return {"echo": request["body"],
                "rid": request.get("request_id")}


def _http_post(path: str, body: dict) -> dict:
    host, port = serve.http_address()
    req = urllib.request.Request(
        f"http://{host}:{port}{path}", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as resp:
        return json.loads(resp.read())


def _flat(span, acc):
    acc.append(span)
    for c in span.get("children", ()):
        _flat(c, acc)
    return acc


def _wait_tree(rid, want_names, timeout=30.0):
    """Poll until the trace for `rid` contains every name in want_names
    (`prefix*` = some name with that prefix: each process flushes its
    spans on its own cycle, so they arrive in any order)."""
    deadline = time.time() + timeout
    spans = []
    while time.time() < deadline:
        tree = tracing.get_trace(rid)
        if tree is not None:
            spans = _flat(tree["root"], [])
            names = {s.get("name") or "" for s in spans}
            if all(w in names or (w.endswith("*") and any(
                    n.startswith(w[:-1]) for n in names)) for w in want_names):
                return spans
        time.sleep(0.4)
    raise AssertionError(
        f"trace incomplete after {timeout}s: have "
        f"{sorted(s.get('name') or '?' for s in spans)}, "
        f"want {sorted(want_names)}")


def _gcs_rpc(msg: dict) -> dict:
    from ray_tpu._private.api import _get_worker

    return _get_worker().rpc(msg)


# --------------------------------------------------------------- sampling


def test_sampling_off_zero_serve_spans(unsampled_cluster):
    """The zero-emit guard: with serve_span_sample_every=0 a request
    produces NO serve spans anywhere (local buffer or GCS) and no trace
    context reaches the replica."""
    serve.start(http_port=0)
    serve.run(_Echo.bind(), name="echo", route_prefix="/echo")
    out = _http_post("/echo", {"x": 1})
    assert out["echo"] == {"x": 1}
    assert out["rid"]  # request ids are always assigned, sampling or not
    # give the flushers one full cycle, then check the GCS event log
    time.sleep(2.5)
    events = _gcs_rpc({"type": "task_events"}).get("events", [])
    serve_spans = [e for e in events
                   if e.get("event") == "trace:span" and e.get("request_id")]
    assert serve_spans == []
    assert tracing.get_trace(out["rid"]) is None


def test_sampled_request_span_tree(sampled_cluster):
    """A sampled HTTP request yields one tree: serve:request root, proxy
    phase spans, and the replica's span — ≥2 processes."""
    serve.start(http_port=0)
    serve.run(_Echo.bind(), name="echo", route_prefix="/echo")
    out = _http_post("/echo", {"x": 1})
    rid = out["rid"]
    spans = _wait_tree(rid, {"serve:request", "proxy:route", "proxy:handle",
                             "replica:echo*"})
    # every span in the tree carries the request id (chrome-trace grouping)
    assert all(s.get("request_id") == rid for s in spans
               if s.get("name") != "(root)")
    pids = {s.get("pid") for s in spans if s.get("pid")}
    assert len(pids) >= 2  # proxy actor + replica at minimum
    root = [s for s in spans if s.get("name") == "serve:request"]
    assert root and root[0]["span_kind"] == "root"


def test_sampled_pd_request_span_tree(sampled_cluster):
    """The acceptance bar: one sampled PD request → one trace with ≥6 named
    phases (proxy, route, prefill, kv-transfer, admission, decode) across
    ≥3 processes."""
    from ray_tpu.llm import LLMConfig, ModelLoadingConfig, build_pd_openai_app

    cfg = LLMConfig(
        model_loading_config=ModelLoadingConfig(model_id="tiny",
                                                tokenizer="byte"),
        model_family="llama", accelerator_type=None,
        engine_kwargs=dict(max_slots=2, max_len=128, min_bucket=16,
                           page_size=16))
    serve.start(http_port=0)
    serve.run(build_pd_openai_app(cfg), name="pd", route_prefix="/pd")
    out = _http_post("/pd", {"prompt": "abc", "max_tokens": 6})
    assert out["usage"]["completion_tokens"] == 6
    rows = _wait_requests(lambda r: r.get("component") == "http_proxy"
                          and r.get("path") == "/pd")
    rid = rows[-1]["request_id"]
    want = {"serve:request", "proxy:route", "pd:prefill", "pd:kv_send",
            "pd:kv_transfer", "pd:admission", "pd:decode"}
    spans = _wait_tree(rid, want, timeout=45.0)
    names = {s.get("name") for s in spans}
    assert len(want & names) >= 6
    pids = {s.get("pid") for s in spans if s.get("pid")}
    # proxy actor, PD proxy replica, prefill replica, decode replica
    assert len(pids) >= 3, pids
    # the PD proxy also left a phase-split flight-recorder entry
    pd_rows = _wait_requests(lambda r: r.get("component") == "pd_proxy")
    assert "prefill" in (pd_rows[-1].get("phases") or {})


def _wait_requests(pred, timeout=25.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        rows = [r for r in _gcs_rpc({"type": "list_requests"}).get(
            "requests", []) if pred(r)]
        if rows:
            return rows
        time.sleep(0.4)
    raise AssertionError("no matching flight-recorder rows in the GCS")


# --------------------------------------------------------- flight recorder


def test_flight_recorder_ring_bounds(monkeypatch):
    """The ring keeps the LAST N summaries; drain returns new-since-last
    entries still in the ring, once."""
    from ray_tpu._private.ray_config import RayConfig

    monkeypatch.setenv("RAY_TPU_SERVE_FLIGHT_RECORDER_SIZE", "8")
    RayConfig.reset()
    task_events.reset_request_log()
    try:
        for i in range(20):
            task_events.record_request({"request_id": f"r{i}"})
        ring = task_events.recent_requests()
        assert len(ring) == 8
        assert [r["request_id"] for r in ring] == [f"r{i}" for i in range(12, 20)]
        # drain ships only what the ring retains, exactly once
        drained = task_events.drain_request_log()
        assert [r["request_id"] for r in drained] == [
            f"r{i}" for i in range(12, 20)]
        assert task_events.drain_request_log() == []
        task_events.record_request({"request_id": "r20"})
        assert [r["request_id"] for r in task_events.drain_request_log()] == ["r20"]
    finally:
        task_events.reset_request_log()
        RayConfig.reset()


def test_api_requests_endpoint(sampled_cluster):
    """GET /api/requests on the dashboard returns the GCS request log."""
    from ray_tpu._private import api as _api
    from ray_tpu.dashboard import start_dashboard

    serve.start(http_port=0)
    serve.run(_Echo.bind(), name="echo", route_prefix="/echo")
    out = _http_post("/echo", {"x": 2})
    _wait_requests(lambda r: r.get("request_id") == out["rid"])
    head = start_dashboard(_api._node.session_dir)
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{head.port}/api/requests",
                timeout=30) as resp:
            rows = json.loads(resp.read())
        assert any(r.get("request_id") == out["rid"] for r in rows)
        entry = [r for r in rows if r.get("request_id") == out["rid"]][0]
        assert entry["component"] == "http_proxy"
        assert "handle" in entry.get("phases", {})
        assert entry.get("duration_s", 0) > 0
    finally:
        head.stop()


# ----------------------------------------------------------- GCS rpc stats


def test_gcs_rpc_histograms_present(sampled_cluster):
    """Server-side per-RPC-type latency histograms ride metrics_snapshot
    under the reserved 'gcs' source and render as Prometheus text."""
    from ray_tpu.util.metrics import to_prometheus

    ray_tpu.get(ray_tpu.put(1))  # guarantee some RPC traffic
    snap = _gcs_rpc({"type": "metrics_snapshot"})["metrics"]
    assert "ray_tpu_gcs_rpc_seconds" in snap
    rec = snap["ray_tpu_gcs_rpc_seconds"]
    assert rec["kind"] == "histogram"
    series = rec["series"]["gcs"]
    types = {dict(tuple(t) for t in tags).get("rpc") for tags, _ in series}
    assert "register" in types  # every session registers workers
    assert all(st["count"] > 0 for _, st in series)
    text = to_prometheus(snap)
    assert "ray_tpu_gcs_rpc_seconds_bucket" in text
    assert 'rpc="register"' in text


# ------------------------------------------------------------ chrome trace


def test_chrome_trace_groups_request_rows():
    """Serve/PD request spans group under one row per request id (satellite:
    mirrors the per-dag grouping for DAG step spans)."""
    events = [
        {"event": "trace:span", "name": "serve:request", "start": 1.0,
         "end": 2.0, "request_id": "req1", "pid": 10},
        {"event": "trace:span", "name": "replica:echo", "start": 1.2,
         "end": 1.8, "request_id": "req1", "pid": 11},
        {"event": "trace:span", "name": "serve:request", "start": 1.0,
         "end": 1.5, "request_id": "req2", "pid": 10},
        {"event": "task:done", "name": "other", "start": 1.0, "end": 1.1,
         "pid": 12},
    ]
    trace = json.loads(task_events.to_chrome_trace(events))["traceEvents"]
    rows = {t["name"]: t["pid"] for t in trace}
    assert rows["serve:request"] in ("req:req1", "req:req2")
    by_row: dict = {}
    for t in trace:
        by_row.setdefault(t["pid"], []).append(t["name"])
    assert sorted(by_row["req:req1"]) == ["replica:echo", "serve:request"]
    assert by_row["req:req2"] == ["serve:request"]
    assert "other" in [n for r, ns in by_row.items()
                       if not str(r).startswith("req:") for n in ns]


# -------------------------------------------------------------------- CLI


def test_cli_trace_list_and_show(sampled_cluster, capsys):
    from ray_tpu._private import api as _api
    from ray_tpu.scripts.cli import main as cli_main

    serve.start(http_port=0)
    serve.run(_Echo.bind(), name="echo", route_prefix="/echo")
    out = _http_post("/echo", {"x": 3})
    rid = out["rid"]
    _wait_requests(lambda r: r.get("request_id") == rid)
    _wait_tree(rid, {"serve:request", "proxy:handle"})
    sd = _api._node.session_dir
    cli_main(["--session", sd, "trace", "list"])
    listed = capsys.readouterr().out
    assert rid in listed and "http_proxy" in listed
    cli_main(["--session", sd, "trace", "show", rid])
    shown = capsys.readouterr().out
    assert "serve:request" in shown and "proxy:handle" in shown


# ------------------------------------------------------------ engine phases


def test_engine_phase_histograms(monkeypatch):
    """Always-on engine phases: admission_wait + inter_token observed for a
    plain (non-PD) generation; disabled entirely by serve_metrics=0."""
    import jax
    import jax.numpy as jnp

    from ray_tpu._private.ray_config import RayConfig
    from ray_tpu.llm.engine import SamplingParams, TPUEngine
    from ray_tpu.models import transformer
    from ray_tpu.models.transformer import TransformerConfig
    from ray_tpu.util import metrics as met

    tiny = TransformerConfig(vocab_size=64, d_model=32, n_layers=1,
                             n_heads=2, n_kv_heads=2, d_ff=64,
                             max_seq_len=64, dtype=jnp.float32, remat=False)
    params = transformer.init(jax.random.PRNGKey(0), tiny)

    def totals():
        for m in met.snapshot():
            if m["name"] == "ray_tpu_llm_engine_phase_seconds":
                return {dict(tuple(t) for t in tags)["phase"]: st["count"]
                        for tags, st in m["series"]}
        return {}

    before = totals()
    eng = TPUEngine(tiny, params, max_slots=2, max_len=32, page_size=16)
    try:
        toks = eng.generate([1, 2, 3], SamplingParams(max_tokens=4))
        assert len(toks) == 4
    finally:
        eng.shutdown()
    after = totals()
    assert after.get("admission_wait", 0) > before.get("admission_wait", 0)
    assert after.get("inter_token", 0) > before.get("inter_token", 0)

    # kill switch: a fresh engine under serve_metrics=0 observes nothing
    monkeypatch.setenv("RAY_TPU_SERVE_METRICS", "0")
    RayConfig.reset()
    try:
        base = totals()
        eng2 = TPUEngine(tiny, params, max_slots=2, max_len=32, page_size=16)
        try:
            eng2.generate([1, 2, 3], SamplingParams(max_tokens=4))
        finally:
            eng2.shutdown()
        assert totals() == base
    finally:
        RayConfig.reset()


def test_decode_step_histogram_is_labelled_with_the_kind_of_pass():
    """`ray_tpu_llm_decode_step_seconds{pass}`: one observation a decode step,
    under what the step's pass put on the device before it (`step_prefill`:
    the prompt's prefill went out ahead of the first step), the counts
    `stats()["loop"]["passes"]` keeps. Which attention code runs stays in
    `stats()["decode_attn"]`."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm.engine import PASS_KINDS, SamplingParams, TPUEngine
    from ray_tpu.models import transformer
    from ray_tpu.models.transformer import TransformerConfig
    from ray_tpu.util import metrics as met

    tiny = TransformerConfig(vocab_size=64, d_model=32, n_layers=1,
                             n_heads=2, n_kv_heads=2, d_ff=64,
                             max_seq_len=64, dtype=jnp.float32, remat=False)

    def counts():
        for m in met.snapshot():
            if m["name"] == "ray_tpu_llm_decode_step_seconds":
                return {dict(tuple(t) for t in tags)["pass"]: st["count"]
                        for tags, st in m["series"]}
        return {}

    before = counts()
    eng = TPUEngine(tiny, transformer.init(jax.random.PRNGKey(0), tiny),
                    max_slots=2, max_len=64)
    try:
        eng.generate([1, 2, 3], SamplingParams(max_tokens=5))
        st = eng.stats()
    finally:
        eng.shutdown()
    after = counts()
    assert st["decode_attn"] == "ragged_reference" and set(after) == set(PASS_KINDS)
    seen = {k: after[k] - before.get(k, 0) for k in PASS_KINDS}
    assert seen == {"step": 3, "step_prefill": 1} and st["decode_steps"] == 4
    assert seen == {k: row["count"] for k, row in st["loop"]["passes"].items()}


# ------------------------------------------------- engine spans (ISSUE 24)


def _tiny_llm_app():
    from ray_tpu.llm import LLMConfig, ModelLoadingConfig, build_openai_app

    cfg = LLMConfig(
        model_loading_config=ModelLoadingConfig(model_id="tiny",
                                                tokenizer="byte"),
        model_family="llama", accelerator_type=None,
        engine_kwargs=dict(max_slots=2, max_len=128, min_bucket=16,
                           page_size=16,
                           enable_prefix_cache=True, prefill_chunk=16))
    serve.start(http_port=0)
    serve.run(build_openai_app(cfg), name="llm", route_prefix="/v1")


def _http_stream(path: str, body: dict) -> list:
    host, port = serve.http_address()
    req = urllib.request.Request(
        f"http://{host}:{port}{path}",
        data=json.dumps({**body, "stream": True}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as resp:
        return [json.loads(line[6:]) for line in resp.read().splitlines()
                if line.startswith(b"data: {")]


@pytest.mark.parametrize("streamed", [False, True], ids=["unary", "sse"])
def test_sampled_request_reaches_the_engine(sampled_cluster, streamed, capsys):
    """One sampled request through build_openai_app → one tree whose leaves
    include the engine's queue wait, prefill and decode, in that order and
    inside the serve:request root; a prompt of 41 tokens (BOS) at prefill_chunk 16
    is staged, so the prefill span counts three chunks."""
    _tiny_llm_app()
    body = {"prompt": "a" * 40, "max_tokens": 5}
    if streamed:
        chunks = _http_stream("/v1/completions", body)
        assert sum(len(c["choices"][0].get("token_ids", [])) for c in chunks) == 5
    else:
        out = _http_post("/v1/completions", body)
        assert out["usage"]["completion_tokens"] == 5
    rows = _wait_requests(lambda r: r.get("component") == "http_proxy"
                          and r.get("path") == "/v1/completions")
    rid = rows[-1]["request_id"]
    want = {"serve:request", "engine:queue_wait", "engine:prefill",
            "engine:decode"}
    spans = _wait_tree(rid, want, timeout=45.0)
    by_name = {s["name"]: s for s in spans if s.get("name") in want}
    root = by_name["serve:request"]
    queue, prefill, decode = (by_name["engine:" + n]
                              for n in ("queue_wait", "prefill", "decode"))
    assert not (queue["children"] or prefill["children"] or decode["children"])
    assert (root["start"] <= queue["start"] <= queue["end"] == prefill["start"]
            <= prefill["end"] == decode["start"] <= decode["end"]
            <= root["end"] + 0.05)
    assert prefill["prompt_tokens"] == 41 and prefill["chunks"] == 3
    assert decode["tokens"] == 5
    assert sum(1 for s in spans if s.get("name") == "engine:decode") == 1
    assert all(s.get("request_id") == rid for s in spans
               if (s.get("name") or "").startswith("engine:"))
    # and `ray_tpu trace show <request_id>` prints them under the root
    from ray_tpu._private import api as _api
    from ray_tpu.scripts.cli import main as cli_main

    cli_main(["--session", _api._node.session_dir, "trace", "show", rid])
    shown = capsys.readouterr().out
    assert shown.index("serve:request") < shown.index("engine:queue_wait") \
        < shown.index("engine:prefill") < shown.index("engine:decode")


def test_unsampled_request_emits_no_engine_span(unsampled_cluster):
    _tiny_llm_app()
    out = _http_post("/v1/completions", {"prompt": "abc", "max_tokens": 4})
    assert out["usage"]["completion_tokens"] == 4
    stats = _http_post("/v1/stats", {})
    assert stats["loop"]["requests"]["first_tokens"] >= 1
    time.sleep(2.5)  # one full flusher cycle
    events = _gcs_rpc({"type": "task_events"}).get("events", [])
    assert [e for e in events if e.get("event") == "trace:span"
            and (e.get("name") or "").startswith("engine:")] == []
