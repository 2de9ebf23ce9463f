"""The engine loop on the record (ISSUE 24): the scheduler thread's phase
clock, the per-request sums, `stats()["loop"]`, the `ray_tpu:engine:*`
annotations on the JAX profiler's timeline, the compile log, and the
benchmark reader that turns two `stats()` readings into per-layer metrics.
All on the CPU at tiny widths: no number here is a device number."""

import collections
import functools
import glob
import json
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu._private import accelerators
from ray_tpu.llm import engine as engine_mod
from ray_tpu.llm.engine import (HOST_PHASES, LOOP_PHASES, PASS_KINDS,
                                SamplingParams, TPUEngine)
from ray_tpu.models import transformer
from ray_tpu.models.transformer import TransformerConfig
from ray_tpu.util import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WAITS = ("admit_wait", "prefill_wait", "decode_wait")


@pytest.fixture(scope="module")
def tiny_model():
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=1, n_heads=2,
                            n_kv_heads=2, d_ff=64, max_seq_len=128,
                            dtype=jnp.float32, remat=False)
    return cfg, transformer.init(jax.random.PRNGKey(0), cfg)


def _engine(tiny_model, **kw):
    cfg, params = tiny_model
    opts = dict(max_slots=4, max_len=128, min_bucket=16, page_size=16,
                enable_prefix_cache=True, prefill_chunk=16)
    opts.update(kw)
    return TPUEngine(cfg, params, **opts)


def _prompt(i: int, n: int) -> list:
    return [1 + (i * 7 + j * 3) % 60 for j in range(n)]


def _burst(eng, n_requests: int, prompt_len: int, max_tokens: int) -> list:
    reqs = [eng.submit(_prompt(i, prompt_len), SamplingParams(max_tokens=max_tokens))
            for i in range(n_requests)]
    return [list(r) for r in reqs]


def _quiet_stats(eng) -> dict:
    """A reading with the loop parked: nothing active, nothing queued, no
    step in flight still to be read (a dropped token's is read last)."""
    deadline = time.time() + 30.0
    while time.time() < deadline:
        st = eng.stats()
        if not (st["active"] or st["waiting"] or st.get("prefilling")
                ) and eng._clock.phase == "parked":
            return eng.stats()
        time.sleep(0.01)
    raise AssertionError("the engine did not go quiet")


def test_phases_cover_the_thread(tiny_model):
    """Every second of the scheduler thread is in exactly one phase: the
    phase seconds add up to the thread's wall time between two readings, on
    a paged engine with chunked prefill under a burst of requests."""
    eng = _engine(tiny_model)
    try:
        _burst(eng, 2, 20, 3)  # compile everything outside the readings
        s0 = _quiet_stats(eng)
        outs = _burst(eng, 8, 40, 6)  # 40 > chunk 16: staged, three chunks
        s1 = _quiet_stats(eng)
    finally:
        eng.shutdown()
    assert all(len(o) == 6 for o in outs)
    l0, l1 = s0["loop"], s1["loop"]
    assert tuple(l1["seconds"]) == LOOP_PHASES
    delta = {p: l1["seconds"][p] - l0["seconds"][p] for p in LOOP_PHASES}
    thread = l1["thread_s"] - l0["thread_s"]
    assert thread > 0
    assert sum(delta.values()) == pytest.approx(thread, rel=0.01)
    assert all(d >= 0 for d in delta.values())
    assert s1["decode_steps"] > s0["decode_steps"]
    # eight staged prompts (two or three chunks each: the prefix cache may
    # serve a first block), one first-token fetch each
    chunks = s1["prefill_chunks_run"] - s0["prefill_chunks_run"]
    assert 16 <= chunks <= 24
    assert delta["streams"] == 0
    for busy in ("sweep", "admit", "prefill", "prefill_wait", "decode",
                 "decode_wait", "emit"):
        assert delta[busy] > 0, busy
    r0, r1 = l0["requests"], l1["requests"]
    assert r1["requests_scheduled"] - r0["requests_scheduled"] == 8
    assert r1["first_tokens"] - r0["first_tokens"] == 8


def test_snapshot_is_consistent_while_the_loop_runs(tiny_model):
    """Readings taken from another thread while the loop marks boundaries
    never run backwards, and each is short of the thread's time by at most
    the one phase interval whose boundary fell inside the copy."""
    eng = _engine(tiny_model)
    readings = []
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)  # threads change places mid-boundary
    try:
        reqs = [eng.submit(_prompt(i, 12), SamplingParams(max_tokens=24))
                for i in range(4)]
        while len(readings) < 200:
            readings.append(eng.stats()["loop"])
        for r in reqs:
            list(r)
    finally:
        sys.setswitchinterval(switch)
        eng.shutdown()
    for a, b in zip(readings, readings[1:]):
        assert -0.5 < sum(a["seconds"].values()) - a["thread_s"] < 1e-6
        assert b["thread_s"] >= a["thread_s"]
        assert all(b["seconds"][p] >= a["seconds"][p] - 1e-9 for p in LOOP_PHASES)
        assert b["host_cpu_s"] >= a["host_cpu_s"]
        assert b["dispatch_s"] >= a["dispatch_s"]
        assert all(b["passes"][k]["count"] >= a["passes"][k]["count"]
                   for k in PASS_KINDS)


def test_queue_wait_and_prefill_split_the_admission_wait(tiny_model):
    """submit → bind (what `admission_wait` observes) is queue wait plus
    prefill: the two new sums cover it, and exceed it only by the fetch of
    the first token, which follows the bind."""
    eng = _engine(tiny_model)
    try:
        _burst(eng, 2, 40, 3)
        _quiet_stats(eng)
        admit = eng._phase_admit._st  # the bound series: {"sum", "count", ...}
        a0 = (admit["sum"], admit["count"])
        s0 = eng.stats()["loop"]
        _burst(eng, 6, 40, 4)
        s1 = _quiet_stats(eng)["loop"]
        a1 = (admit["sum"], admit["count"])
    finally:
        eng.shutdown()
    assert a1[1] - a0[1] == 6
    observed = a1[0] - a0[0]
    r0, r1 = s0["requests"], s1["requests"]
    split = (r1["queue_wait_s"] - r0["queue_wait_s"]
             + r1["prefill_s"] - r0["prefill_s"])
    fetch = sum(s1["seconds"][p] - s0["seconds"][p]
                for p in ("admit_wait", "prefill_wait"))
    assert observed <= split + 1e-6
    # per request at most the fetches of the burst and a little host work
    assert split - observed <= fetch + 6 * 0.05
    # the histogram carries the two new labels beside the old one
    from ray_tpu.util import metrics as met

    phases = {dict(tuple(t) for t in tags)["phase"]: st["count"]
              for m in met.snapshot()
              if m["name"] == "ray_tpu_llm_engine_phase_seconds"
              for tags, st in m["series"]}
    assert phases["queue_wait"] >= 6 and phases["prefill"] >= 6


def test_one_slot_makes_the_third_request_wait_for_two_prefills(tiny_model):
    """With one slot and three long prompts the third request's queue wait
    holds the first's whole prefill (and its decode)."""
    eng = _engine(tiny_model, max_slots=1, max_len=128)
    try:
        _burst(eng, 1, 100, 2)  # compile
        _quiet_stats(eng)
        reqs = [eng.submit(_prompt(i + 1, 100), SamplingParams(max_tokens=2))
                for i in range(3)]
        for r in reqs:
            list(r)
    finally:
        eng.shutdown()
    first, _, third = reqs
    for r in reqs:
        assert (r.submitted_ts <= r.scheduled_ts <= r.first_token_ts
                and r.scheduled_ts <= r.admitted_ts)
        assert r.pf_chunks == 7  # 100 tokens in chunks of 16
    assert (third.scheduled_ts - third.submitted_ts
            > first.first_token_ts - first.scheduled_ts)
    assert third.scheduled_ts >= first.first_token_ts


@pytest.mark.parametrize("kind", ["dense", "experts", "experts_sorting_from_16_tokens"])
def test_experts_block_counts_the_tokens_of_each_dispatch(tiny_model, monkeypatch, kind):
    """`stats()["experts"]`: the padded tokens of every prefill call and decode
    step, by `ops.sorted_pays` of the call; a dense model counts neither."""
    import dataclasses

    from ray_tpu import ops
    from ray_tpu.models.transformer import MoEConfig

    cfg, params = tiny_model
    if kind != "dense":
        cfg = dataclasses.replace(cfg, moe=MoEConfig(num_experts=4, top_k=2,
                                                     capacity_factor=None))
        params = transformer.init(jax.random.PRNGKey(0), cfg)
    if kind == "experts_sorting_from_16_tokens":
        monkeypatch.setattr(ops.moe, "SORTED_MIN_TOKENS", 16)
    eng = _engine((cfg, params))
    try:
        out = list(eng.submit(_prompt(0, 40), SamplingParams(max_tokens=4)))
        st = _quiet_stats(eng)
    finally:
        eng.shutdown()
    assert len(out) == 4
    # three chunks of 16 (40 tokens), then three decode steps of 4 slots
    prefill, decode = 3 * 16, st["decode_steps"] * 4
    assert st["decode_steps"] == 3
    assert st["experts"] == {
        "dense": {"tokens_sorted": 0, "tokens_onehot": 0},
        "experts": {"tokens_sorted": 0, "tokens_onehot": prefill + decode},
        "experts_sorting_from_16_tokens": {"tokens_sorted": prefill, "tokens_onehot": decode},
    }[kind]


def test_stats_stay_json_plain(tiny_model):
    eng = _engine(tiny_model)
    try:
        _burst(eng, 2, 20, 3)
        st = _quiet_stats(eng)
    finally:
        eng.shutdown()
    back = json.loads(json.dumps(st))
    assert back["loop"] == st["loop"]
    assert set(back["loop"]) == {
        "seconds", "host_s", "active_s", "thread_s", "requests", "steps_ahead",
        "tokens_discarded", "host_cpu_s", "dispatch_s", "work_s", "dispatch",
        "passes"}
    # two numbers a program the engine can dispatch (17), four a kind of pass
    assert len(json.dumps(back["loop"])) < 2500
    assert tuple(back["loop"]["passes"]) == PASS_KINDS
    for row in back["loop"]["passes"].values():
        assert set(row) == {"count", "seconds", "rows", "prompt_tokens"}
    for row in back["loop"]["dispatch"].values():
        assert set(row) == {"calls", "seconds"}
    assert set(back["loop"]["requests"]) == {
        "requests_scheduled", "queue_wait_s", "first_tokens", "prefill_s"}
    # the compile table rides along: a row a program, plain numbers
    programs = back["compile_cache"]["programs"]
    assert programs == st["compile_cache"]["programs"] and programs
    for row in programs.values():
        assert set(row) == {"traces", "trace_s", "lowers", "lower_s", "compiles",
                            "backend_s", "hits", "misses", "last"}
    assert set(back["compile_cache"]["seconds"]) == {
        "trace", "lower", "backend", "retrieval"}
    assert back["setup"] is None  # not from_config's engine


def test_the_plain_admission_keeps_the_clock(tiny_model):
    """Without a prefix cache or chunks a prompt is prefilled whole by
    `_admit`: its first token's fetch is `admit_wait`."""
    cfg, params = tiny_model
    eng = TPUEngine(cfg, params, max_slots=2, max_len=64)
    try:
        assert len(eng.generate([1, 2, 3, 1, 2, 3], SamplingParams(max_tokens=6))) == 6
        loop = _quiet_stats(eng)["loop"]
    finally:
        eng.shutdown()
    assert loop["seconds"]["admit_wait"] > 0 and loop["seconds"]["decode_wait"] > 0
    assert loop["seconds"]["decode"] > 0 and loop["seconds"]["prefill"] == 0
    # scheduled as of before the prefill's dispatch, once it is inserted
    assert loop["requests"]["requests_scheduled"] == 1
    assert 0 < loop["requests"]["prefill_s"] >= loop["seconds"]["admit_wait"]


def test_compile_log_names_the_program_and_the_phase(tiny_model):
    """`compile_cache_counts()["programs"]`: every program lowered, under
    the name JAX gives it, with the thread of its last compilation and what
    that thread was doing (the engine thread: the loop phase that was
    open). Which programs compiled in a window: the rows whose counts moved
    between two readings."""
    before = accelerators.compile_cache_counts()["programs"]
    eng = _engine(tiny_model, page_size=32, min_bucket=32, prefill_chunk=32)
    try:
        _burst(eng, 1, 40, 3)  # shapes no earlier test compiled
        after = eng.stats()["compile_cache"]["programs"]
    finally:
        eng.shutdown()
    moved = {name: row for name, row in after.items()
             if row["compiles"] > before.get(name, {"compiles": 0})["compiles"]}
    assert moved and all(name.startswith(("jit(", "pjit(")) for name in moved)
    for row in moved.values():
        assert set(row["last"]) == {"t", "thread", "phase"}
        assert row["lowers"] >= row["compiles"] >= 1 and row["backend_s"] > 0
    mine = [row["last"] for row in moved.values()
            if row["last"]["thread"] == "tpu-engine"]
    assert mine and all(e["phase"] in LOOP_PHASES for e in mine)
    assert any(e["phase"] in ("decode", "prefill", "admit") for e in mine)

    done = threading.Event()

    def elsewhere():
        jax.jit(lambda x: x * 3 + 1)(jnp.ones((7, 3)))
        done.set()

    t = threading.Thread(target=elsewhere, name="not-the-engine")
    t.start()
    t.join()
    assert done.is_set()
    last = max((row["last"] for row in accelerators.compile_cache_counts()[
        "programs"].values() if row["last"]), key=lambda e: e["t"])
    assert last["thread"] == "not-the-engine" and last["phase"] is None


def test_device_annotation_is_the_one_prefix():
    span = tracing.device_annotation("engine:decode")()
    with span:
        pass
    assert span.__class__.__name__ == "TraceAnnotation"
    assert not hasattr(tracing, "request_trace")  # the unused form is gone
    src = open(engine_mod.__file__).read()
    assert '"ray_tpu:' not in src and "'ray_tpu:" not in src


def test_engine_phases_are_on_the_profiler_timeline(tiny_model, tmp_path):
    """A `jax.profiler` trace taken around a few steps carries the loop's
    phases as `ray_tpu:engine:*` events, all on the scheduler thread's line,
    and every dispatch as a `ray_tpu:engine:dispatch:*` event inside one."""
    from jax.profiler import ProfileData

    eng = _engine(tiny_model)
    try:
        _burst(eng, 1, 20, 2)
        _quiet_stats(eng)
        jax.profiler.start_trace(str(tmp_path))
        try:
            _burst(eng, 2, 20, 5)
            _quiet_stats(eng)
        finally:
            jax.profiler.stop_trace()
    finally:
        eng.shutdown()
    found = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    assert found
    lines = []
    for plane in ProfileData.from_file(found[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            mine = [(e.name, e.start_ns, e.end_ns) for e in line.events
                    if e.name.startswith("ray_tpu:")]
            if mine:
                lines.append(mine)
    if not lines:
        pytest.skip("this JAX writes no TraceMe events to the host plane on CPU")
    # one thread marks the phases, so they are on one line (which the
    # profiler names after the OS thread, `python` before Python 3.14)
    (events,) = lines
    names = [n for n, _, _ in events]
    assert names.count("ray_tpu:engine:decode") >= 4
    assert {"ray_tpu:engine:decode_wait", "ray_tpu:engine:emit",
            "ray_tpu:engine:admit", "ray_tpu:engine:sweep"} <= set(names)
    head = "ray_tpu:engine:dispatch:"
    phases = [e for e in events if not e[0].startswith(head)]
    calls = [e for e in events if e[0].startswith(head)]
    assert {n[len("ray_tpu:engine:"):] for n, _, _ in phases} <= set(LOOP_PHASES)
    # every dispatch is a span of its own, nested in the span of a host phase
    # (a wait dispatches nothing), and no two dispatches overlap
    assert {head + p for p in ("decode_step", "split", "sample", "commit",
                               "h2d", "prefill", "write_pages", "sample_first",
                               "activate", "bind", "release")} <= set(names)
    assert names.count(head + "decode_step") == names.count("ray_tpu:engine:decode")
    for name, start, end in calls:
        around = [p for p, s, e in phases if s <= start and end <= e]
        assert len(around) == 1, (name, around)
        assert around[0][len("ray_tpu:engine:"):] in HOST_PHASES, (name, around)
    calls.sort(key=lambda e: e[1])
    assert all(a[2] <= b[1] for a, b in zip(calls, calls[1:]))


# ------------------- dispatch seconds, CPU seconds, passes: inside the phases


def _delta(a: dict, b: dict) -> dict:
    """b - a, leaf by leaf, over two readings of one nested counter block (a
    key the first reading lacks counts from 0: a program's first dispatch)."""
    return {k: _delta(a.get(k, {}), v) if isinstance(v, dict) else v - a.get(k, 0)
            for k, v in b.items()}


def test_dispatch_and_cpu_seconds_lie_inside_the_host_phases(tiny_model):
    """With the three additions in, the phases still add up to the thread's
    time; the dispatches' seconds are a part of the host phases' and `work_s`
    the rest; the thread is on the CPU in its host phases for no longer than
    they lasted."""
    eng = _engine(tiny_model)
    try:
        _burst(eng, 2, 20, 3)
        s0 = _quiet_stats(eng)["loop"]
        _burst(eng, 8, 40, 6)
        s1 = _quiet_stats(eng)["loop"]
    finally:
        eng.shutdown()
    d = _delta(s0, s1)
    assert sum(d["seconds"].values()) == pytest.approx(d["thread_s"], rel=0.01)
    assert 0 < d["dispatch_s"] <= d["host_s"]
    assert d["work_s"] == pytest.approx(d["host_s"] - d["dispatch_s"])
    assert d["work_s"] > 0
    assert d["dispatch_s"] == pytest.approx(
        sum(row["seconds"] for row in d["dispatch"].values()))
    assert all(0 < row["seconds"] and 0 < row["calls"]
               for row in s1["dispatch"].values())
    # the two clocks are read one after the other where a host stretch ends
    assert 0 < d["host_cpu_s"] <= d["host_s"] + 0.005


def test_the_cpu_clock_is_read_where_the_thread_enters_or_leaves_the_host(monkeypatch):
    """`thread_time` is a system call: a boundary between two host phases, or
    between a wait and parking, reads `perf_counter` alone; a host stretch
    costs two reads and books what the thread's CPU clock moved by."""
    cpu = iter(range(100, 200))
    reads = []
    monkeypatch.setattr(engine_mod.time, "thread_time",
                        lambda: reads.append(next(cpu)) or reads[-1])
    clock = engine_mod._PhaseClock()
    for phase in ("sweep", "admit", "streams", "prefill", "decode"):
        clock.mark(phase)
    assert reads == [100] and clock.snapshot()["host_cpu_s"] == 0
    clock.mark("decode_wait")
    assert reads == [100, 101] and clock.snapshot()["host_cpu_s"] == 1
    clock.mark("emit")
    clock.mark("sweep")
    clock.mark("admit_wait")
    clock.mark("parked")
    assert reads == [100, 101, 102, 103] and clock.snapshot()["host_cpu_s"] == 2


def test_a_greedy_run_books_four_dispatches_a_decode_step(tiny_model):
    """N decode steps are N calls each of `decode_step`, `sample` and
    `commit`, and N of `split` beside the one of every admission; no bias is
    uploaded for unguided rows; every step read is a pass of kind `step`
    unless a prefill went out before it."""
    eng = _plain(tiny_model)
    try:
        eng.generate(_prompt(0, 12), SamplingParams(max_tokens=3))
        s0 = _quiet_stats(eng)
        outs = [list(r) for r in [
            eng.submit(_prompt(i, 12 + i), SamplingParams(max_tokens=20 - 5 * i))
            for i in range(3)]]
        s1 = _quiet_stats(eng)
    finally:
        eng.shutdown()
    assert [len(o) for o in outs] == [20, 15, 10]
    steps = s1["decode_steps"] - s0["decode_steps"]
    d = _delta(s0["loop"], s1["loop"])
    assert steps >= 19
    for program in ("decode_step", "sample", "commit"):
        assert d["dispatch"][program]["calls"] == steps, program
    assert d["dispatch"]["split"]["calls"] == steps + 3
    for program in ("prefill", "sample_first", "insert", "bind", "release"):
        assert d["dispatch"][program]["calls"] == 3, program
    assert "bias" not in s1["loop"]["dispatch"]
    passes = d["passes"]
    assert passes["step"]["count"] + passes["step_prefill"]["count"] == steps
    # three unstaged prompts of bucket 16 went out before one, two or three steps
    assert 1 <= passes["step_prefill"]["count"] <= 3
    assert passes["step_prefill"]["prompt_tokens"] == 3 * 16
    assert passes["step"]["prompt_tokens"] == 0
    assert passes["step"]["rows"] + passes["step_prefill"]["rows"] == 19 + 14 + 9
    assert all(passes[k]["seconds"] > 0 for k in PASS_KINDS)
    # what the histogram is given is what the passes book
    from ray_tpu.util import metrics as met

    counted = {dict(tuple(t) for t in tags)["pass"]: st for m in met.snapshot()
               if m["name"] == "ray_tpu_llm_decode_step_seconds"
               for tags, st in m["series"]}
    assert set(counted) <= set(PASS_KINDS) and counted["step"]["count"] >= passes["step"]["count"]


def test_a_chunked_prompt_books_its_padded_tokens_to_step_prefill_passes(tiny_model):
    """Every chunk goes out ahead of a decode step of its pass (or, with no
    row to step yet, of the first step after it): the `step_prefill` passes'
    prompt tokens are the padded tokens of the chunks dispatched, and once
    everything is read the passes are the decode steps."""
    eng = _engine(tiny_model)
    try:
        _burst(eng, 2, 20, 3)
        s0 = _quiet_stats(eng)
        slot_steps0 = eng.decode_slot_steps
        outs = _burst(eng, 6, 40, 8)  # three chunks each, bucket 16 every one
        s1 = _quiet_stats(eng)
        slot_steps1 = eng.decode_slot_steps
    finally:
        eng.shutdown()
    assert all(len(o) == 8 for o in outs)
    d = _delta(s0["loop"], s1["loop"])
    chunks = s1["prefill_chunks_run"] - s0["prefill_chunks_run"]
    passes = d["passes"]
    assert chunks >= 12
    assert passes["step_prefill"]["prompt_tokens"] == 16 * chunks
    assert passes["step"]["prompt_tokens"] == 0
    assert 0 < passes["step_prefill"]["count"] <= chunks
    assert (passes["step"]["count"] + passes["step_prefill"]["count"]
            == s1["decode_steps"] - s0["decode_steps"])
    assert (passes["step"]["rows"] + passes["step_prefill"]["rows"]
            == slot_steps1 - slot_steps0)
    assert d["dispatch"]["write_pages"]["calls"] == chunks
    assert (d["dispatch"]["prefill"]["calls"]
            + d["dispatch"]["prefill_with_prefix"]["calls"]) == chunks
    assert d["dispatch"]["gather_prefix"]["calls"] == d["dispatch"][
        "prefill_with_prefix"]["calls"]
    assert d["dispatch"]["activate"]["calls"] == 6


def test_a_guided_row_still_counts_its_passes(tiny_model):
    """Depth 0 (a guided row lives): every step is read in the pass that
    dispatched it and booked all the same, with its mask's upload a dispatch
    of its own."""
    from ray_tpu.llm.guided import GuidedFSM

    eng = _plain(tiny_model)
    try:
        eng.generate(_prompt(1, 13), SamplingParams(max_tokens=3))
        s0 = _quiet_stats(eng)
        allow_all = GuidedFSM(masks=np.ones((1, 64), bool),
                              trans=np.zeros((1, 64), np.int32))
        out = eng.generate(_prompt(1, 13), SamplingParams(max_tokens=24,
                                                          guided=allow_all))
        s1 = _quiet_stats(eng)
    finally:
        eng.shutdown()
    assert len(out) == 24 and s1["decode_steps"] - s0["decode_steps"] == 23
    d = _delta(s0["loop"], s1["loop"])
    assert d["steps_ahead"] == 0
    assert d["passes"]["step"]["count"] + d["passes"]["step_prefill"]["count"] == 23
    assert d["passes"]["step_prefill"]["count"] == 1  # its own prefill's pass
    assert d["dispatch"]["bias"]["calls"] == 23 + 1  # the first token's too


# ------------------------------- the sampler's form follows the live rows


def _forms_recorded(monkeypatch) -> list:
    """Every decode step's static sampler arguments, in order."""
    forms = []
    real = engine_mod.decoding.sample_per_row

    def recording(logits, key, temperatures, top_ks, sampling, k_bucket):
        forms.append((sampling, k_bucket))
        return real(logits, key, temperatures, top_ks, sampling, k_bucket)

    monkeypatch.setattr(engine_mod.decoding, "sample_per_row", recording)
    return forms


def _runs(forms: list) -> list:
    return [f for i, f in enumerate(forms) if i == 0 or f != forms[i - 1]]


def _sampler_adds_up(st: dict, forms: list) -> None:
    assert sum(st["sampler"].values()) == st["decode_steps"] == len(forms)
    assert st["sampler"] == {
        "steps_argmax": sum(not s for s, _ in forms),
        "steps_categorical": sum(s and not k for s, k in forms),
        "steps_top_k": sum(s and k > 0 for s, k in forms)}


def test_greedy_traffic_takes_the_argmax_form_only(tiny_model, monkeypatch):
    forms = _forms_recorded(monkeypatch)
    eng = _engine(tiny_model)
    try:
        _burst(eng, 6, 20, 8)
        st = _quiet_stats(eng)
    finally:
        eng.shutdown()
    assert st["decode_steps"] > 0
    assert st["sampler"] == {"steps_argmax": st["decode_steps"],
                             "steps_categorical": 0, "steps_top_k": 0}
    assert set(forms) == {(False, 0)}
    _sampler_adds_up(st, forms)


def test_a_sampling_row_moves_the_form_while_it_lives(tiny_model, monkeypatch):
    """A request with a temperature and `top_k` 5 admitted beside two greedy
    rows moves the step to the `top_k` form at bucket 8; its neighbours'
    tokens are a greedy-only run's; once it is released the step is an
    argmax again, although its slot's entries on the device still ask."""
    eng = _engine(tiny_model)
    try:
        alone = _burst(eng, 2, 12, 40)
    finally:
        eng.shutdown()
    forms = _forms_recorded(monkeypatch)
    eng = _engine(tiny_model)
    try:
        greedy = [eng.submit(_prompt(i, 12), SamplingParams(max_tokens=40))
                  for i in range(2)]
        streams = [iter(r) for r in greedy]
        heads = [[next(s) for _ in range(3)] for s in streams]
        sampled = list(eng.submit(_prompt(3, 12), SamplingParams(
            max_tokens=6, temperature=0.8, top_k=5)))
        outs = [h + list(s) for h, s in zip(heads, streams)]
        st = _quiet_stats(eng)
        stale = (jax.device_get(eng._temps), jax.device_get(eng._topks))
        form = eng._sampler_form
    finally:
        eng.shutdown()
    assert outs == alone and len(sampled) == 6
    assert _runs(forms) == [(False, 0), (True, 8), (False, 0)]
    assert st["sampler"]["steps_top_k"] >= 5  # its first token is the prefill's
    _sampler_adds_up(st, forms)
    assert form == (False, 0, "steps_argmax")
    assert stale[0].max() == pytest.approx(0.8) and stale[1].max() == 5


def test_the_bucket_falls_when_the_largest_top_k_leaves(tiny_model, monkeypatch):
    """Two sampling rows of `top_k` 5 and 40 beside a greedy one: bucket 64
    while both live, 8 once the second has left, an argmax once both have;
    a sampling row without a `top_k` takes the form that cuts nothing."""
    forms = _forms_recorded(monkeypatch)
    eng = _engine(tiny_model)
    try:
        greedy = iter(eng.submit(_prompt(0, 12), SamplingParams(max_tokens=60)))
        head = [next(greedy) for _ in range(2)]
        five = eng.submit(_prompt(1, 12), SamplingParams(
            max_tokens=30, temperature=0.8, top_k=5))
        forty = eng.submit(_prompt(2, 12), SamplingParams(
            max_tokens=4, temperature=0.8, top_k=40))
        assert len(list(forty)) == 4 and len(list(five)) == 30
        assert len(head + list(greedy)) == 60
        st = _quiet_stats(eng)
        _sampler_adds_up(st, forms)
        assert _runs(forms)[-3:] == [(True, 64), (True, 8), (False, 0)]
        assert not eng._live_top_ks and not eng._live_sampling
        before = len(forms)
        list(eng.submit(_prompt(4, 12), SamplingParams(max_tokens=5, temperature=1.0)))
        st = _quiet_stats(eng)
    finally:
        eng.shutdown()
    assert set(forms[before:]) == {(True, 0)}
    assert st["sampler"]["steps_categorical"] == len(forms) - before >= 4
    _sampler_adds_up(st, forms)


@pytest.mark.parametrize("temperature,top_k,pinned", [
    (0.8, 5, [47, 46, 36, 53, 12, 23, 36, 47, 8, 5, 55, 1]),
    (1.1, 0, [9, 21, 33, 6, 52, 23, 61, 33, 18, 3, 22, 1]),
    (0.9, 40, [9, 21, 33, 6, 52, 50, 61, 33, 18, 47, 22, 1])])
def test_a_seeded_sampled_request_draws_the_sorted_samplers_tokens(
        tiny_model, temperature, top_k, pinned):
    """`pinned` are the tokens of the tree before ISSUE 34 (a6c2c80), whose
    sampler sorted the vocabulary, for this engine, seed and request: the
    key is split once a step in every form, and the cut is the same."""
    eng = _engine(tiny_model, seed=0)
    try:
        out = list(eng.submit(_prompt(3, 12), SamplingParams(
            max_tokens=12, temperature=temperature, top_k=top_k)))
    finally:
        eng.shutdown()
    assert out == pinned


# ------------------------------------------------ one decode step in flight


def _plain(tiny_model, **kw):
    """No prefix cache, no chunks: a prompt is prefilled whole by `_admit`."""
    cfg, params = tiny_model
    return TPUEngine(cfg, params, **{**dict(max_slots=4, max_len=128, min_bucket=16,
                                            page_size=16), **kw})


def _alone(eng, prompts: list, params: list) -> list:
    """Each request served with nothing else in the engine."""
    return [eng.generate(p, sp) for p, sp in zip(prompts, params)]


def _first_new_token(tokens: list, start: int = 3) -> int:
    """The first position from `start` whose token the stream has not shown
    before: a stop token that hits there and nowhere earlier."""
    return next(i for i in range(start, len(tokens)) if tokens[i] not in tokens[:i])


def test_a_batch_with_a_stop_token_delivers_what_each_request_gets_alone(tiny_model):
    """Mixed `max_tokens` and a stop token that hits mid-stream, all rows in
    one batch with a step in flight: token for token what `generate` gives
    one request at a time, and nothing after the stop token."""
    prompts = [_prompt(i, 12 + i) for i in range(4)]
    eng = _plain(tiny_model)
    try:
        full = _alone(eng, prompts, [SamplingParams(max_tokens=24)] * 4)
        cut = _first_new_token(full[1])
        params = [SamplingParams(max_tokens=24), SamplingParams(
            max_tokens=24, stop_token_ids=(full[1][cut],)),
            SamplingParams(max_tokens=5), SamplingParams(max_tokens=1)]
        alone = _alone(eng, prompts, params)
        s0 = _quiet_stats(eng)
        reqs = [eng.submit(p, sp) for p, sp in zip(prompts, params)]
        batch = [list(r) for r in reqs]
        s1 = _quiet_stats(eng)
    finally:
        eng.shutdown()
    assert batch == alone
    assert batch == [full[0], full[1][:cut], full[2][:5], full[3][:1]]
    assert [r.generated for r in reqs] == [24, cut + 1, 5, 1]  # the stop token counts
    assert s1["loop"]["tokens_discarded"] - s0["loop"]["tokens_discarded"] == 1
    assert s1["free_slots"] == 4 and s1["free_pages"] == s1["num_pages"] - 1


def test_a_slot_freed_by_a_stop_token_serves_the_next_row_its_own_tokens(tiny_model):
    """One slot: the row that stops on a stop token has taken part in one more
    step by the time the host knows, writing into pages the next request is
    granted in the next pass. That request reads none of it."""
    prompts = [_prompt(0, 12), _prompt(5, 30)]
    eng = _plain(tiny_model, max_slots=1)
    try:
        full = _alone(eng, prompts, [SamplingParams(max_tokens=24)] * 2)
        cut = _first_new_token(full[0])
        s0 = _quiet_stats(eng)
        first = eng.submit(prompts[0], SamplingParams(
            max_tokens=24, stop_token_ids=(full[0][cut],)))
        second = eng.submit(prompts[1], SamplingParams(max_tokens=24))
        outs = [list(first), list(second)]
        s1 = _quiet_stats(eng)
    finally:
        eng.shutdown()
    assert outs == [full[0][:cut], full[1]]
    assert first.slot == second.slot == 0
    assert first.dispatched == first.generated + 1  # the step it did not need
    assert s1["loop"]["tokens_discarded"] - s0["loop"]["tokens_discarded"] == 1
    assert s1["free_pages"] == s1["num_pages"] - 1


def test_steps_ahead_counts_every_step_but_the_first_and_none_beside_a_guided_row(
        tiny_model):
    """Unguided: every decode step but the first after an empty engine goes
    out while the step before it is unread, admissions in between included;
    rows that end by count drop no token, a row that stops on a stop token
    drops one. While a guided row lives every step is read before the next."""
    from ray_tpu.llm.guided import GuidedFSM

    eng = _plain(tiny_model)
    try:
        full = eng.generate(_prompt(1, 13), SamplingParams(max_tokens=24))
        cut = _first_new_token(full)
        s0 = _quiet_stats(eng)
        reqs = [eng.submit(_prompt(i, 12 + i), sp) for i, sp in enumerate((
            SamplingParams(max_tokens=40),
            SamplingParams(max_tokens=24, stop_token_ids=(full[cut],)),
            SamplingParams(max_tokens=6)))]
        long, stopped, counted = (list(r) for r in reqs)
        s1 = _quiet_stats(eng)
        allow_all = GuidedFSM(masks=np.ones((1, 64), bool),
                              trans=np.zeros((1, 64), np.int32))
        guided = eng.generate(_prompt(1, 13), SamplingParams(max_tokens=24,
                                                             guided=allow_all))
        s2 = _quiet_stats(eng)
    finally:
        eng.shutdown()
    assert len(long) == 40 and stopped == full[:cut] and len(counted) == 6
    assert guided == full
    steps = s1["decode_steps"] - s0["decode_steps"]
    assert steps == 39  # the long row's; the others ride along
    assert s1["loop"]["steps_ahead"] - s0["loop"]["steps_ahead"] == steps - 1
    assert s1["loop"]["tokens_discarded"] - s0["loop"]["tokens_discarded"] == 1
    assert s2["decode_steps"] - s1["decode_steps"] == 23
    assert s2["loop"]["steps_ahead"] == s1["loop"]["steps_ahead"]
    assert s2["loop"]["tokens_discarded"] == s1["loop"]["tokens_discarded"]


def test_phases_cover_the_thread_with_a_step_in_flight(tiny_model):
    """The phases still partition the thread's time when a step's tokens are
    fetched a pass after its dispatch and first tokens after the decode step
    that follows their prefill: stop tokens, counts and admissions mid-stream."""
    eng = _plain(tiny_model)
    try:
        full = _alone(eng, [_prompt(i, 12 + i) for i in range(6)],
                      [SamplingParams(max_tokens=16)] * 6)
        s0 = _quiet_stats(eng)
        cuts = [_first_new_token(f) if i % 2 else 16 for i, f in enumerate(full)]
        reqs = [eng.submit(_prompt(i, 12 + i), SamplingParams(
            max_tokens=16, stop_token_ids=tuple(full[i][cuts[i]:cuts[i] + 1])))
            for i in range(6)]
        outs = [list(r) for r in reqs]
        s1 = _quiet_stats(eng)
    finally:
        eng.shutdown()
    assert outs == [f[:cut] for f, cut in zip(full, cuts)]
    l0, l1 = s0["loop"], s1["loop"]
    delta = {p: l1["seconds"][p] - l0["seconds"][p] for p in LOOP_PHASES}
    assert sum(delta.values()) == pytest.approx(l1["thread_s"] - l0["thread_s"], rel=0.01)
    assert all(d >= 0 for d in delta.values())
    for busy in ("sweep", "admit", "admit_wait", "decode", "decode_wait", "emit"):
        assert delta[busy] > 0, busy
    assert l1["host_s"] - l0["host_s"] == pytest.approx(
        sum(delta[p] for p in HOST_PHASES), rel=0.01)
    assert l1["tokens_discarded"] - l0["tokens_discarded"] == 3
    assert l1["steps_ahead"] > l0["steps_ahead"]


def test_a_gated_looped_stack_runs_twenty_steps_in_flight():
    """The exit CDF of a step is read after the next step has been given the
    state: it is taken out of the state before that donation, and
    `exit_rows` counts a row a step as before."""
    from ray_tpu.models import ouro_config

    cfg = ouro_config("tiny", vocab_size=300, max_seq_len=1024, dtype=jnp.float32)
    assert cfg.exit_gate and cfg.n_passes > 1
    params = transformer.init(jax.random.PRNGKey(3), cfg)
    eng = TPUEngine(cfg, params, max_slots=3, max_len=512, min_bucket=16,
                    page_size=16, num_pages=60)
    try:
        reqs = [eng.submit(_prompt(i, 20 + i), SamplingParams(max_tokens=21 - 4 * i))
                for i in range(2)]
        outs = [list(r) for r in reqs]
        st = _quiet_stats(eng)
        assert "exit_cdf" not in eng.state
    finally:
        eng.shutdown()
    assert [len(o) for o in outs] == [21, 17]
    assert st["decode_steps"] == 20 and st["loop"]["steps_ahead"] == 19
    assert st["loops"]["stack_passes"] == 20 * cfg.n_passes
    assert sum(st["loops"]["exit_rows"]) == 20 + 16  # a row a step
    assert st["loop"]["tokens_discarded"] == 0


def test_an_abort_returns_slot_and_pages_within_two_steps(tiny_model):
    """An abort mid-stream: the step in flight at the sweep still holds the
    row, none is dispatched for it after, its token is not delivered, and
    slot and pages are back."""
    from ray_tpu.exceptions import RequestCancelledError

    eng = _plain(tiny_model)
    try:
        req = eng.submit(_prompt(0, 12), SamplingParams(max_tokens=100))
        stream = iter(req)
        got = [next(stream) for _ in range(4)]
        eng.abort_request(req.rid)
        with pytest.raises(RequestCancelledError):
            for tok in stream:
                got.append(tok)
        st = _quiet_stats(eng)
    finally:
        eng.shutdown()
    assert req.finished and 4 <= len(got) == req.generated < 100
    assert req.out_queue.empty()  # nothing after the error
    # one step beyond what was delivered, the one in flight at the sweep
    assert req.dispatched == req.generated + 1 == st["decode_steps"] + 1
    assert st["loop"]["tokens_discarded"] == 1 and st["aborts"] == 1
    assert st["free_slots"] == 4 and st["free_pages"] == st["num_pages"] - 1


# ------------------------------------------- the benchmark's reader of these


def _stats(loop_seconds: dict, requests: dict, decode_steps: int,
           inside: dict) -> dict:
    seconds = dict.fromkeys(LOOP_PHASES, 0.0)
    seconds.update(loop_seconds)
    host = sum(seconds[p] for p in HOST_PHASES)
    return {"decode_steps": decode_steps,
            "loop": {"seconds": seconds, "host_s": host,
                     "active_s": host + sum(seconds[p] for p in WAITS),
                     "thread_s": sum(seconds.values()), "requests": requests,
                     **inside}}


def _passes(step: tuple, step_prefill: tuple) -> dict:
    return {kind: dict(zip(("count", "seconds", "rows", "prompt_tokens"), row))
            for kind, row in (("step", step), ("step_prefill", step_prefill))}


S0 = _stats({"parked": 5.0, "sweep": 0.1, "admit": 0.2, "admit_wait": 0.3,
             "prefill": 0.4, "prefill_wait": 0.5, "decode": 1.0,
             "decode_wait": 8.0, "emit": 0.5},
            {"requests_scheduled": 10, "queue_wait_s": 1.0, "first_tokens": 10,
             "prefill_s": 2.0}, 100,
            {"dispatch_s": 0.5, "host_cpu_s": 1.0,
             "passes": _passes((80, 1.6, 200, 0), (19, 1.9, 60, 19000))})
S1 = _stats({"parked": 6.0, "sweep": 0.3, "admit": 0.6, "admit_wait": 0.9,
             "prefill": 1.2, "prefill_wait": 1.5, "decode": 3.0,
             "decode_wait": 24.0, "emit": 1.5},
            {"requests_scheduled": 30, "queue_wait_s": 5.0, "first_tokens": 26,
             "prefill_s": 10.0}, 300,
            {"dispatch_s": 1.6, "host_cpu_s": 3.2,
             "passes": _passes((230, 4.6, 650, 0), (69, 8.9, 180, 69000))})
# deltas: host 0.2+0.4+0.8+2.0+1.0 = 4.4; waits 0.6+1.0+16.0 = 17.6; active 22.0;
# dispatch 1.1, CPU in host phases 2.2; 150 passes `step` in 3.0 s, 50
# `step_prefill` in 7.0 s
HAND = {
    "engine_host_pct.chat": 100 * 4.4 / 22.0,
    "engine_host_pct.doc": 100 * 4.4 / 22.0,
    "step_host_ms.chat": 1e3 * 4.4 / 200,
    "step_device_wait_ms.chat": 1e3 * 16.0 / 200,
    "engine_decode_pct.doc": 100 * (2.0 + 16.0 + 1.0) / 22.0,
    "queue_wait_ms.chat": 1e3 * 4.0 / 20,
    "queue_wait_ms.doc": 1e3 * 4.0 / 20,
    "prefill_latency_ms.doc": 1e3 * 8.0 / 16,
}
# ISSUE 37's seven: what lies inside the phases
HAND_INSIDE = {
    "step_dispatch_ms.chat": 1e3 * 1.1 / 200,
    "engine_dispatch_pct.doc": 100 * 1.1 / 22.0,
    "engine_cpu_pct.chat": 100 * 2.2 / 22.0,
    "engine_cpu_pct.doc": 100 * 2.2 / 22.0,
    "decode_pass_ms.chat": 1e3 * 3.0 / 150,
    "decode_pass_ms.doc": 1e3 * 3.0 / 150,
    "prefill_pass_ms.doc": 1e3 * 7.0 / 50,
}
HAND.update(HAND_INSIDE)


@pytest.mark.parametrize("metric", sorted(HAND))
def test_stats_ratio_reads_the_metric(metric):
    from chipbench.readers import stats_ratio

    spec = json.load(open(os.path.join(
        REPO, "chipbench", "layer_metrics", metric + ".json")))
    assert spec["reader"] == "stats_ratio"
    facts = {"stats0": S0, "stats1": S1}
    assert stats_ratio.read(facts, spec["params"]) == pytest.approx(HAND[metric])
    # the parent's stats() has no "loop": nothing to read, nothing raised
    bare = {"stats0": {"decode_steps": 100}, "stats1": {"decode_steps": 300}}
    assert stats_ratio.read(bare, spec["params"]) is None
    assert stats_ratio.read({}, spec["params"]) is None
    # a denominator that did not move gives no number
    assert stats_ratio.read({"stats0": S1, "stats1": S1}, spec["params"]) is None
    entry = [m for m in json.load(open(os.path.join(REPO, "BENCHMARK.json")))[
        "per_layer"] if m["name"] == metric]
    assert len(entry) == 1 and entry[0]["source"] == "program_counter"
    # one cell, or (`.doc`, since PR 28) every cell judged on `served_tok_s`
    cells = {w["name"] for w in json.load(open(os.path.join(REPO, "BENCHMARK.json")))["workloads"]}
    assert entry[0]["better"] == "lower" and set(entry[0]["workloads"]) <= cells
    served = [m for m in json.load(open(os.path.join(REPO, "BENCHMARK.json")))[
        "end_to_end"] if m["name"] == "served_tok_s"][0]["workloads"]
    assert entry[0]["workloads"] == (
        ["mixtral-8x7b.chat-steady"] if metric.endswith(".chat") else served)


def test_the_new_metric_files_read_a_real_engines_two_readings(tiny_model):
    """The seven data files of ISSUE 37 name paths that `stats()` really has:
    through the benchmark's own `read_layer_metrics`, from two readings of a
    tiny engine (CPU: the values are no device numbers, only their presence
    and their arithmetic are checked)."""
    from chipbench import harness

    eng = _engine(tiny_model)
    try:
        _burst(eng, 2, 20, 3)
        s0 = _quiet_stats(eng)
        _burst(eng, 6, 40, 8)
        s1 = _quiet_stats(eng)
    finally:
        eng.shutdown()
    new = sorted(HAND_INSIDE)
    per_layer = [m for m in harness.load_json(harness.ROOT, "BENCHMARK.json")[
        "per_layer"] if m["name"] in new]
    facts = {"stats0": s0, "stats1": s1}
    got = {k: v["value"] for k, v in harness.read_layer_metrics(
        {"per_layer": per_layer}, facts).items()}
    assert sorted(got) == new and all(v > 0 for v in got.values())
    l0, l1 = s0["loop"], s1["loop"]
    active = l1["active_s"] - l0["active_s"]
    host_pct = 100 * (l1["host_s"] - l0["host_s"]) / active
    assert got["engine_dispatch_pct.doc"] <= host_pct
    assert got["engine_cpu_pct.doc"] == got["engine_cpu_pct.chat"] <= host_pct + 1
    assert got["decode_pass_ms.doc"] == got["decode_pass_ms.chat"]
    assert got["step_dispatch_ms.chat"] == pytest.approx(
        1e3 * (l1["dispatch_s"] - l0["dispatch_s"])
        / (s1["decode_steps"] - s0["decode_steps"]))
    # the parent's program has none of the three blocks: nothing read, no raise
    old = {k: {**s, "loop": {p: v for p, v in s["loop"].items() if p not in (
        "dispatch_s", "host_cpu_s", "passes")}} for k, s in facts.items()}
    assert harness.read_layer_metrics({"per_layer": per_layer}, old) == {}


def test_host_and_active_sums_partition_the_phases(tiny_model):
    """"host" is every phase but `parked` and the waits, "active" every
    phase but `parked`: `stats()` gives both sums, so a metric file names
    one path and a phase added to the engine is in them."""
    assert set(HOST_PHASES) | set(WAITS) | {"parked"} == set(LOOP_PHASES)
    eng = _engine(tiny_model)
    try:
        _burst(eng, 2, 40, 3)
        loop = _quiet_stats(eng)["loop"]
    finally:
        eng.shutdown()
    sec = loop["seconds"]
    assert loop["host_s"] == pytest.approx(sum(sec[p] for p in HOST_PHASES))
    assert loop["active_s"] == pytest.approx(
        sum(sec[p] for p in LOOP_PHASES if p != "parked"))
    assert 0 < loop["host_s"] < loop["active_s"] < loop["thread_s"]


# ---------------------- one admission, one prefill runner, one go-live tail


@functools.lru_cache(maxsize=None)
def _form_model(kind: str) -> tuple:
    """A tiny stand-in of a served family (built once a process)."""
    from ray_tpu import models

    sizes = dict(vocab_size=300, max_seq_len=1024, dtype=jnp.float32)
    cfg = {
        "dense": lambda: TransformerConfig(
            d_model=32, n_layers=1, n_heads=2, n_kv_heads=2, d_ff=64, remat=False, **sizes),
        "window": lambda: models.mellum_config("tiny", n_layers=4, **sizes),
        "latent": lambda: models.kimi_vl_config("tiny", **sizes),
        "looped": lambda: models.ouro_config("tiny", **sizes),
        "state_space": lambda: models.granite_config("tiny", **sizes),
        "held_experts_window": lambda: models.trinity_config(
            "tiny", n_layers=6, experts_held=8, first_expert=16, **sizes),
        "held_experts_state": lambda: models.solar_open2_config(
            "tiny", n_layers=4, experts_held=8, first_expert=16, **sizes),
    }[kind]()
    return cfg, transformer.init(jax.random.PRNGKey(3), cfg)


# The shapes of engine the benchmark's eight serve configurations build (and
# granite's with chunks, which its tests serve), each at a tiny stand-in of
# the model: (model, engine options).
ENGINE_FORMS = {
    "plain": ("dense", {}),
    "prefix_chunk": ("dense", dict(enable_prefix_cache=True, prefill_chunk=32)),
    "window_chunk": ("window", dict(prefill_chunk=32)),
    "latent_prefix_chunk": ("latent", dict(enable_prefix_cache=True, prefill_chunk=32)),
    "looped_one_prefill_a_pass": ("looped", dict(max_prefills_per_step=1)),
    "state_space": ("state_space", {}),
    "state_space_chunk": ("state_space", dict(prefill_chunk=32)),
    "held_experts_window_chunk": ("held_experts_window", dict(prefill_chunk=32)),
    # a recurrent state a row AND a share of the experts (since PR 50)
    "held_experts_state_chunk": ("held_experts_state", dict(prefill_chunk=32)),
}


def _form_engine(form: str) -> TPUEngine:
    model, opts = ENGINE_FORMS[form]
    cfg, params = _form_model(model)
    # 15 pages to grant: any request of the script alone, never two long ones
    return TPUEngine(cfg, params, max_slots=2, max_len=256, min_bucket=16, page_size=16,
                     num_pages=16, **opts)


def _form_tokens(n: int, seed: int) -> list:
    return np.random.default_rng(seed).integers(1, 300, n).tolist()


GO_LIVE_PROGRAMS = ("insert_sequence_paged", "insert_sequence_paged_prefix", "activate_slot")
STEP = " decode_step split sample commit"


def _admission_tables(form: str, monkeypatch) -> tuple:
    """The script of requests a form is given, one at a time on a quiet
    engine, four tokens each: a prompt shorter than a chunk (`whole`); where
    the engine has a chunk size one of three chunks (`staged`); where it also
    has the prefix cache one that shares the first chunk with it and is staged
    behind those two blocks (`staged_cached`), and the long one again
    (`cached`: its last 11 tokens behind four cached blocks, unstaged).
    Returns what each dispatched, in order (`STEP` for a decode step's four),
    with the program that took the row live by name, and the tokens each was
    answered with; then, with a long row holding all but one page, what a
    request that has to wait for pages dispatched while it waited. The calls
    that `stats()["loop"]["dispatch"]` counted are those."""
    from ray_tpu.models import decoding_paged as dp

    _, opts = ENGINE_FORMS[form]
    long_ = _form_tokens(75, 2)
    script = [("whole", _form_tokens(23, 1))]
    if "prefill_chunk" in opts:
        script.append(("staged", long_))
        if "enable_prefix_cache" in opts:
            script += [("staged_cached", long_[:32] + _form_tokens(43, 3)), ("cached", long_)]
    went_live, order = [], []
    for name in GO_LIVE_PROGRAMS:
        def counted(*a, _real=getattr(dp, name), _name=name, **kw):
            went_live.append(_name)
            return _real(*a, **kw)
        monkeypatch.setattr(dp, name, counted)
    eng = _form_engine(form)
    timed = eng._clock.dispatch
    eng._clock.dispatch = lambda program: (order.append(program), timed(program))[1]

    def table(before: dict, after: dict) -> str:
        counted = _delta(before["loop"]["dispatch"], after["loop"]["dispatch"])
        assert ({program: row["calls"] for program, row in counted.items() if row["calls"]}
                == collections.Counter(order))
        out = " ".join(order).replace(STEP, " STEP") + " by " + ",".join(went_live)
        order.clear(), went_live.clear()
        return out

    tables, tokens = {}, {}
    try:
        for case, prompt in script:
            before = _quiet_stats(eng)
            tokens[case] = eng.generate(prompt, SamplingParams(max_tokens=4))
            tables[case] = table(before, _quiet_stats(eng))
        # 23 + 200 tokens reach into 14 of the 15 pages; the next needs two
        holder = eng.submit(_form_tokens(23, 5), SamplingParams(max_tokens=200))
        stream = iter(holder)
        next(stream)
        upto = len(order)
        waiter = eng.submit(_form_tokens(20, 4), SamplingParams(max_tokens=4))
        deadline = time.time() + 30.0
        while not eng._backlog and time.time() < deadline:
            time.sleep(0.001)
        while_waiting = order[upto:]
        assert not holder.finished and waiter.scheduled_ts == 0.0
        eng.abort_request(holder.rid)
        tokens["backlogged"] = list(waiter)
        # the holder's decode steps went on beside it
        tables["backlogged"] = " ".join(p for p in while_waiting if p not in STEP.split())
        assert _quiet_stats(eng)["free_pages"] + len(eng._prefix_cache) == 15
    finally:
        eng.shutdown()
    return tables, tokens


# What the engine at PR 47 (9124fbf) dispatched for the script, program for
# program (taken there with this file's `_admission_tables`); since PR 48 a
# chunk's page ids travel in an `h2d` of their own, after its prefill and
# before `write_pages`, and a staged row with a ring uploads the ring once
# more before `activate`: every other row is the parent's.
_LIVE = " bind STEP STEP STEP release by "
_WHOLE = "h2d prefill split sample_first insert" + _LIVE
_CHUNKS = ("h2d prefill h2d write_pages"
           " h2d h2d gather_prefix prefill_with_prefix h2d write_pages"
           " h2d h2d gather_prefix prefill_with_prefix h2d write_pages"
           " split sample_first activate" + _LIVE + "activate_slot")
_INSERTED = {"whole": _WHOLE + "insert_sequence_paged"}
_CHUNKED = {"whole": _WHOLE + "insert_sequence_paged_prefix", "staged": _CHUNKS}
_CHUNKED_CACHED = {
    **_CHUNKED,
    "staged_cached": _CHUNKS.removeprefix("h2d prefill h2d write_pages "),
    "cached": ("h2d h2d gather_prefix prefill_with_prefix split sample_first insert"
               + _LIVE + "insert_sequence_paged_prefix")}
_CHUNKED_RING = {
    "whole": ("h2d prefill split sample_first h2d insert" + _LIVE
              + "insert_sequence_paged_prefix"),
    "staged": ("h2d h2d prefill h2d write_pages"
               " h2d h2d h2d gather_prefix gather_window prefill_with_prefix h2d write_pages"
               " h2d h2d h2d gather_prefix gather_window prefill_with_prefix h2d write_pages"
               " split sample_first h2d activate" + _LIVE + "activate_slot")}
ADMISSIONS = {
    "plain": _INSERTED, "prefix_chunk": _CHUNKED_CACHED, "window_chunk": _CHUNKED_RING,
    "latent_prefix_chunk": _CHUNKED_CACHED, "looped_one_prefill_a_pass": _INSERTED,
    "state_space": _INSERTED, "state_space_chunk": _CHUNKED,
    "held_experts_window_chunk": _CHUNKED_RING, "held_experts_state_chunk": _CHUNKED}


@pytest.mark.parametrize("form", sorted(ENGINE_FORMS))
def test_every_form_of_engine_admits_a_prompt_by_the_same_programs(form, monkeypatch):
    """A prompt goes live by one admission, one prefill runner and one tail,
    whatever the engine: the programs each kind of admission dispatches, in
    order, are the table's; a prompt answered out of the prefix cache gets the
    tokens it got in chunks; a request that waits for pages has dispatched
    nothing and been granted nothing, and is served when the pages come back."""
    tables, tokens = _admission_tables(form, monkeypatch)
    assert tables == {**ADMISSIONS[form], "backlogged": ""}
    assert all(len(out) == 4 for out in tokens.values())
    if "cached" in tokens:
        assert tokens["cached"] == tokens["staged"]
