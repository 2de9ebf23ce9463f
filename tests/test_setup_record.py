"""The set-up on the record (ISSUE 57): `compile_cache_counts()` counts
JAX's tracing, lowering and backend seconds once each and by program,
`stats()["setup"]` times a replica's start by stage, and the thirteen
per-layer metrics read both. All on the CPU at tiny widths: no number here
is a device number."""

import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from ray_tpu._private import accelerators
from ray_tpu.llm import LLMConfig, ModelLoadingConfig, SamplingParams, TPUEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STAGES = ("trace", "lower", "backend")
SETUP_STAGES = ("process", "backend", "weights", "engine", "to_first_request")
SERVE_METRICS = (
    "ready_process_s.serve", "ready_backend_s.serve", "ready_weights_s.serve",
    "ready_engine_s.serve", "ready_runtime_s.serve", "setup_trace_s.serve",
    "setup_lower_s.serve", "setup_load_s.serve", "setup_cold_programs.serve")
TRAIN_METRICS = ("setup_trace_s.train", "setup_lower_s.train",
                 "setup_load_s.train", "setup_cold_programs.train")


def _in_a_thread(fn, name="compiles-here"):
    """`fn()` on a thread of its own; its result."""
    out = []
    t = threading.Thread(target=lambda: out.append(fn()), name=name)
    t.start()
    t.join(timeout=120)
    assert not t.is_alive() and out
    return out[0]


def _moved(before: dict, after: dict) -> dict:
    return {k: after["seconds"][k] - before["seconds"][k] for k in after["seconds"]}


@pytest.mark.parametrize("calls", [20, 1500])
def test_a_nested_trace_is_counted_once(calls):
    """A `jit` inside a `jit`: the inner traces, and every `jnp` call's,
    fire before the outer one and lie inside it. The totals hold the outer
    interval alone, the table one row, under the lowering's name. (1,500
    calls: a kernel body that unrolls its heads in Python has thousands of
    events directly inside one trace, after other programs' intervals.)"""
    @jax.jit
    def inner_of_57(x):
        return jnp.sin(x) * 2

    @jax.jit
    def outer_of_57(x):
        for _ in range(calls):
            x = inner_of_57(x) + inner_of_57(x + 1)
        return x

    accelerators.compile_cache_counts()
    x = jnp.ones((5, 3))

    def after_other_programs():
        # this thread's earlier intervals are not the wide trace's to absorb
        jax.jit(lambda x: x - 57)(x).block_until_ready()
        before, t0 = accelerators.compile_cache_counts(), time.time()
        outer_of_57(x).block_until_ready()
        return before, time.time() - t0

    before, wall_s = _in_a_thread(after_other_programs)
    after = accelerators.compile_cache_counts()
    moved = _moved(before, after)
    assert all(moved[k] > 0 for k in STAGES)
    assert sum(moved[k] for k in STAGES) <= wall_s
    changed = {name for name, row in after["programs"].items()
               if row != before["programs"].get(name)}
    assert changed == {"jit(outer_of_57)"}  # not inner_of_57, not sin
    row = after["programs"]["jit(outer_of_57)"]
    was = before["programs"].get("jit(outer_of_57)") or dict.fromkeys(row, 0)
    assert all(row[k] - was[k] == 1 for k in ("traces", "lowers", "compiles"))
    # the whole trace, the nested ones in it, and nothing counted twice
    assert row["trace_s"] - was["trace_s"] == pytest.approx(moved["trace"])
    assert row["lower_s"] - was["lower_s"] == pytest.approx(moved["lower"])
    assert row["backend_s"] - was["backend_s"] == pytest.approx(moved["backend"])
    assert row["last"]["thread"] == "compiles-here" and row["last"]["phase"] is None


def test_a_second_call_moves_nothing():
    f = jax.jit(lambda x: x * 5 - 2)
    x = jnp.ones((3, 11))
    f(x)
    before = accelerators.compile_cache_counts()
    assert _in_a_thread(lambda: f(x).block_until_ready()).shape == (3, 11)
    assert accelerators.compile_cache_counts() == before


def test_a_programs_last_compile_holds_the_threads_phase():
    def named_in_57(x):
        return x * 7 + 3

    def compile_it():
        accelerators.note_thread_activity(lambda: "a-phase-of-57")
        t0 = time.time()
        jax.jit(named_in_57)(jnp.ones((2, 13))).block_until_ready()
        return t0, time.time()

    accelerators.compile_cache_counts()
    t0, t1 = _in_a_thread(compile_it, name="has-a-phase")
    last = accelerators.compile_cache_counts()["programs"]["jit(named_in_57)"]["last"]
    assert last["thread"] == "has-a-phase" and last["phase"] == "a-phase-of-57"
    assert t0 <= last["t"] <= t1 and set(last) == {"t", "thread", "phase"}


def test_a_cache_hit_is_counted_by_program_with_its_retrieval(tmp_path):
    """Two functions of one name and one body make one module: the second
    is found in the persistent cache (a hit, the cache's own seconds in
    `retrieval`, inside `backend`)."""
    from jax.experimental.compilation_cache import compilation_cache

    def twice_in_57(x):
        return jnp.tanh(x) * 57.0

    def again():
        def twice_in_57(x):
            return jnp.tanh(x) * 57.0
        return twice_in_57

    keep = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    accelerators.compile_cache_counts()
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        compilation_cache.reset_cache()
        x = jnp.ones((4, 9))
        jax.jit(twice_in_57)(x).block_until_ready()
        first = accelerators.compile_cache_counts()
        jax.jit(again())(x).block_until_ready()
        second = accelerators.compile_cache_counts()
    finally:
        for k, v in keep.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    row0, row1 = (c["programs"]["jit(twice_in_57)"] for c in (first, second))
    assert (row0["misses"], row0["hits"]) == (1, 0)
    assert (row1["misses"], row1["hits"], row1["compiles"]) == (1, 1, 2)
    assert second["hits"] - first["hits"] == 1
    assert second["misses"] == first["misses"]
    moved = _moved(first, second)
    assert 0 < moved["retrieval"] <= moved["backend"]


def _tiny_llm_config():
    return LLMConfig(
        model_loading_config=ModelLoadingConfig(model_id="tiny", tokenizer="byte"),
        model_family="llama", accelerator_type=None,
        model_kwargs=dict(vocab_size=300, max_seq_len=128, d_model=64,
                          n_layers=2, n_heads=4, n_kv_heads=2, d_ff=128,
                          dtype=jnp.float32, remat=False),
        engine_kwargs={"max_slots": 4, "max_len": 128, "min_bucket": 16})


@pytest.fixture(scope="module")
def two_readings():
    """A host-only engine from `from_config` (the record is stamped nowhere
    else), read before its first request and after a few."""
    eng = TPUEngine.from_config(_tiny_llm_config())
    try:
        unasked = eng.stats()
        for prompt in ([1, 2, 3, 4, 5], [9, 8, 7]):
            assert len(eng.generate(prompt, SamplingParams(max_tokens=4))) == 4
        s0 = eng.stats()
        assert len(eng.generate([5, 5, 5, 6], SamplingParams(max_tokens=6))) == 6
        s1 = eng.stats()
    finally:
        eng.shutdown()
    return unasked, s0, s1, time.time()


def test_setup_has_the_start_by_stage(two_readings):
    unasked, s0, s1, now = two_readings
    early = unasked["setup"]
    assert tuple(early["seconds"]) == SETUP_STAGES
    assert early["seconds"]["to_first_request"] is None
    assert set(early["compile_at"]) == {"weights", "engine"}
    setup = s0["setup"]
    assert setup == s1["setup"] == json.loads(json.dumps(setup))  # stamped once
    assert tuple(setup["seconds"]) == SETUP_STAGES
    assert all(v >= 0 for v in setup["seconds"].values())
    # the model and the engine's tables were built inside their stages
    assert setup["seconds"]["weights"] > 0 and setup["seconds"]["engine"] > 0
    assert sum(setup["seconds"].values()) <= now - setup["t_process"]
    assert setup["t_process"] == pytest.approx(
        accelerators.process_start_time(), abs=0.05)
    at = setup["compile_at"]
    assert tuple(at) == ("weights", "engine", "first_request")
    for earlier, later in (("weights", "engine"), ("engine", "first_request")):
        assert set(at[earlier]) == {"trace", "lower", "backend", "retrieval"}
        assert all(at[earlier][k] <= at[later][k] for k in at[earlier])
    # the constructor compiled something, and stats() counts on from there
    assert at["engine"]["backend"] > at["weights"]["backend"]
    assert all(at["first_request"][k] <= s0["compile_cache"]["seconds"][k]
               for k in STAGES)
    # a stage holds its compile work: no more of it than the stage is long
    in_engine = sum(at["engine"][k] - at["weights"][k] for k in STAGES)
    assert in_engine <= setup["seconds"]["engine"]


def test_an_engine_built_directly_has_no_record():
    from ray_tpu.models import transformer
    from ray_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=1, n_heads=2,
                            n_kv_heads=2, d_ff=64, max_seq_len=64,
                            dtype=jnp.float32, remat=False)
    eng = TPUEngine(cfg, transformer.init(jax.random.PRNGKey(0), cfg),
                    max_slots=2, max_len=64)
    try:
        assert len(eng.generate([1, 2, 3], SamplingParams(max_tokens=2))) == 2
        assert eng.stats()["setup"] is None
    finally:
        eng.shutdown()


def _entry(name: str) -> dict:
    found = [m for m in json.load(open(os.path.join(REPO, "BENCHMARK.json")))[
        "per_layer"] if m["name"] == name]
    assert len(found) == 1
    return found[0]


@pytest.mark.parametrize("metric", SERVE_METRICS + TRAIN_METRICS)
def test_the_metric_file_reads_a_number_from_the_real_record(metric, two_readings):
    """Through the benchmark's own `read_layer_metrics`, on what a serve run
    and a train run put into `facts.json`: a renamed key fails here, not on
    the chip. A program without the record (the parent) gives no number."""
    from chipbench import harness

    _, s0, s1, _ = two_readings
    entry = _entry(metric)
    serve = metric.endswith(".serve")
    facts = ({"ready_s": 1e4, "stats0": s0, "stats1": s1} if serve
             else {"ready_s": 1.0, "compile_cache": accelerators.compile_cache_counts()})
    got = harness.read_layer_metrics({"per_layer": [entry]}, facts)
    assert set(got) == {metric} and got[metric]["value"] >= 0
    assert got[metric]["unit"] == entry["unit"] == (
        "programs" if "cold_programs" in metric else "s")
    assert entry["moves"] == "setup_s" and entry["source"] == "program_counter"
    ready = _entry("ready_s.serve" if serve else "ready_s.train")
    assert (entry["layer"], entry["workloads"]) == (ready["layer"], ready["workloads"])
    if metric == "ready_runtime_s.serve":
        four = s0["setup"]["seconds"]
        assert got[metric]["value"] == pytest.approx(
            1e4 - sum(four[k] for k in SETUP_STAGES[:4]))
    # the parent: `requests`, `hits`, `misses`, `dir` and no more
    old = {k: v for k, v in s0["compile_cache"].items()
           if k not in ("seconds", "programs")}
    bare = ({"ready_s": 1e4, "stats0": {
        **{k: v for k, v in s0.items() if k != "setup"}, "compile_cache": old}}
        if serve else {"ready_s": 1.0, "compile_cache": old})
    left = harness.read_layer_metrics({"per_layer": [entry]}, bare)
    assert set(left) == ({metric} if "cold_programs" in metric else set())


def test_fact_less_gives_nothing_when_a_path_is_missing():
    from chipbench.readers import fact_less

    params = {"path": "a", "less": ["b.c", "b.d"]}
    assert fact_less.read({"a": 10.0, "b": {"c": 1.5, "d": 2.5}}, params) == 6.0
    assert fact_less.read({"a": 10.0, "b": {"c": 1.5}}, params) is None
    assert fact_less.read({"b": {"c": 1.5, "d": 2.5}}, params) is None
    assert fact_less.read({"a": 10.0, "b": {"c": 1.5, "d": None}}, params) is None
    assert fact_less.read({}, params) is None
