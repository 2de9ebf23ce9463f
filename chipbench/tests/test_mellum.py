"""mellum2-12b-a2.5b: the configuration's sizes by the compiler's account for
a described v5e, its plain reference against the program at a tiny size, the
CPU rehearsal of its cell, and the reader of its two launches' roofline."""

import argparse
import copy
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from chipbench import harness, program
from chipbench.kinds import serve
from chipbench.reference import mellum
from ray_tpu.models import transformer

CELL = "mellum2-12b-a2.5b.mixed-saturated"
USABLE = 15.49e9  # 15.75 GB of HBM less 0.26 GB the runtime reserves


def tiny_cell():
    """The cell's files at toy widths: the same keys, two periods of four
    layers, a window of 128 positions = 8 pages of 16, a ring of 13."""
    cell = copy.deepcopy(harness.resolve_cell(CELL))
    conf, mix = cell["config_file"], cell["traffic_file"]
    small = dict(d_model=64, n_layers=8, n_heads=4, n_kv_heads=2, d_head=16, d_ff=32,
                 vocab_size=300, max_seq_len=1024)
    conf["sizes"].update(small, num_experts=8, top_k=3, window=128)
    conf["sizes"]["yarn"]["original_max_position"] = 256
    conf["program"].update(model_id="tiny", model_kwargs=dict(
        vocab_size=300, max_seq_len=1024, dtype="float32", param_dtype="float32"))
    conf["engine"] = {"kv_layout": "paged", "page_size": 16, "max_slots": 4, "max_len": 640,
                      "min_bucket": 32, "num_pages": 120, "prefill_chunk": 64,
                      "enable_prefix_cache": False}
    conf["check"].update(sample_tokens=300, positions=6, logits_rel_tol=2e-3,
                         logits_median_tol=2e-3, served_gap_tol=1e-2, router_tie=1e-5)
    conf["ready_timeout_s"] = 300.0
    # every bucket, (prefix span, tail bucket) pair and decode bound of the tiny mix
    mix.update(rate_rps=6.0, warmup_wave=2,
               warmup=[[10, 4], [20, 4], [40, 4], [60, 6], [210, 4], [250, 4], [270, 4],
                       [310, 4], [330, 4], [380, 6]])
    short, long = mix["classes"]
    short["prompt"].update(median=24, min=8, max=60)
    short["output"].update(median=4, min=2, max=6)
    long["prompt"].update(median=260, min=200, max=380)
    long["output"].update(min=2, max=6)
    return {**cell, "name": "tiny.mixed", "run_seconds": 2}


def test_reference_agrees_with_the_program():
    conf = tiny_cell()["config_file"]
    cfg = program.transformer_config(conf["program"])
    assert (cfg.window, cfg.window_period, cfg.yarn.original_max_position) == (128, 4, 256)
    p = program.init_params(cfg, 2**31 + 5)
    p = jax.tree.map(lambda x: x + 0.01 * jax.random.normal(
        jax.random.PRNGKey(7), x.shape, x.dtype), p)  # norms away from one
    tokens = np.random.default_rng(0).integers(0, 300, 3 * 128 + 9, dtype=np.int32)
    logits, _ = transformer.forward(p, tokens[None], cfg)
    want, margin = mellum.forward(p, jnp.asarray(tokens), conf["sizes"])
    assert margin.shape == (8, len(tokens), 2) and bool((margin >= 0).all())
    assert float(jnp.abs(logits[0] - want).max() / jnp.abs(want).max()) < 1e-4


def test_the_published_sizes_are_the_catalogs():
    """Every number of the published config is in the file under its key, and
    the program's configuration is built from the same ones."""
    conf = harness.resolve_cell(CELL)["config_file"]
    cfg = program.transformer_config(conf["program"])
    assert conf["reduced"] == ["num_hidden_layers"] and conf["reduced_from"] == {
        "num_hidden_layers": 28}
    assert (cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.d_ff) == (
        conf["hidden_size"], conf["num_attention_heads"], conf["num_key_value_heads"],
        conf["head_dim"], conf["moe_intermediate_size"])
    assert (cfg.moe.num_experts, cfg.moe.top_k, cfg.vocab_size, cfg.window) == (
        conf["num_experts"], conf["num_experts_per_tok"], conf["vocab_size"],
        conf["sliding_window"])
    full = conf["rope_parameters"]["full_attention"]
    assert (cfg.rope_theta, cfg.yarn.factor, cfg.yarn.original_max_position,
            cfg.yarn.scale) == (full["rope_theta"], full["factor"],
                                full["original_max_position_embeddings"],
                                full["attention_factor"])
    kinds = conf["layer_types"][:cfg.n_layers]
    assert [k == "full_attention" for k in kinds] == [
        l % cfg.window_period == cfg.window_period - 1 for l in range(cfg.n_layers)]
    sizes = conf["sizes"]
    assert sizes["yarn"]["attention_factor"] == cfg.yarn.scale
    assert (sizes["n_layers"], sizes["window"], sizes["window_period"]) == (
        cfg.n_layers, cfg.window, cfg.window_period)


@pytest.fixture(scope="module")
def on_chip():
    """Shapes on one described v5e chip (no chip attached)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    one = SingleDeviceSharding(topo.devices[0])
    return lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), tree)


def _total(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def test_the_steps_fit_beside_the_weights_and_match_the_file(on_chip, monkeypatch):
    """The decode step at the file's pools (both aliased in place), and the
    largest prefill program (a 1024-token chunk over an 8,192-token prefix:
    the longest whose float32 scores are taken for all heads at once) with
    the pools it runs beside: under what a v5e has, and what `aot` records."""
    import sys

    import ray_tpu.ops.attention  # noqa: F401
    from ray_tpu.models import decoding_paged as dp

    monkeypatch.setattr(sys.modules["ray_tpu.ops.attention"], "_flash_ok",
                        lambda q: q.shape[1] % 256 == 0 and q.shape[1] >= 1024)
    conf = harness.resolve_cell(CELL)["config_file"]
    cfg, eng, aot = program.transformer_config(conf["program"]), conf["engine"], conf["aot"]
    params = on_chip(jax.eval_shape(lambda k: transformer.init(k, cfg), jax.random.PRNGKey(0)))
    weights = sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert weights == aot["weights_bytes"] == 10931913216
    ring = dp.window_ring(cfg, eng["page_size"], eng["prefill_chunk"])
    state = on_chip(jax.eval_shape(lambda: dp.init_paged_state(
        cfg, eng["max_slots"], eng["max_len"], eng["num_pages"], eng["page_size"],
        ring=ring)))
    window_pages = state["wkp"].shape[1]
    assert ring == 33 and window_pages == eng["max_slots"] * ring + 1 == 1585
    pools = {k: int(np.prod(state[k].shape)) * 2 for k in ("kp", "vp", "wkp", "wvp")}
    assert pools["kp"] + pools["vp"] == aot["full_pool_bytes"]
    assert pools["wkp"] + pools["wvp"] == aot["window_pool_bytes"]
    assert pools["kp"] // eng["num_pages"] * 2 == aot["full_page_bytes"] == 393216
    assert pools["wkp"] // window_pages * 2 == aot["window_page_bytes"] == 1179648
    step = dp.decode_step_paged_ragged.lower(params, state, cfg, 256, True).compile()
    text = step.as_text()
    assert "ragged_window_attention" in text and "ragged_paged_attention" in text
    m = step.memory_analysis()
    assert m.alias_size_in_bytes >= sum(pools.values())          # all four pools in place
    assert m.temp_size_in_bytes < 0.5 * pools["kp"]              # far under a pool
    assert _total(step) < USABLE
    assert _total(step) == pytest.approx(aot["decode_step_bytes"], rel=0.01)
    ints = on_chip(jax.ShapeDtypeStruct((), jnp.int32))

    def kv(layers, tokens):
        return on_chip(jax.ShapeDtypeStruct((layers, tokens, 4, 128), cfg.dtype))

    Lf = cfg.n_full_layers
    chunk = dp.prefill_with_prefix.lower(
        params, on_chip(jax.ShapeDtypeStruct((1, eng["prefill_chunk"]), jnp.int32)),
        kv(Lf, 8192), kv(Lf, 8192), ints, ints, cfg,
        kv(cfg.n_layers - Lf, 1024), kv(cfg.n_layers - Lf, 1024)).compile()
    total = _total(chunk) + sum(pools.values())
    assert total < USABLE
    assert total == pytest.approx(aot["prefill_chunk_1024_prefix_8192_bytes"], rel=0.01)


@pytest.mark.parametrize("fault", ["window_ignored", "yarn_left_out", "window_page_zeroed"])
def test_the_configurations_own_faults_read_not_ok(fault):
    """`window_faults.py` at the tiny size: a sample longer than the window
    cannot pass with the window ignored, nor with the plain rope on the full
    layers, nor with a page of the window layers' ring zeroed; the sound
    program passes the same comparison (the rehearsal)."""
    from chipbench import window_faults

    conf = tiny_cell()["config_file"]
    rows = list(window_faults.sweep(conf, [2**31 + 11], fault, on_chip=False))
    assert len(rows) == 1 and rows[0]["ok"] is False
    # at 64-wide random weights the scores are nearly flat, so the rope's
    # share of a logit is small (0.24 %): over the toy limit, not by much
    assert rows[0]["logits_rel_err_median"] > conf["check"]["logits_median_tol"]


@pytest.fixture
def workers_see_the_repo(monkeypatch):
    here = os.path.dirname(os.path.abspath(__file__))
    monkeypatch.setenv("PYTHONPATH", os.pathsep.join([harness.ROOT, here]))


def test_cell_rehearsal(tmp_path, workers_see_the_repo):
    cell = tiny_cell()
    args = argparse.Namespace(seed=2**31 + 9, seconds=2.0, trace=0)
    r = serve.run(cell, args, str(tmp_path), time.time(), on_chip=False)
    facts = r["facts"]
    assert facts["check"]["ok"] and facts["check"]["logits_rel_err_median"] < 1e-4
    assert facts["check"]["control_fails"] and facts["check"]["prompt_tokens"] == 300
    assert r["failed"] == 0 and r["attempted"] == 12
    assert facts["compiles_in_window"] == 0 and r["correct"]
    assert r["end_to_end"]["served_tok_s"] > 0
    records = [json.loads(line) for line in open(tmp_path / "requests.jsonl")]
    assert all(rec["status"] == "ok" for rec in records)
    assert sum(rec["prompt_tokens"] >= 200 for rec in records) == 3      # 3 : 1
    assert facts["stats1"]["prefill_chunks_run"] > facts["stats0"]["prefill_chunks_run"]
    # every page of both pools is free again once the answers have come
    s1 = facts["stats1"]
    assert s1["free_pages"] == s1["num_pages"] - 1
    assert s1["free_window_pages"] == s1["window_pages"] - 1 and s1["ring"] == 13
    names = ("kv_bytes_per_tok.longdoc", "decode_ctx_tok.longdoc", "kv_pool_used_pct.longdoc",
             "kv_bytes_per_held_tok.mixed", "window_ctx_tok.mixed",
             "ragged_window_attention_roofline_pct.mixed",
             "ragged_paged_attention_roofline_pct.mixed", "decode_occupancy.doc",
             "queue_wait_ms.doc", "prefill_latency_ms.doc")
    got = harness.read_layer_metrics({"per_layer": [
        m for m in harness.load_json(harness.ROOT, "BENCHMARK.json")["per_layer"]
        if m["name"] in names]}, facts)
    # no device trace on the CPU: the kernels' reader finds nothing to read
    assert set(got) == set(names) - {"ragged_window_attention_roofline_pct.mixed",
                                     "ragged_paged_attention_roofline_pct.mixed"}
    every_layer = 8 * 2 * 2 * 16 * 4                 # K and V of every layer, float32
    assert got["kv_bytes_per_tok.longdoc"]["value"] == every_layer
    # pages are granted for a row's whole life and its bucket, so a held token
    # costs more than its own bytes; rows past the window hold a ring on six
    # layers in eight, so long rows pull it under
    assert 0 < got["kv_bytes_per_held_tok.mixed"]["value"]
    assert 0 < got["window_ctx_tok.mixed"]["value"] <= got["decode_ctx_tok.longdoc"]["value"]
    assert 0 < got["kv_pool_used_pct.longdoc"]["value"] <= 100


@pytest.mark.parametrize("metric,found,layers", [
    ("ragged_window_attention_roofline_pct.mixed", ["ragged_window_attention.5"], 9),
    ("ragged_paged_attention_roofline_pct.mixed", ["ragged_paged_attention.7"], 3),
    ("ragged_paged_attention_roofline_pct.mixed", [], None)])
def test_roofline_reads_the_named_ops_and_the_counter(metric, found, layers):
    """The share is least time / the named op's device time, the work every
    layer of its kind; no op of that name (the parent's program, a run
    without a trace): nothing to read."""
    from chipbench.readers import gqa_decode_roofline as reader

    spec = harness.load_json(harness.BENCH_DIR, "layer_metrics", metric + ".json")
    work = spec["params"]["work"].split(".")[1]
    calls = {"grouped_matmul.75": {"calls": 8.0, "seconds": 7.0},
             **{name: {"calls": 4.0, "seconds": 2.0} for name in found}}
    facts = {"stats_t0": {"cache": {work: 1_000_000}},
             "stats_t1": {"cache": {work: 27_000_000}},
             "stats1": {"device": {"kind": "TPU v5 lite"}},
             "trace": {"kernel_calls": calls}}
    got = reader.read(facts, spec["params"])
    if not found:
        assert got is None and reader.read({}, spec["params"]) is None
        return
    cost = reader.gqa_decode_attention_cost(26_000_000 * layers, 32, 4, 128)
    assert cost["bytes"] == 26_000_000 * layers * 2 * 4 * 128 * 2
    assert cost["flops"] == 26_000_000 * layers * 32 * 2 * (128 + 128)
    least = max(cost["bytes"] / 819e9, cost["flops"] / 197e12)
    assert got == pytest.approx(100 * least / (2.0 * len(found)))
    assert 0 < got < 100 and facts[spec["params"]["op"] + "_bound"] == "memory"
