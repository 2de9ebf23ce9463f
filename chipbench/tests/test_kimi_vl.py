"""kimi-vl-a3b: the configuration's sizes by the compiler's account for a
described v5e, its plain reference against the program at a tiny size, and
the CPU rehearsal of its cell."""

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
from chipbench.reference import kimi_vl
from ray_tpu.models import transformer

CELL = "kimi-vl-a3b.longdoc-saturated"
USABLE = 15.49e9  # 15.75 GB of HBM less 0.26 GB the runtime reserves


def tiny_cell():
    """The cell's files at toy widths: the same keys, two dense-free kinds of
    layer, a latent cache of 128 lanes."""
    cell = copy.deepcopy(harness.resolve_cell(CELL))
    conf, mix = cell["config_file"], cell["traffic_file"]
    small = dict(d_model=64, n_layers=3, n_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
                 qk_rope_head_dim=8, v_head_dim=16, d_ff=32, d_ff_dense=96, vocab_size=300,
                 max_seq_len=512)
    conf["sizes"].update(small, num_experts=8, top_k=3)
    kw = conf["program"]["model_kwargs"]
    kw.update(small, dtype="float32", param_dtype="float32")
    kw["moe"].update(num_experts=8, top_k=3, select_bias_init_std=0.05)
    conf["engine"] = {"kv_layout": "paged", "page_size": 16, "max_slots": 4, "max_len": 256,
                      "min_bucket": 32, "num_pages": 64, "prefill_chunk": 64,
                      "enable_prefix_cache": True}
    conf["check"].update(sample_tokens=80, positions=6, logits_rel_tol=2e-3,
                         logits_median_tol=2e-3, served_gap_tol=1e-2, router_tie=1e-5)
    conf["ready_timeout_s"] = 300.0
    mix.update(rate_rps=5.0, warmup=[[70, 4], [100, 4], [150, 4], [170, 4]], warmup_wave=2)
    mix["classes"][0]["prompt"].update(median=100, min=66, max=180)
    mix["classes"][0]["output"].update(min=2, max=6)
    return {**cell, "name": "tiny.longdoc", "run_seconds": 2}


def test_reference_agrees_with_the_program():
    conf = tiny_cell()["config_file"]
    cfg = program.transformer_config(conf["program"])
    p = program.init_params(cfg, 2**31 + 5)
    p = jax.tree.map(lambda x: x + 0.01 * jax.random.normal(
        jax.random.PRNGKey(7), x.shape, x.dtype), p)  # norms away from one
    tokens = np.random.default_rng(0).integers(0, 300, 48, dtype=np.int32)
    logits, _ = transformer.forward(p, tokens[None], cfg)
    want, margin = kimi_vl.forward(p, jnp.asarray(tokens), conf["sizes"])
    assert margin.shape == (3, 48, 2) and bool(jnp.isinf(margin[0]).all())
    assert bool((margin[1:] >= 0).all())
    assert float(jnp.abs(logits[0] - want).max() / jnp.abs(want).max()) < 1e-4


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


def test_the_steps_fit_beside_the_weights_and_match_the_file(on_chip):
    """The decode step at the file's `num_pages`, and the largest prefill
    program (a 1024-token chunk on a full 16,384-token latent prefix) with
    the pool it runs beside: under what a v5e has, and what `aot` records."""
    from ray_tpu.models import decoding_paged as dp

    conf = harness.resolve_cell(CELL)["config_file"]
    cfg, eng, aot = program.transformer_config(conf["program"]), conf["engine"], conf["aot"]
    params = on_chip(jax.eval_shape(lambda k: transformer.init(k, cfg), jax.random.PRNGKey(0)))
    weights = sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert weights == aot["weights_bytes"]
    state = on_chip(jax.eval_shape(lambda: dp.init_paged_state(
        cfg, eng["max_slots"], eng["max_len"], eng["num_pages"], eng["page_size"])))
    pool = int(np.prod(state["kp"].shape)) * 2
    assert "vp" not in state and pool == aot["pool_bytes"]
    assert pool // (eng["num_pages"] * eng["page_size"]) == aot["cache_bytes_per_token"] <= 11520
    step = dp.decode_step_paged_ragged.lower(params, state, cfg, 256, True).compile()
    assert step.as_text().count("tpu_custom_call") >= 2
    assert _total(step) < USABLE
    assert _total(step) == pytest.approx(aot["decode_step_bytes"], rel=0.01)
    assert step.memory_analysis().temp_size_in_bytes < 64 * 2**20    # the pool is held once
    ints = on_chip(jax.ShapeDtypeStruct((), jnp.int32))
    chunk = dp.prefill_with_prefix.lower(
        params, on_chip(jax.ShapeDtypeStruct((1, eng["prefill_chunk"]), jnp.int32)),
        on_chip(jax.ShapeDtypeStruct((cfg.n_layers, 16384, cfg.latent_lanes), cfg.dtype)),
        None, ints, ints, cfg).compile()
    assert _total(chunk) + pool < USABLE
    assert _total(chunk) + pool == pytest.approx(aot["prefill_chunk_1024_prefix_16384_bytes"],
                                                 rel=0.01)


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
    assert facts["check"]["control_fails"]
    assert r["failed"] == 0 and r["attempted"] == 10
    assert facts["compiles_in_window"] == 0 and r["correct"]
    assert r["end_to_end"]["served_tok_s"] > 0
    records = [json.loads(line) for line in open(tmp_path / "requests.jsonl")]
    assert all(rec["status"] == "ok" for rec in records)
    # every document went through chunks that attend over a latent prefix
    assert facts["stats1"]["prefill_chunks_run"] > facts["stats0"]["prefill_chunks_run"]
    names = ("kv_bytes_per_tok.longdoc", "decode_ctx_tok.longdoc",
             "prefix_expand_tok.longdoc", "kv_pool_used_pct.longdoc",
             "ragged_latent_attention_roofline_pct.longdoc", "decode_occupancy.doc",
             "queue_wait_ms.doc", "prefill_latency_ms.doc")
    got = harness.read_layer_metrics({"per_layer": [
        m for m in harness.load_json(harness.ROOT, "BENCHMARK.json")["per_layer"]
        if m["name"] in names]}, facts)
    # no device trace on the CPU: the kernel's reader finds nothing to read
    assert set(got) == set(names) - {"ragged_latent_attention_roofline_pct.longdoc"}
    assert got["kv_bytes_per_tok.longdoc"]["value"] == 3 * 128 * 4   # a latent row a layer
    assert got["decode_ctx_tok.longdoc"]["value"] > 66
    assert got["prefix_expand_tok.longdoc"]["value"] >= 32
    assert 0 < got["kv_pool_used_pct.longdoc"]["value"] <= 100


@pytest.mark.parametrize("found,layers", [(["ragged_latent_attention.10"], 8),
                                          (["ragged_latent_attention.10",
                                            "ragged_latent_attention.3"], 9), ([], None)])
def test_kernel_roofline_reads_the_named_op_and_the_counter(found, layers):
    """The share is least time / the named ops' device time over the traced
    slice; a layer scan whose op did not run takes its layers' work out of
    the count; no op of that name (the parent's program, a run without a
    trace): nothing to read."""
    from chipbench import kernel_costs
    from chipbench.readers import kernel_roofline

    spec = harness.load_json(harness.BENCH_DIR, "layer_metrics",
                             "ragged_latent_attention_roofline_pct.longdoc.json")
    calls = {"grouped_matmul.24": {"calls": 8.0, "seconds": 7.0},
             **{name: {"calls": 4.0, "seconds": 2.0} for name in found}}
    facts = {"stats_t0": {"cache": {"context_tokens": 1_000_000}},
             "stats_t1": {"cache": {"context_tokens": 27_000_000}},
             "stats1": {"device": {"kind": "TPU v5 lite"}},
             "trace": {"kernel_calls": calls}}
    got = kernel_roofline.read(facts, spec["params"])
    if not found:
        assert got is None and kernel_roofline.read({}, spec["params"]) is None
        return
    cost = kernel_costs.latent_decode_attention_cost(26_000_000, layers, heads=16,
                                                     row_values=576, value_values=512)
    assert cost["bytes"] == 26_000_000 * layers * 576 * 2
    assert cost["flops"] == 26_000_000 * layers * 16 * 2 * (576 + 512)
    least = max(cost["bytes"] / 819e9, cost["flops"] / 197e12)
    assert got == pytest.approx(100 * least / (2.0 * len(found)))
    assert 0 < got < 100 and facts["ragged_latent_attention_bound"] == "memory"
