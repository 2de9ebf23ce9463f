"""ouro-2.6b: the configuration's sizes against the catalog's and by the
compiler's account for a described v5e, its plain reference against the
program at a tiny size, its faults, and the CPU rehearsal of its cell."""

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
from chipbench.reference import ouro
from ray_tpu.models import transformer

CELL = "ouro-2.6b.reason-saturated"
USABLE = 16_909_336_064  # device_memory.bytes_limit as the chip reads it (PR 35, every call)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def tiny_cell():
    """The cell's files at toy widths: the same keys, 6 layers run 3 times,
    pages of 16."""
    cell = copy.deepcopy(harness.resolve_cell(CELL))
    conf, mix = cell["config_file"], cell["traffic_file"]
    conf["sizes"].update(d_model=64, n_layers=6, n_passes=3, n_heads=4, n_kv_heads=4,
                         d_head=16, d_ff=96, vocab_size=300, max_seq_len=256)
    conf["program"].update(model_id="tiny", model_kwargs=dict(
        vocab_size=300, max_seq_len=256, dtype="float32", param_dtype="float32"))
    conf["engine"] = {"kv_layout": "paged", "page_size": 16, "max_slots": 4, "max_len": 256,
                      "min_bucket": 16, "num_pages": 40, "enable_prefix_cache": False}
    conf["check"].update(sample_tokens=40, positions=6, logits_rel_tol=2e-3,
                         logits_median_tol=2e-3, served_gap_tol=1e-2)
    conf["ready_timeout_s"] = 300.0
    # every bucket and decode bound of the tiny mix
    mix.update(rate_rps=6.0, warmup_wave=2,
               warmup=[[10, 8], [20, 16], [40, 30], [70, 8], [100, 30]])
    mix["classes"][0]["prompt"].update(median=24, min=8, max=100)
    mix["classes"][0]["output"].update(median=8, min=2, max=24)
    return {**cell, "name": "tiny.reason", "run_seconds": 2}


def test_reference_agrees_with_the_program():
    conf = tiny_cell()["config_file"]
    cfg = program.transformer_config(conf["program"])
    p = program.init_params(cfg, 2**31 + 5)
    p = jax.tree.map(lambda x: x + 0.05 * jax.random.normal(
        jax.random.PRNGKey(7), x.shape, x.dtype), p)
    tokens = np.random.default_rng(0).integers(0, 300, 57, dtype=np.int32)
    logits, _ = transformer.forward(p, tokens[None], cfg)
    want, margin = ouro.forward(p, jnp.asarray(tokens), conf["sizes"])
    assert float(jnp.abs(logits[0] - want).max() / jnp.abs(want).max()) < 1e-4
    assert margin.shape == (6, 57, 2) and json.dumps(np.asarray(margin).min().item())


def test_the_published_sizes_are_the_catalogs():
    """Every key of the catalog's `config` is in the file with its value,
    nothing is reduced, and the program's configuration is built from them."""
    conf = harness.resolve_cell(CELL)["config_file"]
    if os.path.exists(CATALOG):
        row = next(r for r in map(json.loads, open(CATALOG)) if r["name"] == "Ouro-2.6B")
        assert conf["source"] == row["source_url"]
        assert {k: conf[k] for k in row["config"]} == row["config"]
    assert conf["reduced"] == [] and "reduced_from" not in conf
    for line in ("norms", "norm_between_passes", "cache_index", "exit_gate"):
        assert "as the author of ISSUE 35 knew" in conf["assumed"][line], line
    cfg = program.transformer_config(conf["program"])
    assert (cfg.d_model, cfg.n_layers, cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.d_ff) == (
        conf["hidden_size"], conf["num_hidden_layers"], conf["num_attention_heads"],
        conf["num_key_value_heads"], conf["head_dim"], conf["intermediate_size"])
    assert (cfg.n_passes, cfg.vocab_size, cfg.rope_theta, cfg.norm_eps, cfg.tie_embeddings) == (
        conf["total_ut_steps"], conf["vocab_size"], conf["rope_theta"], conf["rms_norm_eps"],
        conf["tie_word_embeddings"])
    assert cfg.sandwich_norms and cfg.exit_gate and cfg.window is None and cfg.moe is None
    assert set(conf["layer_types"]) == {"full_attention"} and len(conf["layer_types"]) == 48
    sizes = conf["sizes"]
    assert (sizes["n_layers"], sizes["n_passes"], sizes["rope_theta"], sizes["norm_eps"]) == (
        cfg.n_layers, cfg.n_passes, cfg.rope_theta, cfg.norm_eps)
    with pytest.raises(ValueError, match="leave the loop"):
        program.transformer_config({**conf["program"], "model_kwargs": {
            **conf["program"]["model_kwargs"], "early_exit_threshold": 0.5}})


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
    """The decode step at the file's pool (aliased in place) and the largest
    prefill program (the 512-token bucket, whose 192 planes of keys and
    values are 0.8 GB) beside the pool: under what a v5e has, and what `aot`
    records."""
    from ray_tpu.models import decoding
    from ray_tpu.models import decoding_paged as dp

    conf = harness.resolve_cell(CELL)["config_file"]
    cfg, eng, aot = program.transformer_config(conf["program"]), conf["engine"], conf["aot"]
    params = on_chip(jax.eval_shape(lambda k: transformer.init(k, cfg), jax.random.PRNGKey(0)))
    weights = sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert weights == aot["weights_bytes"] == 2 * cfg.num_params()
    state = on_chip(jax.eval_shape(lambda: dp.init_paged_state(
        cfg, eng["max_slots"], eng["max_len"], eng["num_pages"], eng["page_size"])))
    assert state["kp"].shape == (192, eng["num_pages"], 64, 16, 128)
    pool = sum(int(np.prod(state[k].shape)) * 2 for k in ("kp", "vp"))
    assert pool == aot["pool_bytes"] and pool // eng["num_pages"] == aot["page_bytes"] == 100663296
    assert aot["page_bytes"] // eng["page_size"] == aot["cache_bytes_per_token"] == 1572864
    step = dp.decode_step_paged_ragged.lower(params, state, cfg, 16, True).compile()
    assert "ragged_paged_attention" in step.as_text()
    m = step.memory_analysis()
    assert m.alias_size_in_bytes >= pool                         # both pools in place
    assert m.temp_size_in_bytes == pytest.approx(aot["decode_step_temp_bytes"], rel=0.02)
    assert _total(step) < USABLE
    assert _total(step) == pytest.approx(aot["decode_step_bytes"], rel=0.01)
    ints = on_chip(jax.ShapeDtypeStruct((), jnp.int32))
    prefill = decoding.prefill.lower(
        params, on_chip(jax.ShapeDtypeStruct((1, 512), jnp.int32)), ints, cfg).compile()
    total = _total(prefill) + pool
    assert total < USABLE
    assert total == pytest.approx(aot["prefill_512_beside_pool_bytes"], rel=0.01)


@pytest.mark.parametrize("fault", ["planes_shared", "one_pass", "pass_norm_left_out",
                                   "sandwich_left_out", "rope_theta_1e4", "kv_page_zeroed"])
def test_the_configurations_faults_read_not_ok(fault):
    """`loop_faults.py`'s four and `check.FAULTS`' two that apply, at the tiny
    size through `check.serve_check`: each reads not ok where the sound
    program passes the same comparison (the rehearsal)."""
    from chipbench import check, check_sweep, loop_faults

    conf = tiny_cell()["config_file"]
    seed = 2**31 + 11
    if fault in loop_faults.FAULTS:
        rows = list(loop_faults.sweep(conf, [seed], fault, on_chip=False))
    else:
        assert fault in check.FAULTS
        rows = list(check_sweep.sweep(conf, [seed], fault, on_chip=False))
    assert len(rows) == 1 and rows[0]["ok"] is False
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
    assert facts["check"]["control_fails"] and facts["check"]["prompt_tokens"] == 40
    assert facts["check"]["ties_tried"] == 0 and facts["check"]["joint_trials"] == 0
    assert r["failed"] == 0 and r["attempted"] == 12
    assert facts["compiles_in_window"] == 0 and r["correct"]
    assert r["end_to_end"]["served_tok_s"] > 0
    json.dumps(facts)                                            # the result line stays JSON
    s1 = facts["stats1"]
    assert s1["free_pages"] == s1["num_pages"] - 1
    assert s1["loops"]["passes"] == 3 and s1["loops"]["planes"] == 18
    # every per-layer metric the cell lists that needs no device trace
    got = harness.read_layer_metrics(cell, facts)
    assert set(m["name"] for m in cell["per_layer"]) - set(got) == {
        "serve_device_idle_pct.doc", "ragged_paged_attention_roofline_pct.reason"}
    assert got["loop_passes_per_step.reason"]["value"] == 3.0
    assert got["kv_bytes_per_tok.longdoc"]["value"] == 18 * 2 * 4 * 16 * 4     # float32
    assert 0 < got["kv_pool_used_pct.longdoc"]["value"] <= 100
    assert got["tpot_p50_ms.reason"]["value"] > 0 and got["step_host_ms.reason"]["value"] > 0
    assert got["step_device_wait_ms.reason"]["value"] > 0
    assert got["decode_occupancy.doc"]["value"] >= 1 and got["decode_ctx_tok.longdoc"]["value"] > 0


@pytest.mark.parametrize("found", [["ragged_paged_attention.5"],
                                   ["ragged_paged_attention.5", "ragged_paged_attention.9"], []])
def test_roofline_reads_the_launch_of_every_plane(found):
    """The ragged launch's share of its roofline in this cell: the positions
    the engine counted, attended over in each of the 192 planes by 16 query
    heads on 16 KV heads, against the device time of the ops of that name
    (one a compiled decode program); no such op (the parent's program, a run
    without a trace): nothing to read."""
    from chipbench.readers import gqa_decode_roofline as reader

    spec = harness.load_json(harness.BENCH_DIR, "layer_metrics",
                             "ragged_paged_attention_roofline_pct.reason.json")
    conf = harness.load_json(harness.BENCH_DIR, "configs", "ouro-2.6b.json")
    sizes = conf["sizes"]
    assert spec["params"] == {
        "op": "ragged_paged_attention", "work": "cache.context_tokens",
        "layers": sizes["n_passes"] * sizes["n_layers"], "heads": sizes["n_heads"],
        "kv_heads": sizes["n_kv_heads"], "head_dim": sizes["d_head"]}
    calls = {name: {"calls": 192.0, "seconds": 10.0} for name in found}
    facts = {"stats_t0": {"cache": {"context_tokens": 100_000}},
             "stats_t1": {"cache": {"context_tokens": 2_100_000}},
             "stats1": {"device": {"kind": "TPU v5 lite"}},
             "trace": {"kernel_calls": calls}}
    got = reader.read(facts, spec["params"])
    if not found:
        assert got is None and reader.read({}, spec["params"]) is None
        return
    # a token's keys and values in every plane, read once: the cache's bytes a token
    least = 2_000_000 * conf["aot"]["cache_bytes_per_token"] / 819e9
    assert got == pytest.approx(100 * least / (10.0 * len(found)))
    assert 0 < got < 100 and facts["ragged_paged_attention_bound"] == "memory"
