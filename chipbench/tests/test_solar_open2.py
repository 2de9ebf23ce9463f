"""solar-open2-250b: the configuration's sizes against the catalog's and by
the compiler's account for a described v5e, its faults at a tiny size, the CPU
rehearsal of its cell, and its metric files against its sizes."""

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
from ray_tpu.models import transformer

CELL = "solar-open2-250b.reasondoc-saturated"
USABLE = 16_909_336_064  # device_memory.bytes_limit as the chip reads it
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def tiny_cell():
    """The cell's files at toy widths: the same keys, two periods of four
    layers, a share of 8 of 64 experts, a scan chunk of 32, pages of 16."""
    cell = copy.deepcopy(harness.resolve_cell(CELL))
    conf, mix = cell["config_file"], cell["traffic_file"]
    conf["sizes"].update(d_model=64, n_layers=8, gqa_layers=[0, 4], n_heads=4, n_kv_heads=2,
                         d_head=16, kda_heads=4, kda_head_dim=16, d_ff=32, vocab_size=300,
                         num_experts=64, top_k=4, experts_held=list(range(16, 24)),
                         max_seq_len=256)
    conf["program"].update(model_id="tiny", model_kwargs=dict(
        vocab_size=300, max_seq_len=256, dtype="float32", param_dtype="float32",
        experts_held=8, first_expert=16, select_bias_init_std=0.02))
    conf["engine"] = {"kv_layout": "paged", "page_size": 16, "max_slots": 4, "max_len": 256,
                      "min_bucket": 16, "num_pages": 64, "prefill_chunk": 32,
                      "enable_prefix_cache": False}
    conf["check"].update(sample_tokens=75, positions=6, logits_rel_tol=1e-4,
                         logits_median_tol=1e-4, served_gap_tol=1e-2, max_tie_seconds=20.0)
    conf["ready_timeout_s"] = 300.0
    # every bucket, every (span, tail) pair and every decode bound of the tiny mix
    mix.update(rate_rps=5.0, warmup_wave=2,
               warmup=[[10, 4], [20, 14], [40, 30], [70, 30], [100, 30], [140, 30]])
    mix["classes"][0]["prompt"].update(median=24, min=8, max=60)
    mix["classes"][0]["output"].update(median=8, min=2, max=24)
    mix["classes"][1]["prompt"].update(median=90, min=70, max=140)
    mix["classes"][1]["output"].update(median=6, min=2, max=12)
    return {**cell, "name": "tiny.reasondoc", "run_seconds": 2}


def test_the_published_sizes_are_the_catalogs():
    """Every key of the catalog's `config` is in the file with its value but
    the three that `reduced` lists, each with what it was cut from, and the
    program's configuration is built from them."""
    conf = harness.resolve_cell(CELL)["config_file"]
    assert conf["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    if os.path.exists(CATALOG):
        row = next(r for r in map(json.loads, open(CATALOG)) if r["name"] == "Solar-Open2-250B")
        assert conf["source"] == row["source_url"]
        assert {k: conf[k] for k in row["config"] if k not in conf["reduced"]} == {
            k: v for k, v in row["config"].items() if k not in conf["reduced"]}
        assert conf["reduced_from"] == {k: row["config"][k] for k in conf["reduced"]}
    for line in conf["assumed"].values():
        assert len(line) > 40                                     # each with its reason
    cfg = program.transformer_config(conf["program"])
    assert (cfg.d_model, cfg.n_layers, cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.d_ff) == (
        conf["hidden_size"], conf["num_hidden_layers"], conf["num_attention_heads"],
        conf["num_key_value_heads"], conf["head_dim"], conf["moe_intermediate_size"])
    assert (cfg.vocab_size, cfg.norm_eps, cfg.tie_embeddings, cfg.pos, cfg.attn_gate) == (
        conf["vocab_size"], conf["rms_norm_eps"], conf["tie_word_embeddings"], "none",
        conf["use_gqa_gate"])
    assert conf["use_rope"] is False and conf["first_k_dense_replace"] == 0
    lin, s = conf["linear_attn_config"], cfg.ssm
    assert cfg.kda and (s.n_heads, s.d_head, s.d_conv, s.period, s.attn_at) == (
        lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"],
        conf["gqa_interval"] + 1, 0)
    assert [l for l in range(cfg.n_layers) if transformer.is_attn_layer(cfg, l)] == [
        l for l in conf["gqa_layers"] if l < cfg.n_layers] == conf["sizes"]["gqa_layers"]
    moe, held = cfg.moe, conf["experts_held"]
    assert (moe.num_experts, moe.top_k, moe.n_shared_experts, moe.routed_scaling_factor) == (
        conf["router_outputs"], conf["num_experts_per_tok"], conf["n_shared_experts"],
        conf["routed_scaling_factor"]) == (320, 8, 1, 1)
    assert (moe.held, moe.first_expert, held["of"]) == (
        conf["n_routed_experts"], held["first"], moe.num_experts)
    assert held["count"] * held["chips_sharing_a_layer"] == held["of"]
    assert conf["sizes"]["experts_held"] == list(range(held["first"], held["first"] + 40))
    # the floors of a cut: a whole period, 8 experts or more, an eighth of the vocabulary
    assert cfg.n_layers % s.period == 0 and moe.held >= 8
    assert conf["vocab_size"] * 8 >= conf["reduced_from"]["vocab_size"]


def test_the_metric_files_are_the_configurations_sizes():
    conf = harness.load_json(harness.BENCH_DIR, "configs", "solar-open2-250b.json")
    sizes = conf["sizes"]

    def spec(name):
        return harness.load_json(harness.BENCH_DIR, "layer_metrics",
                                 name + ".reasondoc.json")["params"]

    assert spec("kda_state_update_roofline_pct") == {
        "op": "kda_state_update", "work": "decode_slot_steps",
        "layers": sizes["n_layers"] - len(sizes["gqa_layers"]), "heads": sizes["kda_heads"],
        "head_dim": sizes["kda_head_dim"], "d_state": sizes["kda_head_dim"],
        "state_bytes_per_el": 4}
    experts = spec("expert_grouped_matmul_roofline_pct")
    assert (experts["d_model"], experts["d_ff"]) == (sizes["d_model"], sizes["d_ff"])
    attn = spec("ragged_paged_attention_roofline_pct")
    assert (attn["layers"], attn["heads"], attn["kv_heads"], attn["head_dim"]) == (
        len(sizes["gqa_layers"]), sizes["n_heads"], sizes["n_kv_heads"], sizes["d_head"])
    # the decode step of the file's slots sorts its held slots (the grouped
    # product's kernel is then every expert call's, which its reader presumes)
    from ray_tpu import ops

    cfg = program.transformer_config(conf["program"])
    slots = conf["engine"]["max_slots"]
    assert ops.sorted_pays(slots, cfg.moe.slots_a_held_expert(slots))


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
    """The decode step at the file's slots and pool (state and pool aliased in
    place, the update kernel and the held experts' grouped product in it) and
    the largest program, a 2,048-token chunk over a 32,768-token prefix,
    beside state and pool: under what a v5e has, and what `aot` records."""
    from ray_tpu.models import decoding
    from ray_tpu.models import decoding_paged as dp

    conf = harness.resolve_cell(CELL)["config_file"]
    cfg, eng, aot = program.transformer_config(conf["program"]), conf["engine"], conf["aot"]
    params = on_chip(jax.eval_shape(lambda k: transformer.init(k, cfg), jax.random.PRNGKey(0)))
    weights = sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert cfg.num_params() == aot["parameters"] == 3_308_377_920
    # bfloat16 but the select bias (4 x 320) and a KDA layer's dt_bias and A_log: float32
    assert weights == aot["weights_bytes"] == 2 * cfg.num_params() + 2 * (
        4 * 320 + 3 * (8192 + 64))
    state = on_chip(jax.eval_shape(lambda: dp.init_paged_state(
        cfg, eng["max_slots"], eng["max_len"], eng["num_pages"], eng["page_size"])))
    nbytes = {k: int(np.prod(v.shape)) * v.dtype.itemsize for k, v in state.items()}
    assert state["ssm"].shape == (3, eng["max_slots"], 64, 128, 128)
    assert state["ssm"].dtype == jnp.float32
    assert state["kp"].shape == (1, eng["num_pages"], 64, 8, 128)
    assert (nbytes["ssm"] + nbytes["conv"]) // eng["max_slots"] == aot[
        "state_bytes_per_slot"] == 12_582_912 + 442_368
    pool = nbytes["kp"] + nbytes["vp"]
    assert pool == aot["pool_bytes"] and pool // eng["num_pages"] == aot["page_bytes"]
    assert aot["page_bytes"] // eng["page_size"] == aot["cache_bytes_per_token"] == 4096
    held = nbytes["ssm"] + nbytes["conv"] + pool
    step = dp.decode_step_paged_ragged.lower(params, state, cfg, 32, True).compile()
    text = step.as_text()
    assert all(k in text for k in ("kda_state_update", "ragged_paged_attention",
                                   "grouped_matmul"))
    m = step.memory_analysis()
    assert m.alias_size_in_bytes >= held                          # all of it in place
    assert _total(step) < USABLE
    assert _total(step) == pytest.approx(aot["decode_step_bytes"], rel=0.01)
    i32 = on_chip(jax.ShapeDtypeStruct((), jnp.int32))
    tokens = on_chip(jax.ShapeDtypeStruct((1, 2048), jnp.int32))
    kv = jax.eval_shape(lambda p, t, n: decoding.prefill(p, t, n, cfg)[1], params, tokens, i32)
    prefix = on_chip(jax.ShapeDtypeStruct((1, 32768, 8, 128), jnp.bfloat16))
    chunk = dp.prefill_with_prefix.lower(
        params, tokens, prefix, prefix, i32, i32, cfg,
        row_state=on_chip({"ssm": kv["ssm"], "conv": kv["conv"]}), kernel=True).compile()
    assert "flash_prefix_attention" in chunk.as_text()
    assert chunk.memory_analysis().temp_size_in_bytes == pytest.approx(
        aot["prefill_chunk_2048_prefix_32768_temp_bytes"], rel=0.05)
    total = _total(chunk) + held
    assert total < USABLE
    assert total == pytest.approx(aot["prefill_chunk_2048_prefix_32768_beside_state_bytes"],
                                  rel=0.01)


CPU_FAULTS = ["delta_left_out", "decay_per_head", "beta_not_doubled", "qk_l2norm_left_out",
              "out_gate_left_out", "gqa_gate_left_out", "weights_over_held",
              "absent_expert_wrapped", "state_not_carried_between_chunks",
              "conv_tail_dropped_between_chunks"]


def test_the_configurations_faults_read_not_ok():
    """`solar_faults.py`'s faults through `check.serve_check` at a tiny size in
    float32: each reads not ok where the sound program, and the sound chunked
    path's tokens, pass the same comparison. `state_bfloat16` moves a toy
    float32 model's logits by less than a wrong program does and is read on
    the chip (the file's `check.faults`); here it has to RUN."""
    from chipbench import solar_faults

    conf = tiny_cell()["config_file"]
    seed = 2**31 + 11
    rows = list(solar_faults.sweep(conf, [seed], CPU_FAULTS + ["chunks_sound", "state_bfloat16"],
                                   on_chip=False, tie_seconds=5.0))
    assert [r["fault"] for r in rows] == CPU_FAULTS + ["chunks_sound", "state_bfloat16"]
    by = {r["fault"]: r for r in rows}
    assert all(r["sound_ok"] for r in rows)
    for fault in CPU_FAULTS:
        assert by[fault]["ok"] is False, fault
    assert by["chunks_sound"]["ok"] is True and by["chunks_sound"]["served_same"] == 6
    for fault in solar_faults.CHUNK_FAULTS:  # the program is sound, the tokens are not
        assert by[fault]["served_gap_max"] > conf["check"]["served_gap_tol"], fault
    assert np.isfinite(by["state_bfloat16"]["logits_rel_err_median"])
    assert by["state_bfloat16"]["logits_rel_err_median"] > by["state_bfloat16"]["sound_median"]
    assert set(solar_faults.FAULTS) - set(CPU_FAULTS) == {"state_bfloat16"}
    with pytest.raises(ValueError, match="no fault"):
        list(solar_faults.sweep(conf, [seed], ["planes_shared"], on_chip=False))


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
    assert facts["check"]["control_fails"] and facts["check"]["prompt_tokens"] == 75
    assert r["failed"] == 0 and r["attempted"] == 10
    assert facts["compiles_in_window"] == 0 and r["correct"]
    assert r["end_to_end"]["served_tok_s"] > 0
    json.dumps(facts)                                            # the result line stays JSON
    s1 = facts["stats1"]
    assert s1["free_pages"] == s1["num_pages"] - 1 and s1["loops"]["planes"] == 2
    assert s1["decode_slot_steps"] >= s1["decode_steps"] > 0
    # every per-layer metric the cell lists that needs no device trace
    got = harness.read_layer_metrics(cell, facts)
    assert set(m["name"] for m in cell["per_layer"]) - set(got) == {
        "serve_device_idle_pct.doc", "kda_state_update_roofline_pct.reasondoc",
        "ragged_paged_attention_roofline_pct.reasondoc",
        "expert_grouped_matmul_roofline_pct.reasondoc"}
    assert got["state_bytes_per_row.chat"]["value"] == 6 * (4 * 16 * 16 + 3 * 192) * 4
    assert got["kv_bytes_per_tok.longdoc"]["value"] == 2 * 2 * 2 * 16 * 4      # float32
    assert 0 < got["kda_scan_pad_pct.reasondoc"]["value"] < 100
    assert 0 < got["expert_slots_held_pct.agent"]["value"] < 100
    assert got["expert_rows_per_group.agent"]["value"] >= 1
    assert got["decode_occupancy.doc"]["value"] >= 1 and got["prefill_chunks_per_s.doc"]["value"] > 0
    assert got["decode_pass_ms.doc"]["value"] > 0 and got["queue_wait_ms.doc"]["value"] >= 0
