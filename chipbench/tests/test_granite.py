"""granite-4.0-h-micro: the configuration's sizes against the catalog's and by
the compiler's account for a described v5e, its plain reference against the
program at a tiny size, its faults, the CPU rehearsal of its cell, and the
state-update kernel's roofline reader on hand-made facts."""

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
from chipbench.reference import granite
from ray_tpu.models import transformer

CELL = "granite-4.0-h-micro.chat-saturated"
USABLE = 16_909_336_064  # device_memory.bytes_limit as the chip reads it
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def tiny_cell():
    """The cell's files at toy widths: the same keys, one period of ten
    layers in the published order, a chunk of 8, pages of 16."""
    cell = copy.deepcopy(harness.resolve_cell(CELL))
    conf, mix = cell["config_file"], cell["traffic_file"]
    conf["sizes"].update(d_model=64, n_layers=10, attn_layers=[5], n_heads=4, n_kv_heads=2,
                         d_head=64, d_ff=96, vocab_size=300, max_seq_len=256, ssm_heads=8,
                         ssm_d_head=16, ssm_d_state=16, ssm_chunk=8,
                         attention_multiplier=1 / 32)
    conf["program"].update(model_id="tiny", model_kwargs=dict(
        vocab_size=300, max_seq_len=256, dtype="float32", param_dtype="float32"))
    conf["engine"] = {"kv_layout": "paged", "page_size": 16, "max_slots": 4, "max_len": 256,
                      "min_bucket": 16, "num_pages": 40, "enable_prefix_cache": False}
    # float32 at toy widths reads 2e-7; the recurrent state is a small part
    # of a toy mixer's output (its pre-activations are 0.02 * sqrt(64)), so a
    # fault of the state reads 1e-4 here where the chip reads tens of percent
    conf["check"].update(sample_tokens=40, positions=6, logits_rel_tol=2e-5,
                         logits_median_tol=2e-5, served_gap_tol=1e-2)
    conf["ready_timeout_s"] = 300.0
    # every bucket and decode bound of the tiny mix
    mix.update(rate_rps=6.0, warmup_wave=2,
               warmup=[[10, 8], [20, 16], [40, 30], [70, 8], [100, 30]])
    mix["classes"][0]["prompt"].update(median=24, min=8, max=100)
    mix["classes"][0]["output"].update(median=8, min=2, max=24)
    return {**cell, "name": "tiny.chat", "run_seconds": 2}


def test_reference_agrees_with_the_program():
    conf = tiny_cell()["config_file"]
    cfg = program.transformer_config(conf["program"])
    p = program.init_params(cfg, 2**31 + 5)
    p = jax.tree.map(lambda x: x + 0.05 * jax.random.normal(
        jax.random.PRNGKey(7), x.shape, x.dtype), p)
    tokens = np.random.default_rng(0).integers(0, 300, 57, dtype=np.int32)
    logits, _ = transformer.forward(p, tokens[None], cfg)
    want, margin = granite.forward(p, jnp.asarray(tokens), conf["sizes"])
    assert float(jnp.abs(logits[0] - want).max() / jnp.abs(want).max()) < 1e-4
    assert margin.shape == (10, 57, 2) and json.dumps(np.asarray(margin).min().item())
    loss = granite.loss(p, jnp.asarray(tokens), conf["sizes"])
    assert abs(float(loss) - float(transformer.loss_fn(p, tokens[None], cfg))) < 1e-5


def test_the_published_sizes_are_the_catalogs():
    """Every key of the catalog's `config` is in the file with its value,
    nothing is reduced, and the program's configuration is built from them."""
    conf = harness.resolve_cell(CELL)["config_file"]
    if os.path.exists(CATALOG):
        row = next(r for r in map(json.loads, open(CATALOG))
                   if r["name"] == "granite-4.0-h-micro")
        assert conf["source"] == row["source_url"]
        assert {k: conf[k] for k in row["config"]} == row["config"]
    assert conf["reduced"] == [] and "reduced_from" not in conf
    assert next(iter(conf["assumed"])) == "ssm_state_dtype"      # float32 state first
    for line in conf["assumed"].values():
        assert len(line) > 40                                     # each with its reason
    cfg = program.transformer_config(conf["program"])
    assert (cfg.d_model, cfg.n_layers, cfg.n_heads, cfg.kv_heads, cfg.d_ff) == (
        conf["hidden_size"], conf["num_hidden_layers"], conf["num_attention_heads"],
        conf["num_key_value_heads"], conf["shared_intermediate_size"])
    assert cfg.head_dim == conf["hidden_size"] // conf["num_attention_heads"] == 64
    assert (cfg.vocab_size, cfg.norm_eps, cfg.tie_embeddings, cfg.pos) == (
        conf["vocab_size"], conf["rms_norm_eps"], conf["tie_word_embeddings"], "none")
    assert conf["position_embedding_type"] == "nope" and conf["num_local_experts"] == 0
    assert (cfg.embedding_multiplier, cfg.residual_multiplier, cfg.softmax_scale,
            cfg.logits_scaling) == (conf["embedding_multiplier"], conf["residual_multiplier"],
                                    conf["attention_multiplier"], conf["logits_scaling"])
    s = cfg.ssm
    assert (s.n_heads, s.d_head, s.d_state, s.d_conv, s.chunk) == (
        conf["mamba_n_heads"], conf["mamba_d_head"], conf["mamba_d_state"],
        conf["mamba_d_conv"], conf["mamba_chunk_size"])
    assert s.d_inner == conf["mamba_expand"] * conf["hidden_size"] and conf["mamba_n_groups"] == 1
    assert [("attention" if transformer.is_attn_layer(cfg, l) else "mamba")
            for l in range(cfg.n_layers)] == conf["layer_types"]
    sizes = conf["sizes"]
    assert sizes["attn_layers"] == [l for l, t in enumerate(conf["layer_types"])
                                    if t == "attention"] == [5, 15, 25, 35]
    assert (sizes["ssm_heads"], sizes["ssm_d_state"], sizes["norm_eps"]) == (
        s.n_heads, s.d_state, cfg.norm_eps)
    assert cfg.moe is None and cfg.window is None and cfg.kv_packed


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
    """The decode step at the file's slots and pool (state and pools aliased
    in place) and the largest prefill program (the 1,024-token bucket) beside
    state and pool: under what a v5e has, and what `aot` records."""
    from ray_tpu.models import decoding
    from ray_tpu.models import decoding_paged as dp

    conf = harness.resolve_cell(CELL)["config_file"]
    cfg, eng, aot = program.transformer_config(conf["program"]), conf["engine"], conf["aot"]
    params = on_chip(jax.eval_shape(lambda k: transformer.init(k, cfg), jax.random.PRNGKey(0)))
    weights = sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert cfg.num_params() == aot["parameters"] == 3_191_396_096
    # bfloat16 but a head's dt_bias, A_log and D of the 36 mixers: float32
    assert weights == aot["weights_bytes"] == 2 * cfg.num_params() + 2 * 36 * 3 * 64
    assert eng["num_pages"] == eng["max_slots"] * (eng["max_len"] // eng["page_size"]) + 1
    state = on_chip(jax.eval_shape(lambda: dp.init_paged_state(
        cfg, eng["max_slots"], eng["max_len"], eng["num_pages"], eng["page_size"])))
    nbytes = {k: int(np.prod(v.shape)) * v.dtype.itemsize for k, v in state.items()}
    assert state["ssm"].shape == (36, eng["max_slots"], 64, 64, 128)
    assert state["kp"].shape == (4, eng["num_pages"], 64, 4, 128)
    row = (nbytes["ssm"] + nbytes["conv"]) // eng["max_slots"]
    assert row == aot["state_bytes_per_slot"] == 75_497_472 + 940_032
    assert nbytes["ssm"] + nbytes["conv"] == aot["state_bytes"]
    pool = nbytes["kp"] + nbytes["vp"]
    assert pool == aot["pool_bytes"] and pool // eng["num_pages"] == aot["page_bytes"] == 524288
    assert aot["page_bytes"] // eng["page_size"] == aot["cache_bytes_per_token"] == 8192
    held = aot["state_bytes"] + pool
    step = dp.decode_step_paged_ragged.lower(params, state, cfg, 32, True).compile()
    assert "ssm_state_update" in step.as_text() and "ragged_paged_attention" in step.as_text()
    m = step.memory_analysis()
    assert m.alias_size_in_bytes >= held                          # all of it in place
    assert m.temp_size_in_bytes == pytest.approx(aot["decode_step_temp_bytes"], rel=0.05)
    assert _total(step) < USABLE
    assert _total(step) == pytest.approx(aot["decode_step_bytes"], rel=0.01)
    ints = on_chip(jax.ShapeDtypeStruct((), jnp.int32))
    prefill = decoding.prefill.lower(
        params, on_chip(jax.ShapeDtypeStruct((1, 1024), jnp.int32)), ints, cfg).compile()
    assert prefill.memory_analysis().temp_size_in_bytes == pytest.approx(
        aot["prefill_1024_temp_bytes"], rel=0.05)
    total = _total(prefill) + held
    assert total < USABLE
    assert total == pytest.approx(aot["prefill_1024_beside_state_bytes"], rel=0.01)


CPU_FAULTS = ["pad_advances_state", "state_not_inserted", "conv_tail_dropped", "gate_after_norm",
              "decay_left_out", "d_skip_left_out", "embedding_multiplier_left_out",
              "residual_multiplier_left_out", "logits_scaling_left_out",
              "attention_scale_rsqrt", "kv_page_zeroed"]


def test_the_configurations_faults_read_not_ok():
    """`ssm_faults.py`'s faults that float32 at a tiny size can tell, and
    `check.FAULTS`' one that applies, through `check.serve_check`: each reads
    not ok where the sound program passes the same comparison (the
    rehearsal). `ssm_state_bfloat16` and `rope_applied` move a toy float32
    model's logits by about what its tolerance allows and are read on the chip
    alone (the file's `check.faults`); here they have to RUN."""
    from chipbench import check, check_sweep, ssm_faults

    conf = tiny_cell()["config_file"]
    seed = 2**31 + 11
    planted = [f for f in CPU_FAULTS if f in ssm_faults.FAULTS]
    rows = list(ssm_faults.sweep(conf, [seed], planted, on_chip=False))
    rows += list(check_sweep.sweep(conf, [seed], "kv_page_zeroed", on_chip=False))
    assert [r["fault"] for r in rows] == CPU_FAULTS
    for r in rows:
        assert r["ok"] is False, r["fault"]
        assert r["logits_rel_err_median"] > conf["check"]["logits_median_tol"], r["fault"]
    assert set(ssm_faults.FAULTS) - set(CPU_FAULTS) == {"ssm_state_bfloat16", "rope_applied"}
    rest = list(ssm_faults.sweep(conf, [seed], ["ssm_state_bfloat16", "rope_applied"],
                                 on_chip=False))
    assert all(np.isfinite(r["logits_rel_err_median"]) and r["logits_rel_err_median"]
               > r["sound_median"] for r in rest)
    with pytest.raises(ValueError, match="no fault"):
        list(ssm_faults.sweep(conf, [seed], ["planes_shared"], on_chip=False))


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
    assert s1["free_pages"] == s1["num_pages"] - 1 and s1["loops"]["planes"] == 1
    cache = s1["cache"]
    assert s1["decode_slot_steps"] >= s1["decode_steps"] > 0
    # every per-layer metric the cell lists that needs no device trace
    got = harness.read_layer_metrics(cell, facts)
    assert set(m["name"] for m in cell["per_layer"]) - set(got) == {
        "serve_device_idle_pct.doc", "ssm_state_update_roofline_pct.chat",
        "ragged_paged_attention_roofline_pct.chat"}
    assert got["state_bytes_per_row.chat"]["value"] == 9 * (8 * 16 * 16 + 3 * 160) * 4
    assert got["kv_bytes_per_tok.longdoc"]["value"] == 1 * 2 * 2 * 64 * 4      # float32
    assert 0 < got["kv_pool_used_pct.longdoc"]["value"] <= 100
    assert got["decode_occupancy.doc"]["value"] >= 1 and got["decode_ctx_tok.longdoc"]["value"] > 0
    assert got["decode_pass_ms.doc"]["value"] > 0 and got["queue_wait_ms.doc"]["value"] >= 0


@pytest.mark.parametrize("found", [["ssm_state_update.6", "ssm_state_update.7"],
                                   ["ssm_state_update.6", "ssm_state_update.7",
                                    "ssm_state_update.11", "ssm_state_update.12"], []])
def test_roofline_reads_the_update_of_every_layer(found):
    """The state update's share of its roofline: the row-steps the engine
    counted, each in every one of the 36 state-space layers, a row's state
    read once and written once, against the device time of the ops of that
    name (two a compiled decode program: the layers before and after a
    period's attention layer); no such op (the parent's program, a run without
    a trace): nothing to read."""
    from chipbench.readers import ssm_state_update_roofline as reader

    spec = harness.load_json(harness.BENCH_DIR, "layer_metrics",
                             "ssm_state_update_roofline_pct.chat.json")
    conf = harness.load_json(harness.BENCH_DIR, "configs", "granite-4.0-h-micro.json")
    sizes = conf["sizes"]
    assert spec["params"] == {
        "op": "ssm_state_update", "work": "decode_slot_steps",
        "layers": sizes["n_layers"] - len(sizes["attn_layers"]), "heads": sizes["ssm_heads"],
        "head_dim": sizes["ssm_d_head"], "d_state": sizes["ssm_d_state"],
        "state_bytes_per_el": 4}
    cost = reader.ssm_state_update_cost(1, 64, 64, 128)
    assert cost == {"bytes": 4_194_304, "flops": 2_621_440}       # a row, a layer
    calls = {name: {"calls": 1000.0, "seconds": 1.5} for name in found}
    # the slice's two readings lie 2.0 s apart on the engine's clock and the
    # device's trace holds 2.5 s: 640 row-steps between them are 800 a slice
    facts = {"stats1": {"device": {"kind": "TPU v5 lite"}},
             "stats_t0": {"decode_slot_steps": 5_000, "loop": {"thread_s": 62.5}},
             "stats_t1": {"decode_slot_steps": 5_640, "loop": {"thread_s": 64.5}},
             "trace": {"kernel_calls": calls, "window_s": 2.5}}
    got = reader.read(facts, spec["params"])
    if not found:
        assert got is None and reader.read({}, spec["params"]) is None
        return
    assert facts["ssm_state_update_row_steps"] == pytest.approx(800)
    least = 800 * 36 * 4_194_304 / 819e9                           # the state, in and out
    assert got == pytest.approx(100 * least / (1.5 * len(found)), rel=1e-6)
    assert 0 < got < 100 and facts["ssm_state_update_bound"] == "memory"
    # a program without the counter (the parent's): nothing to read
    assert reader.read({**facts, "stats_t0": {"loop": {"thread_s": 62.5}}},
                       spec["params"]) is None
