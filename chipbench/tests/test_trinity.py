"""trinity-large-preview: the configuration's file against the catalog's row
and by the compiler's account for a described v5e, its plain reference against
the program at a tiny size (a share of the experts held), its faults, the CPU
rehearsal of its cell, and the grouped product's roofline reader on hand-made
facts."""

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
from chipbench.reference import trinity
from ray_tpu.models import transformer

CELL = "trinity-large-preview.agent-saturated"
USABLE = 15.49e9  # 15.75 GB of HBM less 0.26 GB the runtime reserves
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def tiny_cell():
    """The cell's files at toy widths: the same keys, two dense layers and two
    periods, a window of 32 (two pages of 16), experts 16-23 of 64 held, a
    chunk of 64."""
    cell = copy.deepcopy(harness.resolve_cell(CELL))
    conf, mix = cell["config_file"], cell["traffic_file"]
    conf["sizes"].update(d_model=64, n_layers=10, n_dense_layers=2, n_heads=6, n_kv_heads=2,
                         d_head=16, d_ff=32, d_ff_dense=96, vocab_size=300, max_seq_len=512,
                         num_experts=64, window=32, embedding_multiplier=8.0,
                         experts_held=list(range(16, 24)))
    conf["program"].update(model_id="tiny", model_kwargs=dict(
        vocab_size=300, max_seq_len=512, dtype="float32", param_dtype="float32",
        experts_held=8, first_expert=16, select_bias_init_std=0.02))
    conf["engine"] = {"kv_layout": "paged", "page_size": 16, "max_slots": 4, "max_len": 512,
                      "min_bucket": 32, "num_pages": 100, "prefill_chunk": 64,
                      "enable_prefix_cache": False}
    # float32 at toy widths reads 1e-6
    conf["check"].update(sample_tokens=150, positions=6, logits_rel_tol=2e-5,
                         logits_median_tol=2e-5, served_gap_tol=1e-2, max_tie_seconds=3.0)
    conf["ready_timeout_s"] = 300.0
    # every chunk program, prefix span and decode bound of the tiny mix
    mix.update(rate_rps=5.0, warmup_wave=2,
               warmup=[[40, 8], [80, 8], [100, 8], [150, 8], [200, 8], [290, 8], [400, 8]])
    mix["classes"][0]["prompt"].update(median=120, min=40, max=400)
    mix["classes"][0]["output"].update(median=8, min=2, max=24)
    return {**cell, "name": "tiny.agent", "run_seconds": 2}


def _tiny_model(seed=2**31 + 5):
    conf = tiny_cell()["config_file"]
    cfg = program.transformer_config(conf["program"])
    p = program.init_params(cfg, seed)
    return conf, cfg, jax.tree.map(lambda x: x + 0.05 * jax.random.normal(
        jax.random.PRNGKey(7), x.shape, x.dtype), p)


def test_reference_agrees_with_the_program():
    """The file's `sizes` are all the reference is given: three windows of
    tokens, experts 16-23 held in both."""
    conf, cfg, p = _tiny_model()
    assert cfg.moe.share and (cfg.moe.first_expert, cfg.moe.held) == (16, 8)
    assert p["layers"]["mlp"]["gate"].shape[:2] == (8, 8)
    assert p["layers"]["mlp"]["router"].shape == (8, 64, 64)
    tokens = np.random.default_rng(0).integers(0, 300, 100, dtype=np.int32)
    want, margin = trinity.forward(p, jnp.asarray(tokens), conf["sizes"])
    got, _ = transformer.forward(p, tokens[None], cfg)
    assert float(jnp.abs(got[0] - want).max() / jnp.abs(want).max()) < 1e-5
    assert margin.shape == (10, 100, 2) and bool(jnp.isinf(margin[:2]).all())
    # the other side of a tie is another routing: another result
    depth = np.zeros((10, 100), np.int8)
    depth[4, 50:] = 1
    other, _ = trinity.forward(p, jnp.asarray(tokens), conf["sizes"], depth)
    assert float(jnp.abs(other[:50] - want[:50]).max()) == 0.0
    assert float(jnp.abs(other[50:] - want[50:]).max()) > 0.0


def test_the_published_sizes_are_the_catalogs():
    """Every key of the catalog's `config` is in the file with its value but
    the four in `reduced`, no width among them, and the program's
    configuration is built from them."""
    conf = harness.resolve_cell(CELL)["config_file"]
    reduced = {"num_hidden_layers": (5, 60), "num_dense_layers": (1, 6),
               "num_experts": (32, 256), "vocab_size": (25024, 200192)}
    if os.path.exists(CATALOG):
        row = next(r for r in map(json.loads, open(CATALOG))
                   if r["name"] == "Trinity-Large-Preview")
        assert conf["source"] == row["source_url"]
        assert {k: conf[k] for k in row["config"] if k not in reduced} == {
            k: v for k, v in row["config"].items() if k not in reduced}
        assert {k: row["config"][k] for k in reduced} == conf["reduced_from"]
    assert conf["reduced"] == list(reduced)
    assert {k: (conf[k], conf["reduced_from"][k]) for k in reduced} == reduced
    entry = next(c for c in harness.load_json(harness.ROOT, "BENCHMARK.json")["configs"]
                 if c["name"] == conf["name"])
    assert entry["reduced"] == conf["reduced"] and entry["source"] == conf["source"]
    for line in conf["assumed"].values():
        assert len(line) > 40                                     # each with its reason
    assert conf["router_outputs"] == 256 and conf["experts_held"] == {
        "first": 0, "count": 32, "of": 256, "chips_sharing_a_layer": 8}
    cfg = program.transformer_config(conf["program"])
    assert (cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.d_ff, cfg.d_ff_dense) == (
        conf["hidden_size"], conf["num_attention_heads"], conf["num_key_value_heads"],
        conf["head_dim"], conf["moe_intermediate_size"], conf["intermediate_size"])
    assert (cfg.n_layers, cfg.n_dense_layers, cfg.vocab_size) == (5, 1, 25024)
    assert (cfg.window, cfg.window_period, cfg.norm_eps, cfg.rope_theta) == (
        conf["sliding_window"], conf["global_attn_every_n_layers"], conf["rms_norm_eps"],
        conf["rope_theta"])
    moe = cfg.moe
    assert (moe.num_experts, moe.top_k, moe.n_shared_experts, moe.routed_scaling_factor,
            moe.score_func, moe.held, moe.first_expert) == (
        conf["router_outputs"], conf["num_experts_per_tok"], conf["num_shared_experts"],
        conf["route_scale"], conf["score_func"], conf["num_experts"], 0)
    assert cfg.embedding_multiplier == conf["hidden_size"] ** 0.5 and conf["mup_enabled"]
    assert cfg.attn_gate and cfg.qk_norm and cfg.sandwich_norms and not cfg.full_layer_rope
    assert not cfg.tie_embeddings and not conf["tie_word_embeddings"]
    # the cut's kinds are the published ones of the layers it stands for
    stood = [int(v.split()[2]) for v in conf["layers_stood_for"].values()]
    assert stood == [0, 8, 9, 10, 11]
    assert [("full_attention" if transformer.is_full_layer(cfg, l) else "sliding_attention")
            for l in range(5)] == [conf["layer_types"][l] for l in stood]
    sizes = conf["sizes"]
    assert sizes["experts_held"] == list(range(32)) and sizes["top_k"] == 4
    assert (sizes["n_layers"], sizes["n_dense_layers"], sizes["window"], sizes["norm_eps"]) == (
        cfg.n_layers, cfg.n_dense_layers, cfg.window, cfg.norm_eps)
    assert sizes["embedding_multiplier"] == cfg.embedding_multiplier
    assert conf["check"]["sample_tokens"] >= 4600 and conf["check"]["positions"] >= 16


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


@pytest.fixture
def flash_as_on_the_chip(monkeypatch):
    import sys

    import ray_tpu.ops.attention  # noqa: F401
    monkeypatch.setattr(sys.modules["ray_tpu.ops.attention"], "_flash_ok",
                        lambda q: q.shape[1] % 256 == 0 and q.shape[1] >= 1024)


def _total(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def test_the_steps_fit_beside_the_weights_and_match_the_file(on_chip, flash_as_on_the_chip):
    """The decode step at the file's slots and pools (both kinds aliased in
    place, no pool re-laid around it: a group of 6 query heads, a ring of 97
    pages), the largest chunk program (2,048 tokens over a 32,768-token
    prefix) beside both pools, and the check's own unchunked prefill of a
    bucket of 8,192, twice the window: under what a v5e has, and what `aot`
    records."""
    from ray_tpu.models import decoding
    from ray_tpu.models import decoding_paged as dp

    conf = harness.resolve_cell(CELL)["config_file"]
    cfg, eng, aot = program.transformer_config(conf["program"]), conf["engine"], conf["aot"]
    params = on_chip(jax.eval_shape(lambda k: transformer.init(k, cfg), jax.random.PRNGKey(0)))
    weights = sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert cfg.num_params() == aot["parameters"] == 4_321_903_872
    # bfloat16 but the four expert layers' select bias of 256: float32
    assert weights == aot["weights_bytes"] == 2 * cfg.num_params() + 2 * 4 * 256
    ring = dp.window_ring(cfg, eng["page_size"], eng["prefill_chunk"])
    assert ring == aot["window_ring_pages"] == 97
    state = on_chip(jax.eval_shape(lambda: dp.init_paged_state(
        cfg, eng["max_slots"], eng["max_len"], eng["num_pages"], eng["page_size"], ring=ring)))
    nbytes = {k: int(np.prod(v.shape)) * v.dtype.itemsize for k, v in state.items()}
    assert state["kp"].shape == (1, eng["num_pages"], 64, 8, 128)
    assert state["wkp"].shape == (4, eng["max_slots"] * 97 + 1, 64, 8, 128)
    full, window = nbytes["kp"] + nbytes["vp"], nbytes["wkp"] + nbytes["wvp"]
    assert (full, window) == (aot["full_pool_bytes"], aot["window_pool_bytes"])
    assert full // eng["num_pages"] == aot["full_page_bytes"] == 262_144
    assert window // state["wkp"].shape[1] == aot["window_page_bytes"] == 1_048_576
    assert aot["cache_bytes_per_token"] == (262_144 + 1_048_576) // 64
    step = dp.decode_step_paged_ragged.lower(params, state, cfg, 512, True).compile()
    text = step.as_text()
    assert all(k in text for k in ("ragged_paged_attention", "ragged_window_attention",
                                   "grouped_matmul"))
    m = step.memory_analysis()
    assert m.alias_size_in_bytes >= full + window                  # both kinds in place
    assert m.temp_size_in_bytes < 0.1e9                            # no pool re-laid
    assert m.temp_size_in_bytes == pytest.approx(aot["decode_step_temp_bytes"], rel=0.1)
    assert _total(step) == pytest.approx(aot["decode_step_bytes"], rel=0.01)
    ints = on_chip(jax.ShapeDtypeStruct((), jnp.int32))

    def kv(layers, tokens):
        return on_chip(jax.ShapeDtypeStruct((layers, tokens, 8, 128), jnp.bfloat16))

    chunk = dp.prefill_with_prefix.lower(
        params, on_chip(jax.ShapeDtypeStruct((1, 2048), jnp.int32)), kv(1, 32768), kv(1, 32768),
        ints, ints, cfg, kv(4, 4096), kv(4, 4096)).compile()
    assert chunk.memory_analysis().temp_size_in_bytes == pytest.approx(
        aot["prefill_chunk_2048_prefix_32768_temp_bytes"], rel=0.05)
    total = _total(chunk) + full + window
    assert total < USABLE
    assert total == pytest.approx(aot["prefill_chunk_2048_prefix_32768_bytes"], rel=0.01)
    check = decoding.prefill.lower(
        params, on_chip(jax.ShapeDtypeStruct((1, 8192), jnp.int32)), ints, cfg).compile()
    # scores a KV head's group at a time: 6 x 8192 x 8192 x 4, not 48 x
    assert check.memory_analysis().temp_size_in_bytes < 3e9
    assert _total(check) < USABLE
    assert _total(check) == pytest.approx(aot["check_prefill_8192_bytes"], rel=0.01)


CPU_FAULTS = ["gate_left_out", "qk_norms_left_out", "post_norms_left_out", "weights_over_held",
              "absent_expert_wrapped", "window_page_zeroed", "rope_on_full_layer",
              "window_ignored", "embedding_unscaled"]


def test_the_configurations_faults_read_not_ok():
    """Every fault of `trinity_faults.py` through `check.serve_check` at the
    tiny size in float32: each reads not ok where the sound program passes the
    same comparison, and `check.FAULTS`' page fault with them."""
    from chipbench import check_sweep, trinity_faults

    conf = tiny_cell()["config_file"]
    seed = 2**31 + 11
    assert list(trinity_faults.FAULTS) == CPU_FAULTS
    rows = list(trinity_faults.sweep(conf, [seed], CPU_FAULTS, on_chip=False))
    rows += list(check_sweep.sweep(conf, [seed], "kv_page_zeroed", on_chip=False))
    assert [r["fault"] for r in rows] == CPU_FAULTS + ["kv_page_zeroed"]
    assert rows[0]["sound_ok"] and rows[0]["sound_median"] < 2e-5
    for r in rows:
        assert r["ok"] is False, r["fault"]
        assert r["logits_rel_err_median"] > conf["check"]["logits_median_tol"], r["fault"]
    with pytest.raises(ValueError, match="no fault"):
        list(trinity_faults.sweep(conf, [seed], ["experts_shuffled"], on_chip=False))


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
    assert facts["check"]["control_fails"] and facts["check"]["prompt_tokens"] == 150
    assert r["failed"] == 0 and r["attempted"] == 10
    assert facts["compiles_in_window"] == 0 and r["correct"]
    assert r["end_to_end"]["served_tok_s"] > 0
    json.dumps(facts)                                            # the result line stays JSON
    s1 = facts["stats1"]
    assert s1["free_pages"] == s1["num_pages"] - 1
    assert s1["free_window_pages"] == s1["window_pages"] - 1 and s1["loops"]["planes"] == 10
    experts = s1["experts"]
    assert experts["tokens_onehot"] > 0 and experts["tokens_sorted"] > 0
    assert 0 < experts["slots_held"] < experts["slots_routed"]
    assert 0 < experts["groups_with_rows"] <= 8 * experts["calls"]
    # every per-layer metric the cell lists that needs no device trace
    got = harness.read_layer_metrics(cell, facts)
    assert set(m["name"] for m in cell["per_layer"]) - set(got) == {
        "serve_device_idle_pct.doc", "ragged_window_attention_roofline_pct.agent",
        "ragged_paged_attention_roofline_pct.agent", "expert_grouped_matmul_roofline_pct.agent"}
    assert 0 < got["expert_slots_held_pct.agent"]["value"] < 100
    assert got["expert_rows_per_group.agent"]["value"] >= 1
    assert got["kv_bytes_per_tok.longdoc"]["value"] == 10 * 2 * 2 * 16 * 4      # float32
    assert 0 < got["kv_pool_used_pct.longdoc"]["value"] <= 100
    assert got["window_ctx_tok.mixed"]["value"] > 0 and got["prefill_chunks_per_s.doc"]["value"] > 0
    assert got["decode_occupancy.doc"]["value"] >= 1 and got["decode_ctx_tok.longdoc"]["value"] > 0


@pytest.mark.parametrize("found", [["grouped_matmul.3", "grouped_matmul.4", "grouped_matmul.5"],
                                   []])
def test_roofline_reads_the_grouped_product(found):
    """The held experts' product's share of its roofline: a slot's three
    products against the weights of the experts that had a row, read once a
    call, over the device time of every op of the kernel's name; no such op or
    no such counters (the parent's program, a run without a trace): nothing."""
    from chipbench.readers import expert_grouped_matmul_roofline as reader

    spec = harness.load_json(harness.BENCH_DIR, "layer_metrics",
                             "expert_grouped_matmul_roofline_pct.agent.json")
    sizes = harness.load_json(harness.BENCH_DIR, "configs", "trinity-large-preview.json")["sizes"]
    assert spec["params"] == {"op": "grouped_matmul", "slots": "experts.slots_held",
                              "groups": "experts.groups_with_rows", "d_model": sizes["d_model"],
                              "d_ff": sizes["d_ff"], "bytes_per_el": 2}
    cost = reader.expert_grouped_matmul_cost(1, 1, 3072, 3072)
    assert cost == {"flops": 6 * 3072 * 3072, "bytes": 3 * 3072 * 3072 * 2}
    calls = {name: {"calls": 100.0, "seconds": 0.5} for name in found}
    counters = lambda held, groups, t: {  # noqa: E731
        "experts": {"slots_held": held, "groups_with_rows": groups}, "loop": {"thread_s": t}}
    facts = {"stats1": {"device": {"kind": "TPU v5 lite"}},
             "stats_t0": counters(10_000, 500, 60.0), "stats_t1": counters(90_000, 8_500, 64.0),
             "trace": {"kernel_calls": calls, "window_s": 5.0}}
    got = reader.read(facts, spec["params"])
    if not found:
        assert got is None and reader.read({}, spec["params"]) is None
        return
    # 80,000 slots and 8,000 groups in 4 s of the engine's clock: 5 s traced
    least = max(100_000 * 6 * 3072 * 3072 / 197e12, 10_000 * 3 * 3072 * 3072 * 2 / 819e9)
    assert got == pytest.approx(100 * least / 1.5, rel=1e-6)
    assert 0 < got < 100 and facts["grouped_matmul_bound"] == "memory"
    assert reader.read({**facts, "stats_t0": {"loop": {"thread_s": 60.0}}},
                       spec["params"]) is None
