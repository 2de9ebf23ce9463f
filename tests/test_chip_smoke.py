"""chip_smoke.py without the chip: it must fail here, and its phase functions
must run end to end at a tiny size (on-chip-measurement guide, sections 2.1
and 2.2) — on CPU workers, which the script itself never accepts.

The rehearsals are `slow`: they are run by hand before a chip call, not in
tier-1.
"""

from __future__ import annotations

import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

import chip_smoke
import ray_tpu
from ray_tpu import serve, train

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY_TRAIN = dict(family="gpt2", size="124m", batch=4, seq=64, steps=3, lr=1e-2,
                  meshes=[{}], model_kwargs=dict(
                      vocab_size=301, max_seq_len=64, d_model=64, n_layers=2,
                      n_heads=4, d_ff=128))
TINY_SERVE = dict(family="llama", model_id="tiny",
                  model_kwargs=dict(vocab_size=300, max_seq_len=256, d_model=64,
                                    n_layers=2, n_heads=8, n_kv_heads=8, d_ff=128,
                                    dtype=jnp.float32, remat=False),
                  engine_kwargs={"page_size": 16,
                                 "max_slots": 4, "max_len": 256},
                  prompt_tokens=(16, 60, 200), max_tokens=6)
TINY_KERNELS = dict(flash=(1, 2, 128, 64), interpret=True,
                    ragged=dict(batch=2, kv_heads=2, group=2, head_dim=64,
                                page=16, pages_per_seq=4),
                    grouped=dict(tiny=dict(tokens=64, top_k=2, experts=4, layers=2,
                                           d_model=256, d_ff=128)),
                    kda_update=dict(layers=2, slots=4, heads=2, head_dim=128),
                    kda_scan=dict(tokens=128, tail=64, heads=2, head_dim=128, chunk=32),
                    kda_mixer=dict(tokens=256, heads=2, head_dim=128, d_conv=4, d_model=64))


def test_without_a_chip_the_script_fails_and_prints_no_result():
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       cwd=ROOT, capture_output=True, text=True, timeout=120,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "TPU chip" in r.stderr


@pytest.fixture
def cluster():
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8, num_workers=2, max_workers=12)
    yield
    serve.shutdown()
    ray_tpu.shutdown()


@pytest.mark.slow
def test_rehearse_train_phase(cluster):
    facts = chip_smoke.run_train_phase(
        TINY_TRAIN, train.ScalingConfig(num_workers=1), seed=0,
        kernels=TINY_KERNELS)
    chip_smoke.check_kernels(facts["kernels"])
    assert facts["kernels"]["ragged"]["bit_equal"]
    assert len(facts["kernels"]["kda_update"]["steps"]) == 5
    assert facts["kernels"]["kda_scan"]["ok"] and "ms" not in facts["kernels"]["kda_scan"]
    assert facts["kernels"]["kda_mixer"]["ok"] and "ms" not in facts["kernels"]["kda_mixer"]
    assert sum(k.startswith("grouped_tiny_") for k in facts["kernels"]) == 4
    chip_smoke.check_train_run(facts["runs"][0], min_steps=3, want_kernel=False)
    # a CPU worker is what the script exists to refuse
    with pytest.raises(chip_smoke.SmokeFailure, match="given no chip"):
        chip_smoke.check_worker_device(facts, 1, "the train worker")
    with pytest.raises(chip_smoke.SmokeFailure, match="tpu_custom_call"):
        chip_smoke.check_train_run(facts["runs"][0], min_steps=3, want_kernel=True)


@pytest.mark.slow
def test_rehearse_sharded_step_on_four_virtual_devices(cluster):
    spec = {**TINY_TRAIN, "steps": 2, "meshes": [{"fsdp": 4}, {}]}
    facts = chip_smoke.run_train_phase(
        spec, train.ScalingConfig(num_workers=1), seed=0, kernels=None)
    sharded, single = facts["runs"]
    assert len(sharded["param_shard_devices"]) == 4
    assert len(single["param_shard_devices"]) == 1
    held = sharded["state_bytes_per_device"]  # params AND Adam moments spread
    assert len(held) == 4 and max(held) < 1.5 * min(held)
    assert abs(sharded["losses"][0] - single["losses"][0]) < 1e-3


@pytest.mark.slow
def test_rehearse_serve_phase(cluster):
    facts = chip_smoke.run_serve_phase(TINY_SERVE, seed=0, ready_timeout_s=120,
                                       accelerator_type=None)
    assert facts["requests"] == 9 and facts["engine"]["decode_attn"] == "ragged_reference"
    # everything but the device holds on the CPU; the device check refuses it
    with pytest.raises(chip_smoke.SmokeFailure, match="given no chip"):
        chip_smoke.check_serve(facts, TINY_SERVE)
    facts["engine"]["device"] = {"platform": "tpu", "kind": "rehearsal", "count": 1}
    with pytest.raises(chip_smoke.SmokeFailure, match="Pallas ragged kernel"):
        chip_smoke.check_serve(facts, TINY_SERVE)
    facts["engine"]["decode_attn"] = "ragged_kernel"
    chip_smoke.check_serve(facts, TINY_SERVE)


@pytest.mark.slow
def test_rehearse_replica_without_a_chip_never_deploys(cluster):
    # accelerator_type "TPU" (the default) asks for a chip this session lacks
    with pytest.raises(chip_smoke.SmokeFailure, match="not healthy"):
        chip_smoke.run_serve_phase(TINY_SERVE, seed=0, ready_timeout_s=5)


@pytest.mark.slow
def test_rehearse_replicas_phase(cluster):
    facts = chip_smoke.run_replicas_phase(
        TINY_SERVE, replicas=2, seed=0, ready_timeout_s=120,
        accelerator_type=None)
    assert len(facts["replicas"]) == 2
    assert all(r["decode_steps"] > 0 for r in facts["replicas"])


@pytest.mark.slow
def test_rehearse_tp_decode_compare():
    out = chip_smoke.tp_decode_compare(
        {**TINY_SERVE, "prompt_tokens": (20, 100), "max_tokens": 6,
         "matmul_precision": "highest"}, seed=0)
    assert out["tp"]["tokens"] == out["unsharded"]["tokens"]
    assert [len(t) for t in out["tp"]["tokens"]] == [6, 6]
