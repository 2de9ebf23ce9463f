"""The FLOP and byte functions against values worked by hand for gpt2-large."""

import pytest

from chipbench import flops, harness

LARGE = harness.load_json(harness.BENCH_DIR, "configs", "gpt2-large.json")["sizes"]
PEAKS = harness.peaks_for("TPU v5 lite")


def test_gpt2_large_matmul_weights():
    # per layer: qkv 3*1280*1280, out 1280*1280, mlp 2*1280*5120 = 19,660,800
    # 36 layers = 707,788,800; tied head once 1280*50257 = 64,328,960; the
    # input embedding is a gather and the positions an add
    assert flops.matmul_params_per_token(LARGE) == 707_788_800 + 64_328_960


def test_gpt2_large_train_flops_per_token():
    # causal attention forward: 2 * 1024 * 20 * 64 * 36 = 94,371,840
    assert flops.attention_flops_per_token(LARGE, 1024) == 94_371_840
    forward = 2 * 772_117_760 + 94_371_840
    assert flops.forward_flops_per_token(LARGE, 1024) == forward
    assert flops.train_flops_per_token(LARGE, 1024) == 3 * forward == 4_915_822_080


def test_sparse_experts_count_top_k_and_the_router():
    sizes = harness.load_json(harness.BENCH_DIR, "configs", "mixtral-8x7b.json")["sizes"]
    attn = 4096 * (32 * 128 + 2 * 8 * 128) + 32 * 128 * 4096
    mlp = 2 * 3 * 4096 * 14336 + 4096 * 8
    assert flops.matmul_params_per_token(sizes) == 4 * (attn + mlp) + 4096 * 32000


def test_flash_kernel_cost_and_roofline():
    cost = flops.flash_attention_cost(4, 20, 1024, 64)
    assert cost["fwd_flops"] == 4 * 1024 * 1024 * 64 * 80 / 2 == 10_737_418_240
    assert cost["fwd_bytes"] == 4 * (80 * 1024 * 64 * 2) + 80 * 1024 * 4 == 42_270_720
    assert cost["bwd_flops"] == 2.5 * cost["fwd_flops"]
    assert cost["bwd_bytes"] == 8 * (80 * 1024 * 64 * 2) + 80 * 1024 * 4
    seconds, bound = flops.roofline_seconds(cost["fwd_flops"], cost["fwd_bytes"], PEAKS)
    assert bound == "compute" and seconds == pytest.approx(54.5e-6, rel=1e-3)
    seconds, bound = flops.roofline_seconds(1e9, 819e9, PEAKS)
    assert bound == "memory" and seconds == pytest.approx(1.0)
