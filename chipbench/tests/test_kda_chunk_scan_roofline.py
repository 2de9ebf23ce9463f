"""`kda_chunk_scan_roofline_pct.reasondoc`: the reader's cost function by
hand at the published shape, the share it reads from a slice, and nothing to
read where no op of the name ran (the parent's program scans in plain XLA)."""

import pytest

from chipbench import harness
from chipbench.readers import kda_chunk_scan_roofline as reader
from chipbench.readers import stats_ratio

SPEC = harness.load_json(harness.BENCH_DIR, "layer_metrics",
                         "kda_chunk_scan_roofline_pct.reasondoc.json")


def test_the_metric_file_is_the_configurations_sizes():
    sizes = harness.load_json(harness.BENCH_DIR, "configs", "solar-open2-250b.json")["sizes"]
    assert SPEC["reader"] == "kda_chunk_scan_roofline"
    assert SPEC["params"] == {
        "op": "kda_chunk_scan", "work": "prefill.scan_positions",
        "padded": "prefill.scan_padded", "heads": sizes["kda_heads"],
        "dk": sizes["kda_head_dim"], "dv": sizes["kda_head_dim"], "chunk": 64,
        "bytes_per_el": 4}


def test_the_cost_of_a_position_by_hand():
    """One position of one head, chunk 64, keys and values of 128: q, k, g, v
    in and o out (5 x 128 floats) and beta; multiply-adds: 64 x 128 of the
    Gram rows, 31.5 x 256 of the solve, 3 x 128 x 128 with the state's two
    products and its update, 32.5 x 128 of tril(B) U: 69,568, two operations
    each. A layer's 2,048-token chunk of 64 heads: 0.336 GB and 18.2 GFLOP."""
    one = reader.kda_chunk_scan_cost(1, 1, 128, 128, 64)
    assert one == {"bytes": 4 * (5 * 128 + 1), "flops": 2 * 69_568}
    assert 69_568 == 64 * 128 + 63 * 128 + 3 * 128 * 128 + 65 * 64
    chunk = reader.kda_chunk_scan_cost(2048, 64, 128, 128, 64)
    assert chunk["bytes"] == 2048 * 64 * 2564 == 336_068_608
    assert chunk["flops"] == pytest.approx(18.24e9, rel=1e-3)


@pytest.mark.parametrize("found", [["kda_chunk_scan.9"], ["kda_chunk_scan.9", "kda_chunk_scan.12"],
                                   []])
def test_the_share_over_a_slice(found):
    calls = {name: {"calls": 30.0, "seconds": 0.25} for name in found}
    calls["kda_state_update.9"] = {"calls": 384.0, "seconds": 0.1}
    # the two readings lie 5.0 s apart on the engine's clock, the trace holds
    # 4.0 s: 3 layers x 25 chunks of 2,048 less 10 % padding, brought to 4 / 5
    ran, padded = 3 * 25 * 2048, 3 * 25 * 2048 // 10
    facts = {"stats1": {"device": {"kind": "TPU v5 lite"}},
             "stats_t0": {"prefill": {"scan_positions": 1000, "scan_padded": 100},
                          "loop": {"thread_s": 60.0}},
             "stats_t1": {"prefill": {"scan_positions": 1000 + ran, "scan_padded": 100 + padded},
                          "loop": {"thread_s": 65.0}},
             "trace": {"kernel_calls": calls, "window_s": 4.0}}
    got = reader.read(facts, SPEC["params"])
    if not found:
        assert got is None and reader.read({}, SPEC["params"]) is None
        return
    real = (ran - padded) * 4.0 / 5.0
    assert facts["kda_chunk_scan_positions"] == pytest.approx(real)
    least = real * 64 * 2564 / 819e9                               # bytes: memory-bound
    assert got == pytest.approx(100 * least / (0.25 * len(found)), rel=1e-6)
    assert 0 < got < 100 and facts["kda_chunk_scan_bound"] == "memory"
    # a program without the counters: nothing to read
    assert reader.read({**facts, "stats_t0": {"loop": {"thread_s": 60.0}}},
                       SPEC["params"]) is None


def test_the_kernels_share_of_the_scanned_positions():
    spec = harness.load_json(harness.BENCH_DIR, "layer_metrics",
                             "kda_scan_kernel_share_pct.reasondoc.json")
    assert spec["reader"] == "stats_ratio"
    s0 = {"prefill": {"scan_positions": 600, "scan_kernel_positions": 600}}
    s1 = {"prefill": {"scan_positions": 6744, "scan_kernel_positions": 6744}}
    assert stats_ratio.read({"stats0": s0, "stats1": s1}, spec["params"]) == 100.0
    parent = [{"prefill": {"scan_positions": n}} for n in (600, 6744)]
    assert stats_ratio.read({"stats0": parent[0], "stats1": parent[1]}, spec["params"]) is None
