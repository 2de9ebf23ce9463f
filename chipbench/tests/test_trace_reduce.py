"""The reduction from trace rows to numbers, on a small recorded trace
(tests/data/trace_rows.json: 600 consecutive device events of a real
gpt2-large train step on a v5e) and on a few hand-made rows for what that
slice does not hold (idle gaps, collectives, a second device)."""

import json
import os

import pytest

from chipbench import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
REC = json.load(open(os.path.join(HERE, "data", "trace_rows.json")))


def _busy_by_sweep(events):
    """Independent of `_union`: count open events at every boundary."""
    marks = sorted([(s, 1) for _, s, d in events if d > 0]
                   + [(s + d, -1) for _, s, d in events if d > 0],
                   key=lambda m: (m[0], -m[1]))
    busy = depth = 0
    last = None
    for t, step in marks:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


def test_recorded_trace():
    out = tr.reduce_rows(REC, REC["kernel_ops"])
    events = REC["devices"]["0"]
    assert len(events) == 600 and out["devices"] == 1
    assert out["busy_s"] == pytest.approx(_busy_by_sweep(events) / 1e9, rel=1e-12)
    assert out["busy_s"] == pytest.approx(0.071290503)
    assert out["window_s"] == pytest.approx(0.071290549)
    # 22 forward calls of the flash kernel (3 operands in, o + lse out) lie in
    # the slice, 573.5 us each: the sum of those events and nothing else
    fwd = [d for n, _, d in events if n == "closed_call.8"]
    assert len(fwd) == 22 and sum(fwd) == 12_616_472
    assert out["kernel_calls"] == {"3in_2out": {"calls": 22.0, "seconds": 0.012616472}}
    assert out["kernel_s"] == pytest.approx(0.012616472)
    assert out["collective_s"] == 0.0
    ops = dict(out["breakdown"]["device_ops"])
    assert len(out["breakdown"]["device_ops"]) == 10
    assert ops["pallas:3in_2out:closed_call.8"] == pytest.approx(0.012616472)
    # the enclosing `while` is charged only what its body's ops do not cover
    whole = next(d for n, _, d in events if n == "while.5")
    assert ops["while.5"] < whole / 1e9 / 2
    assert out["breakdown"]["idle_gaps"] == []


def test_op_names_and_kernel_signatures():
    assert tr.op_name("%fusion.12 = bf16[4,8]{1,0} fusion(%a, %b), kind=kLoop") == "fusion.12"
    assert tr.op_name("copy-done.3") == "copy-done.3"
    hlo = '''
  %closed_call.8 = (bf16[4,20,1024,64]{3,2,1,0}, f32[4,20,1024,1]{3,2,1,0}) custom-call(%fusion.425, %fusion.427, %fusion.429), custom_call_target="tpu_custom_call", operand_layout_constraints={}
  %checkpoint.20 = bf16[4,20,1024,64]{3,2,1,0} custom-call(%a, %b, %c, %d, %e, /*index=5*/%f), custom_call_target="tpu_custom_call"
  %custom-call.4 = bf16[36,4,1024,1280]{3,2,1,0} custom-call(), custom_call_target="AllocateBuffer"
'''
    assert tr.kernel_ops_from_hlo(hlo) == {"closed_call.8": "3in_2out",
                                           "checkpoint.20": "6in_1out"}
    assert tr.kernel_ops_from_hlo(open(os.path.join(HERE, "data", "trace_rows.json")).read()) == {}


def test_gaps_collectives_and_devices_by_hand():
    rows = {"devices": {
        "0": [["fusion.1", 0, 400_000], ["all-gather-done.2", 400_000, 100_000],
              ["fusion.3", 1_000_000, 500_000]],            # idle 500 us in between
        "1": [["fusion.1", 0, 1_500_000]]},
        "host": [["chipbench:report", 600_000, 300_000],
                 ["chipbench:train_step", 0, 2_000_000]]}
    out = tr.reduce_rows(rows)
    assert out["devices"] == 2
    assert out["busy_s"] == pytest.approx((1_000_000 + 1_500_000) / 2 / 1e9)
    assert out["window_s"] == pytest.approx(1.5e-3)
    assert out["collective_s"] == pytest.approx(100_000 / 2 / 1e9)
    # the gap is named by the innermost annotation open at its middle
    assert out["breakdown"]["idle_gaps"] == [["report", pytest.approx(250e-6)]]
    assert tr.reduce_rows({"devices": {}, "host": []}) is None
    assert tr.self_times([["while.1", 0, 100], ["a", 10, 30], ["b", 50, 40]]) == [30, 30, 40]
