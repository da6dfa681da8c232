"""The reduction from a trace to busy and idle time, per-operation sums and
gap attribution: on a trace worked by hand, and on a small recorded one
(``recorded_trace.json``: 300 device operations from the middle of a traced
``vitl16-fused-224`` run on a TPU v5e, with the host spans that touch them;
cut by ``trace_reduce.slice_of``, PR 23) against a second, independent way of
computing the same numbers."""

import json
import os

import pytest

import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))


def test_by_hand():
    # window 0..1000 ns; device busy [100,300) and [250,400) (overlap), [600,700)
    trace = {
        "devices": {"/device:TPU:0": [["a", 100, 200], ["b", 250, 150], ["a", 600, 100]]},
        "host": [["bench.window", 0, 1000], ["fused.train_step", 0, 500],
                 ["data.next_batch", 500, 50], ["fused.train_step", 550, 400]],
    }
    out = trace_reduce.reduce(trace)
    assert out["window_s"] == pytest.approx(1000e-9)
    assert out["busy_s"] == pytest.approx(400e-9)          # 300 + 100, overlap once
    assert out["op_seconds"] == {"a": pytest.approx(300e-9), "b": pytest.approx(150e-9)}
    assert out["op_counts"] == {"a": 2, "b": 1}
    # gaps: [0,100) mid 50 -> train_step; [400,600) mid 500 -> next_batch;
    # [700,1000) mid 850 -> the second train_step
    gaps = dict(out["idle_gaps"])
    assert gaps == {"fused.train_step": pytest.approx(400e-9),
                    "data.next_batch": pytest.approx(200e-9)}
    assert out["busy_s"] + sum(gaps.values()) == pytest.approx(out["window_s"])


def test_an_idle_chip_of_a_four_chip_cell_counts_as_idle():
    trace = {"devices": {"/device:TPU:0": [["a", 0, 100]]}, "host": [["bench.window", 0, 100]]}
    out = trace_reduce.reduce(trace, devices=4)
    assert out["busy_s"] == pytest.approx(25e-9)
    assert dict(out["idle_gaps"]) == {"uncovered": pytest.approx(75e-9)}


def test_no_device_operation_is_an_error():
    with pytest.raises(ValueError):
        trace_reduce.reduce({"devices": {}, "host": []})


def test_the_innermost_span_names_a_gap():
    trace = {"devices": {"/device:TPU:0": [["a", 0, 10], ["a", 90, 10]]},
             "host": [["bench.window", 0, 100], ["party.train_round", 0, 100],
                      ["party.split_step", 20, 60]]}
    assert dict(trace_reduce.reduce(trace)["idle_gaps"]) == {
        "party.split_step": pytest.approx(80e-9)}


def test_recorded_trace_against_a_sweep():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        trace = json.load(f)
    (plane, events), = trace["devices"].items()
    assert plane == "/device:TPU:0" and len(events) == 300
    # the slice is cut out of a run, so its window is the operations' own extent
    trace["host"] = [h for h in trace["host"] if h[0] != "bench.window"]
    out = trace_reduce.reduce(trace)
    lo = min(e[1] for e in events)
    hi = max(e[1] + e[2] for e in events)
    # independent: sweep over sorted edges, counting how many operations are open
    edges = sorted([(e[1], 1) for e in events] + [(e[1] + e[2], -1) for e in events])
    busy = open_ops = 0
    last = lo
    for at, step in edges:
        if open_ops > 0:
            busy += at - last
        open_ops, last = open_ops + step, at
    assert out["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert out["busy_s"] == pytest.approx(busy * 1e-9)
    by_name = {}
    for name, _, dur in events:
        by_name[name] = by_name.get(name, 0) + dur
    # an operation of no duration (a bitcast) holds no time and is not listed
    assert out["op_seconds"] == {k: pytest.approx(v * 1e-9) for k, v in by_name.items() if v}
    # every gap lies under the one fused.train_step span that the slice overlaps
    # or the next_batch between two steps; the shares add up to the idle time
    gaps = dict(out["idle_gaps"])
    assert set(gaps) <= {"fused.train_step", "data.next_batch", "uncovered"}
    assert sum(gaps.values()) == pytest.approx((hi - lo - busy) * 1e-9)
    assert 0 < busy < hi - lo


def test_short_names_tell_the_pallas_kernels_apart():
    fwd = ('%jvp__.1 = (bf16[32,1024,128]{2,1,0:T(8,128)(2,1)}, f32[32,1024,8]{2,1,0}) '
           'custom-call(bf16[32,1024,128]{2,1,0} %pad.4, bf16[32,1024,128]{2,1,0} %pad.0, '
           'bf16[32,1024,128]{2,1,0} %pad.2), custom_call_target="tpu_custom_call", x={}')
    bwd = fwd.replace("%pad.2)", "%pad.2, bf16[1]{0} %a, f32[1]{0} %b, f32[1]{0} %c)")
    assert trace_reduce.short_name(fwd) == "%jvp__.1 custom-call bf16[32,1024,128] tpu_custom_call/3"
    assert trace_reduce.short_name(bwd).endswith("tpu_custom_call/6")
    assert trace_reduce.short_name(
        "%fusion.27 = (f32[1024,50257]{0,1:T(8,128)}, f32[8]{0}) fusion(f32[8]{0:T(8,128)S(1)} %p), "
        "kind=kOutput, calls=%fused_computation.33") == "%fusion.27 fusion f32[1024,50257]"
    assert trace_reduce.short_name("no equals sign") == "no equals sign"
