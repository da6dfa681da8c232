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


def test_a_container_is_busy_time_and_no_operation():
    # a routed layer's conditional [100,400) lies over its branch's two
    # operations [120,200) and [250,390); a fusion [500,600) stands alone
    trace = {"devices": {"/device:TPU:0": [
        ["%cond.7 conditional f32[8,2048,1536]", 100, 300],
        ["%gmm.3 custom-call bf16[8192,1536] tpu_custom_call/4", 120, 80],
        ["%fusion.9 fusion bf16[8192,2048]", 250, 140],
        ["%fusion.9 fusion bf16[8192,2048]", 500, 100],
        ["%while.2 while (s32[], f32[8])", 700, 50], ["%call.1 call f32[8]", 800, 20]]},
        "host": [["bench.window", 0, 1000]]}
    out = trace_reduce.reduce(trace)
    # the union holds every event: the conditional's own head and tail count
    assert out["busy_s"] == pytest.approx((300 + 100 + 50 + 20) * 1e-9)
    assert dict(out["idle_gaps"]) == {"uncovered": pytest.approx(530e-9)}
    # the operations' seconds hold the branch once, and no container
    assert out["op_seconds"] == {
        "%gmm.3 custom-call bf16[8192,1536] tpu_custom_call/4": pytest.approx(80e-9),
        "%fusion.9 fusion bf16[8192,2048]": pytest.approx(240e-9)}
    assert out["op_counts"]["%fusion.9 fusion bf16[8192,2048]"] == 2
    assert [name for name, _ in out["device_ops"]] == [
        "%fusion.9 fusion bf16[8192,2048]",
        "%gmm.3 custom-call bf16[8192,1536] tpu_custom_call/4"]
    assert trace_reduce.is_container("%cond.98 conditional f32[8,2048,1536]")
    assert trace_reduce.is_container("%cond.47.clone conditional bf16[8192,2688]")
    assert not trace_reduce.is_container("%conditional_fusion.1 fusion f32[8]")
    assert not trace_reduce.is_container("no equals sign")


@pytest.mark.parametrize("name", ["recorded_trace.json", "recorded_trace_routed.json"])
def test_the_container_rule_leaves_busy_window_and_gaps_to_the_nanosecond(name, monkeypatch):
    """On a recorded trace (the ViT one with a conditional laid over a
    stretch of it; ``recorded_trace_routed.json``: 300 operations from the
    middle of a traced ``lfm2-moe-fused-t8192`` run on a TPU v5e, PR 49, whose
    routed layers' conditionals are the trace's own) the reduction gives the
    busy time, the window and the gaps it gave before the rule, to the
    nanosecond, and the operations' seconds less the containers' alone."""
    with open(os.path.join(HERE, name)) as f:
        trace = json.load(f)
    (plane, events), = trace["devices"].items()
    if not any(trace_reduce.is_container(e[0]) for e in events):
        ordered = sorted(events, key=lambda e: e[1])
        first, last = ordered[20], ordered[280]
        events.append(["%cond.1 conditional f32[8]", first[1], last[1] + last[2] - first[1]])
    held = {e[0] for e in events if trace_reduce.is_container(e[0])}
    after = trace_reduce.reduce(trace)
    monkeypatch.setattr(trace_reduce, "CONTAINERS", ())      # the reduction before the rule
    before = trace_reduce.reduce(trace)
    for key in ("busy_s", "window_s", "idle_gaps"):
        assert after[key] == before[key]
    assert held and held <= set(before["op_seconds"]) and not held & set(after["op_seconds"])
    assert after["op_seconds"] == {k: v for k, v in before["op_seconds"].items() if k not in held}
    assert after["op_counts"] == {k: v for k, v in before["op_counts"].items() if k not in held}
    assert not held & {n for n, _ in after["device_ops"]}
    assert held & {n for n, _ in before["device_ops"]}
    # what is left is every other event's own time, once
    alone = sum(e[2] for e in events if e[0] not in held) * 1e-9
    assert sum(after["op_seconds"].values()) == pytest.approx(alone)


def test_gaps_are_named_by_the_latest_started_span_that_covers_them():
    """The program's spans lie under the harness's wrappers: a gap inside
    ``loss_wait`` inside ``step_total`` inside ``fused.train_step`` is
    ``loss_wait``'s; spans that start together give the first in the list;
    a span that has ended covers nothing."""
    host = [["bench.window", 0, 1000], ["fused.train_step", 0, 900],
            ["step_total", 10, 880], ["h2d", 20, 30], ["loss_wait", 100, 700],
            ["counters_read", 800, 50], ["twin_a", 900, 50], ["twin_b", 900, 50]]
    trace = {"devices": {"/device:TPU:0": [["a", 0, 30], ["a", 40, 60], ["a", 300, 100],
                                           ["a", 810, 20], ["a", 860, 60], ["a", 960, 40]]},
             "host": host}
    gaps = dict(trace_reduce.reduce(trace)["idle_gaps"])
    # [30,40) under h2d; [100,300) and [400,810) under loss_wait; [830,860)
    # mid 845 under counters_read; [920,960) mid 940 under the twins
    assert gaps == {"h2d": pytest.approx(10e-9), "loss_wait": pytest.approx(610e-9),
                    "counters_read": pytest.approx(30e-9), "twin_a": pytest.approx(40e-9)}


def test_a_calls_bytes_and_where_they_lie_are_read_from_its_own_line():
    """The two convolution calls as the chip's traces name them (PR 49): in
    ``phi4flash-fused-t8192`` XLA keeps ``x`` in the chip's fast memory
    (``S(1)``), so of the forward's arrays ``y`` alone lies in HBM; in
    ``nemotronh-moe-fused-t8192`` every large array lies in HBM, and ``x``,
    handed over twice, counts once."""
    pf = ('%conv_silu_fwd.1 = f32[1,8192,5120]{2,1,0:T(8,128)} custom-call('
          'bf16[1,8192,5120]{2,1,0:T(8,128)(2,1)S(1)} %split.8, f32[4,5120]{1,0:T(4,128)S(1)} '
          '%copy-done.202, f32[1,5120]{1,0:T(1,128)S(1)} %bitcast.1136), '
          'custom_call_target="tpu_custom_call", operand_layout_constraints={bf16[1,8192,5120]{2,1,0}, '
          'f32[4,5120]{1,0}, f32[1,5120]{1,0}}, frontend_attributes={kernel_metadata={}}')
    small = 5 * 5120 * 4
    assert trace_reduce.call_bytes(pf) == (8192 * 5120 * 6 + small, 8192 * 5120 * 2 + small)
    nf = ('%conv_silu_bwd.3 = (bf16[1,8192,6144]{2,1,0:T(8,128)(2,1)}, '
          'f32[1,5,8,6144]{3,2,1,0:T(8,128)S(1)}) custom-call(bf16[1,8192,6144]{2,1,0:T(8,128)(2,1)} '
          '%split.12, bf16[1,8192,6144]{2,1,0:T(8,128)(2,1)} %split.12, f32[4,6144]{1,0:T(4,128)} %g, '
          'f32[1,6144]{1,0:T(1,128)} %b, bf16[1,8192,6144]{2,1,0:T(8,128)(2,1)} %pad_maximum_fusion.18), '
          'custom_call_target="tpu_custom_call", operand_layout_constraints={bf16[1,8192,6144]{2,1,0}}')
    assert trace_reduce.call_bytes(nf) == (3 * 8192 * 6144 * 2 + 45 * 6144 * 4, 40 * 6144 * 4)
    assert trace_reduce.call_bytes("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)") is None
    assert trace_reduce.call_bytes("no equals sign") is None
    # the reader takes the cost's bytes and takes off what the line keeps in
    # fast memory: the cost is the one source of the count, the line of the place
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(HERE), "layer_metrics"))
    import _afmoe
    name = trace_reduce.short_name(pf)
    trace = {"op_seconds": {name: 1e-3}, "op_counts": {name: 4}, "calls": {name: pf}}
    counted = 8192 * 5120 * 6 + small
    (_, (ops, moved)), = _afmoe.calls_in_hbm(trace, (7.0, counted), "conv_silu_fwd")
    assert (ops, moved) == (7.0, 8192 * 5120 * 4)
    # a count that is too high stays too high: the line lowers it by no more
    # than what lies in fast memory
    (_, (_, moved)), = _afmoe.calls_in_hbm(trace, (7.0, 2 * counted), "conv_silu_fwd")
    assert moved == counted + 8192 * 5120 * 4
    (_, (_, moved)), = _afmoe.calls_in_hbm({**trace, "calls": {}}, (7.0, 10 ** 9), "conv_silu_fwd")
    assert moved == 10 ** 9                      # a trace without the lines: the cost as it is
    assert _afmoe.calls_in_hbm(trace, (7.0, 1000), "conv_silu_bwd") == []


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
