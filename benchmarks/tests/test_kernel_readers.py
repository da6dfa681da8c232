"""The readers PR 49 added, on made-up traces: the roofline shares of the
Mamba-2 recurrence's kernels (``ssd_roofline_pct``) and of the convolution's
(``conv_silu_roofline_pct``, in both cells that run it), their costs against
a hand count and against the calls' own lines as the chip recorded them
(``recorded_calls.json``), and the whole step's share of the chip's peak over
the device's busy time (``step_mfu_pct``)."""

import importlib
import json
import os
import sys

import pytest

from flops import common

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NF = ("nemotron-labs-twotower-30b-a3b-base", "nemotron_h", "nemotronh-moe-fused-t8192")
PF = ("phi-4-mini-flash-reasoning", "phi4flash", "phi4flash-fused-t8192")


def fake_run(cell, ops, **more):
    with open(os.path.join(BENCH, "configs", cell[0] + ".json")) as f:
        config = json.load(f)
    return {"trace": {"op_seconds": {n: s for n, (_, s) in ops.items()},
                      "op_counts": {n: c for n, (c, _) in ops.items()},
                      "window_s": 4.0, "busy_s": 3.2, "devices": 1},
            "job": {"rows_per_client": 1, "tokens_per_row": 8192, "clients": 1},
            "config": config, "flops": importlib.import_module("flops." + cell[1]),
            "peak": PEAK, **more}


def reader(name):
    sys.path.insert(0, BENCH)
    import run
    return run.layer_reader(name)


def least(cost):
    return common.least_seconds(*cost, PEAK)


def test_the_recurrences_costs_against_a_hand_count():
    run = fake_run(NF, {})
    flops = run["flops"]
    shape = flops.ssd_shape(run["config"], 1, 8192)
    assert shape == dict(batch=1, t=8192, heads=64, head_dim=64, groups=8, state=128, chunk=128)
    t, inner, bc = 8192, 64 * 64, 2 * 8 * 128
    small = 3 * t * 64 * 4                       # dt and the decays in two layouts
    states = 64 * inner * 128 * 2                # a chunk's first state, 64 chunks
    ops, moved = flops.ssd_fwd(**shape)
    assert ops == flops.ssd_products(**shape) == t * (2 * 8 * 128 * 128 + 2 * 64 * 128 * 64
                                                      + 4 * 64 * 64 * 128)
    assert moved == t * (inner + bc) * 2 + small + t * inner * 4 + states == 308281344
    ops_b, moved_b = flops.ssd_bwd(**shape)
    assert ops_b == 2.5 * ops
    assert moved_b == (2 * t * (inner + bc) * 2 + 2 * small + t * inner * 4 + states
                       + 64 * inner * 4) == 416284672
    # both bound by bytes: 0.376 and 0.508 ms a call
    assert least((ops, moved)) == (pytest.approx(moved / 819e9), "memory")
    assert least((ops_b, moved_b)) == (pytest.approx(moved_b / 819e9), "memory")


@pytest.mark.parametrize("cell, channels, y_itemsize, fwd_mb, bwd_mb", [
    (NF, 6144, 2, 201.3, 302.0), (PF, 5120, 4, 251.7, 335.5)])
def test_the_convolutions_costs_are_its_passes_in_the_stored_types(
        cell, channels, y_itemsize, fwd_mb, bwd_mb):
    run = fake_run(cell, {})
    shape = run["flops"].conv_silu_shape(run["config"], 1, 8192)
    assert shape == dict(batch=1, t=8192, channels=channels, taps=4, x_itemsize=2,
                         y_itemsize=y_itemsize)
    elements = 8192 * channels
    _, fwd = run["flops"].conv_silu_fwd(**shape)
    _, bwd = run["flops"].conv_silu_bwd(**shape)
    assert fwd == elements * (2 + y_itemsize) + 5 * channels * 4
    assert bwd == elements * (4 + y_itemsize) + 45 * channels * 4
    assert fwd / 1e6 == pytest.approx(fwd_mb, abs=0.2) and bwd / 1e6 == pytest.approx(bwd_mb, abs=1.2)
    assert least(run["flops"].conv_silu_fwd(**shape))[1] == "memory"
    assert least(run["flops"].conv_silu_bwd(**shape))[1] == "memory"


def test_the_recurrences_reader_on_a_made_up_trace():
    flops = importlib.import_module("flops.nemotron_h")
    shape = flops.ssd_shape(fake_run(NF, {})["config"], 1, 8192)
    fwd, bwd = least(flops.ssd_fwd(**shape))[0], least(flops.ssd_bwd(**shape))[0]
    ops = {
        # three layers over four steps, at half their roofline; the plain
        # form's fusions, a flash call and a container beside them
        "%ssd_fwd.3 custom-call f32[1,8192,4096] tpu_custom_call/7": (12, 24 * fwd),
        "%ssd_bwd.4 custom-call bf16[1,8192,4096] tpu_custom_call/9": (12, 24 * bwd),
        "%attn_full.1 custom-call bf16[32,8192,128] tpu_custom_call/3": (4, 1.0),
        "%fusion.9 fusion f32[64,8,8,128,128]": (100, 0.5),
    }
    read = reader("ssd_roofline_pct")
    assert read(fake_run(NF, ops)) == pytest.approx(50.0)
    assert read(fake_run(NF, dict(list(ops.items())[:1]))) == pytest.approx(50.0)
    # a rehearsal without a trace, the plain form (no kernel call), a family
    # without the layer: nothing to read
    assert read({**fake_run(NF, ops), "trace": None}) is None
    assert read(fake_run(NF, dict(list(ops.items())[2:]))) is None
    assert read(fake_run(PF, ops)) is None


@pytest.mark.parametrize("cell, calls", [(NF, 3), (PF, 1)])
def test_the_convolutions_reader_on_a_made_up_trace(cell, calls):
    run = fake_run(cell, {})
    shape = run["flops"].conv_silu_shape(run["config"], 1, 8192)
    fwd = least(run["flops"].conv_silu_fwd(**shape))[0]
    bwd = least(run["flops"].conv_silu_bwd(**shape))[0]
    n = 4 * calls
    ops = {
        "%conv_silu_fwd.3 custom-call bf16[1,8192,6144] tpu_custom_call/3": (n, n * fwd / 0.7),
        "%conv_silu_bwd.5 custom-call bf16[1,8192,6144] tpu_custom_call/5": (n, n * bwd / 0.7),
        "%ssm_scan.1 custom-call f32[8192,5120] tpu_custom_call/6": (4, 1.0),
        "%fusion.9 fusion f32[1,8192,6144]": (100, 0.5),
    }
    read = reader("conv_silu_roofline_pct")
    assert read(fake_run(cell, ops)) == pytest.approx(70.0)
    # the forward alone reads the forward's share; it cannot pass 100 while
    # a call takes its bytes' time or longer
    assert read(fake_run(cell, {k: (c, c * fwd) for k, (c, _) in list(ops.items())[:1]})) == (
        pytest.approx(100.0))
    assert read({**fake_run(cell, ops), "trace": None}) is None
    assert read(fake_run(cell, dict(list(ops.items())[2:]))) is None
    other = {**fake_run(cell, ops), "flops": importlib.import_module("flops.lfm2_moe")}
    assert read(other) is None


def recorded_calls():
    with open(os.path.join(BENCH, "tests", "recorded_calls.json")) as f:
        calls = json.load(f)["calls"]
    return [(cell, name) for cell in sorted(calls) for name in sorted(calls[cell])], calls


@pytest.mark.parametrize("cell, name", recorded_calls()[0])
def test_a_kernels_count_comes_to_the_arrays_of_its_own_line(cell, name):
    """One source a count: the cost functions' bytes are the call's operands
    and results in their stored types, so they have to come to what the
    call's line lists (an operand handed over twice once), within 1 %,
    wherever the arrays lie.  A count that drifts from the kernel's operand
    list fails here, and not silently under a ``min`` in the reader."""
    import trace_reduce
    line = recorded_calls()[1][cell][name]
    run = fake_run(NF if cell == NF[2] else PF, {})
    flops, kind = run["flops"], name.split()[0].lstrip("%").split(".")[0]
    shape = getattr(flops, "ssd_shape" if kind.startswith("ssd") else "conv_silu_shape")(
        run["config"], 1, 8192)
    _, counted = getattr(flops, kind)(**shape)
    listed, fast = trace_reduce.call_bytes(line)
    assert counted == pytest.approx(listed, rel=0.01)
    assert 0 <= fast < listed


def test_the_readers_on_the_recorded_lines_take_off_what_lies_in_fast_memory():
    """``phi4flash-fused-t8192``'s two convolution calls at their roofline by
    the bytes that lie in HBM: the share reads 100, where the count alone
    (``x`` taken for HBM traffic) would read 150 forward."""
    calls = recorded_calls()[1][PF[2]]
    run = fake_run(PF, {})
    shape = run["flops"].conv_silu_shape(run["config"], 1, 8192)
    elements = 8192 * 5120
    ops = {}
    for name in calls:
        fwd = "conv_silu_fwd" in name
        in_hbm = elements * 4 if fwd else elements * 6       # y; dy and dx
        ops[name] = (14, 14 * in_hbm / 819e9)
    run = fake_run(PF, ops)
    run["trace"]["calls"] = calls
    assert reader("conv_silu_roofline_pct")(run) == pytest.approx(100.0, abs=0.01)
    counted = run["flops"].conv_silu_fwd(**shape)[1]
    assert counted / (elements * 4) == pytest.approx(1.5, abs=0.01)


def steps(n):
    return [{"name": "step_total", "party": "client", "span_id": i, "parent_id": None,
             "duration": 0.2, "attrs": {}} for i in range(n)]


def test_the_whole_steps_share_of_the_peak_over_the_devices_busy_time():
    run = fake_run(NF, {}, spans=steps(16))
    per_token = run["flops"].train_flops_per_token(run["config"], 8192)
    read = reader("step_mfu_pct")
    # 16 steps of one row of 8192 tokens over 3.2 s busy of a 4 s window:
    # the window's length, and so whatever slows the host, is no part of it
    assert read(run) == pytest.approx(100 * 16 * 8192 * per_token / (3.2 * 197e12))
    assert 0 < read(run) < 100
    longer = {**run, "trace": {**run["trace"], "window_s": 40.0}}
    assert read(longer) == read(run)
    four = {**run, "trace": {**run["trace"], "devices": 4}}
    assert read(four) == pytest.approx(read(run) / 4)
    # a party cell's step is one client's: its own rows, whatever the clients
    party = {**run, "job": {**run["job"], "clients": 4, "rows_per_client": 2}}
    assert read(party) == pytest.approx(2 * read(run))
    assert read({**run, "trace": None}) is None
    assert read({**run, "spans": []}) is None


def test_the_entries_of_the_two_kernels_and_of_the_step():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        by_name = {m["name"]: m for m in json.load(f)["per_layer"]}
    kernel = dict(unit="%", better="higher", source="device_trace", layer="kernels",
                  moves="mfu_pct")
    assert by_name["ssd_roofline_pct"] == dict(name="ssd_roofline_pct", **kernel,
                                               workloads=[NF[2]])
    conv = by_name["conv_silu_roofline_pct"]
    assert {**conv, "workloads": None} == dict(name="conv_silu_roofline_pct", **kernel,
                                               workloads=None)
    assert NF[2] in conv["workloads"] and set(conv["workloads"]) <= {NF[2], PF[2]}
    # every cell reports the step's own share beside its kernels': no list
    assert by_name["step_mfu_pct"] == dict(
        name="step_mfu_pct", unit="%", better="higher", source="device_trace",
        layer="device programs", moves="mfu_pct")
