"""The readers of the program's own spans, on hand-made records: medians,
the share, bytes per step, and nothing to read under five spans or without a
recorder.  And what ties them to the rest: names that do not collide with the
benchmark's own, one reader file per entry and the other way round."""

import json
import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import run
import trace_reduce

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, os.path.join(BENCH, "layer_metrics"))
import _spans

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)

SPAN_METRICS = [m for m in BENCHMARK["per_layer"] if m["source"] == "program_span"]
PARTY = ["gpt2m-party1-t1024", "vitl16-party-224"]
FUSED = ["gpt2m-fused-t1024", "vitl16-fused-224"]


def rec(span_id, name, ms, parent=None, party="client", **attrs):
    return {"span_id": span_id, "parent_id": parent, "name": name, "party": party,
            "duration": ms / 1e3, "attrs": attrs}


def party_window(steps=6):
    """``steps`` client steps of 100 ms: forward 20 (copies 1 + 5 beneath),
    transport 30 + k, backward 25 (a copy of 2 beneath), optimizer 24; the server's
    wait k, dispatch 4, d2h 20 beneath the wire."""
    recs, n = [], 0
    for k in range(steps):
        root = n = n + 1
        recs.append(rec(root, "step_total", 100.0 + k))
        fwd = n = n + 1
        recs.append(rec(fwd, "client_fwd", 20.0, root))
        recs.append(rec((n := n + 1), "h2d", 1.0, fwd, bytes=1000))
        recs.append(rec((n := n + 1), "d2h", 5.0, fwd, bytes=4000))
        tp = n = n + 1
        recs.append(rec(tp, "transport", 30.0 + k, root))
        wire = n = n + 1
        recs.append(rec(wire, "wire", 1.0, tp))
        recs.append(rec((n := n + 1), "queue_wait", float(k), wire, "server"))
        disp = n = n + 1
        recs.append(rec(disp, "dispatch", 4.0, wire, "server"))
        recs.append(rec((n := n + 1), "h2d", 0.5, disp, "server", bytes=4040))
        recs.append(rec((n := n + 1), "d2h", 20.0 + 2 * k, wire, "server", bytes=4004))
        bwd = n = n + 1
        recs.append(rec(bwd, "client_bwd", 25.0, root))
        recs.append(rec((n := n + 1), "h2d", 2.0, bwd, bytes=5000))
        recs.append(rec((n := n + 1), "opt_apply", 24.0 - k, root))
    return recs


def fused_window(steps=6):
    recs, n = [], 0
    for k in range(steps):
        root = n = n + 1
        recs.append(rec(root, "step_total", 50.0 + k))
        recs.append(rec((n := n + 1), "h2d", 2.0, root, bytes=300))
        recs.append(rec((n := n + 1), "dispatch", 1.0, root))
        recs.append(rec((n := n + 1), "loss_wait", 45.0, root))
    return recs


def value(metric, spans):
    return run.layer_reader(metric)({"spans": spans})


def test_party_readers_on_a_hand_made_window():
    recs = party_window(6)
    assert value("queue_wait_ms_p50", recs) == pytest.approx(2.5)      # 0..5
    assert value("lock_hold_ms_p50", recs) == pytest.approx(4.0)
    assert value("reply_d2h_ms_p50", recs) == pytest.approx(25.0)      # 20..30, the server's
    assert value("client_opt_apply_ms_p50", recs) == pytest.approx(21.5)
    # steps 100..105 = 615 ms, transport 30..35 = 195 ms
    assert value("client_host_share_pct", recs) == pytest.approx(100 * (615 - 195) / 615)
    assert value("host_copy_bytes_per_step", recs) == pytest.approx(
        1000 + 4000 + 4040 + 4004 + 5000)
    assert value("fused_host_ms_per_step", recs) is None               # no loss_wait


def test_fused_readers_on_a_hand_made_window():
    recs = fused_window(6)
    assert value("fused_host_ms_per_step", recs) == pytest.approx(7.5)  # 5..10
    assert value("host_copy_bytes_per_step", recs) == pytest.approx(300)
    for metric in ("queue_wait_ms_p50", "lock_hold_ms_p50", "reply_d2h_ms_p50",
                   "client_opt_apply_ms_p50", "client_host_share_pct"):
        assert value(metric, recs) is None
    # the fused step's own dispatch is the client's, not a lock held
    assert [r["party"] for r in recs if r["name"] == "dispatch"] == ["client"] * 6


def test_a_groups_dispatch_and_d2h_count_once():
    """Under coalescing a group's lock-held window and its copy are one span
    each, whatever the group's size; the wait stays one a request."""
    recs = []
    for g in range(5):
        recs += [rec(10 * g + k, "queue_wait", 1.0 + k, party="server") for k in range(4)]
        recs.append(rec(10 * g + 8, "dispatch", 3.0 + g, party="server", group=4))
        recs.append(rec(10 * g + 9, "d2h", 40.0 + g, party="server", bytes=7))
    assert value("lock_hold_ms_p50", recs) == pytest.approx(5.0)
    assert value("reply_d2h_ms_p50", recs) == pytest.approx(42.0)
    assert value("queue_wait_ms_p50", recs) == pytest.approx(2.5)


@pytest.mark.parametrize("metric", [m["name"] for m in SPAN_METRICS])
def test_nothing_to_read_under_five_spans_or_without_a_recorder(metric, monkeypatch):
    assert value(metric, party_window(4)) is None
    assert value(metric, fused_window(4)) is None
    assert value(metric, []) is None
    # no recorder output: the program recorded nothing, or is a parent of PR 24
    monkeypatch.setattr(_spans, "program_records", lambda: None)
    assert run.layer_reader(metric)({}) is None
    monkeypatch.undo()
    from split_learning_tpu import obs
    monkeypatch.delattr(obs, "recorded")
    assert run.layer_reader(metric)({}) is None


def test_the_readers_take_the_programs_last_session(monkeypatch):
    monkeypatch.setattr(_spans, "program_records", lambda: party_window(5))
    assert run.layer_reader("lock_hold_ms_p50")({}) == pytest.approx(4.0)
    assert _spans.records({"spans": fused_window(5)})[0]["name"] == "step_total"


def test_program_span_names_are_not_the_benchmarks_own():
    from split_learning_tpu.obs import spans
    own = set(run.HARNESS_SPANS) | {trace_reduce.WINDOW_SPAN}
    assert not own & set(spans.ALL_SPANS)
    # an idle gap is named by either: the harness's wrappers and, beneath them,
    # every span of the program, imported and not copied
    assert run.host_spans() == run.HARNESS_SPANS + tuple(spans.ALL_SPANS)
    assert {"step_total", "queue_wait", "dispatch", "d2h", "h2d", "opt_apply",
            "transport", "loss_wait", "round"} <= set(spans.ALL_SPANS)


def test_every_span_metric_has_its_reader_and_every_reader_its_entry():
    names = {m["name"] for m in BENCHMARK["per_layer"]}
    files = {f[:-3] for f in os.listdir(os.path.join(BENCH, "layer_metrics"))
             if f.endswith(".py") and not f.startswith("_")}
    assert files == names
    # PR 24's seven, looked up by name: later PRs append entries after them
    by_name = {m["name"]: m for m in BENCHMARK["per_layer"]}
    span_metrics = [by_name[name] for name in (
        "queue_wait_ms_p50", "lock_hold_ms_p50", "reply_d2h_ms_p50",
        "client_opt_apply_ms_p50", "client_host_share_pct", "fused_host_ms_per_step",
        "host_copy_bytes_per_step")]
    assert all(m in SPAN_METRICS for m in span_metrics)
    cells = {m["name"]: m["workloads"] for m in span_metrics}
    assert cells.pop("fused_host_ms_per_step") == FUSED
    assert sorted(cells.pop("host_copy_bytes_per_step")) == sorted(PARTY + FUSED)
    assert all(w == PARTY for w in cells.values())
    for m in span_metrics:
        assert m["better"] == "lower" and m["layer"] in ("runtime", "transport")
        assert m["moves"] in ("reply_ms_p50", "tokens_per_s")
