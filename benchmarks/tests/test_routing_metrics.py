"""The readers of the routed layer's step counters (``layer_metrics/_routing.py``)
on hand-made ``counters_read`` records: one layer that stays in its lower rung,
one that passes it in the middle of the window; nothing under ``MIN_SPANS``
samples, nothing where the program recorded no counters (a parent of PR 35)."""

import json
import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import run

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, os.path.join(BENCH, "layer_metrics"))
import _spans

METRICS = ("moe_rung_fill_pct", "moe_top_rung_share_pct", "moe_pairs_x_even_p50")
CELLS = ["trinity-mini-fused-t8192", "joyai-flash-fused-t8192",
         "lfm2-moe-fused-t8192", "nemotronh-moe-fused-t8192"]
LADDER = [8192, 65536]


def window(steps=6, up_from=3):
    """``steps`` fused steps of two routed layers, 8 held experts each: ``a``
    gets 4096 pairs (the even share) every step; ``b`` gets 6144 until step
    ``up_from`` and 12288 from then on, past its lower rung."""
    recs, n = [], 0
    for k in range(steps):
        root = n = n + 1
        recs.append({"span_id": root, "parent_id": None, "name": "step_total",
                     "party": "client", "duration": 0.25, "attrs": {}})
        b = 768 if k < up_from else 1536
        recs.append({"span_id": (n := n + 1), "parent_id": root, "name": "counters_read",
                     "party": "client", "duration": 0.0002, "attrs": {
                         "layers": ["a", "b"], "pairs": [[512] * 8, [b] * 8],
                         "rows": [8192, 8192 if k < up_from else 65536],
                         "ladder": [LADDER, LADDER]}})
    return recs


def value(metric, spans):
    _, cell, config = run.load_cell(CELLS[0])
    import flops.afmoe
    import traffic
    return run.layer_reader(metric)({"spans": spans, "config": config, "flops": flops.afmoe,
                                     "job": traffic.load(cell["traffic"])})


def test_the_three_readers_on_a_hand_made_window():
    recs = window(6, 3)
    # a: 6 x 4096 in 8192; b: 3 x 6144 in 8192 and 3 x 12288 in 65536
    pairs = 6 * 4096 + 3 * 6144 + 3 * 12288
    rows = 6 * 8192 + 3 * 8192 + 3 * 65536
    assert value("moe_rung_fill_pct", recs) == pytest.approx(100 * pairs / rows)
    assert value("moe_top_rung_share_pct", recs) == pytest.approx(100 * 3 / 12)
    # over even (8192 x 8 x 8 / 128 = 4096): 1.0 six times, 1.5 and 3.0 three times each
    assert value("moe_pairs_x_even_p50", recs) == pytest.approx(1.25)


def test_a_middle_rung_is_not_the_top_rung():
    """Since PR 41 a ladder has a rung between the lowest and the worst case;
    the share counts the samples that ran the ladder's last rung, as its name
    says, and a sample on the middle rung only lowers the fill."""
    recs = window(6, 3)
    for k, r in enumerate(x for x in recs if x["name"] == "counters_read"):
        r["attrs"]["ladder"] = [[8192, 16384, 65536]] * 2
        r["attrs"]["rows"] = [8192, (8192, 8192, 8192, 16384, 16384, 65536)[k]]
    assert value("moe_top_rung_share_pct", recs) == pytest.approx(100 * 1 / 12)
    pairs = 6 * 4096 + 3 * 6144 + 3 * 12288
    rows = 6 * 8192 + 3 * 8192 + 2 * 16384 + 65536
    assert value("moe_rung_fill_pct", recs) == pytest.approx(100 * pairs / rows)


def test_a_window_that_stays_low_reads_no_top_rung():
    recs = window(6, 6)
    assert value("moe_top_rung_share_pct", recs) == 0.0
    assert value("moe_rung_fill_pct", recs) == pytest.approx(100 * (4096 + 6144) / (2 * 8192))


@pytest.mark.parametrize("metric", METRICS)
def test_nothing_to_read_under_five_samples_or_without_counters(metric, monkeypatch):
    assert value(metric, window(2)) is None           # four samples
    assert value(metric, window(3)) is not None       # six
    # a parent of PR 35: steps, and no counters_read among them
    steps_only = [r for r in window(6) if r["name"] != "counters_read"]
    assert value(metric, steps_only) is None
    monkeypatch.setattr(_spans, "program_records", lambda: None)
    assert run.layer_reader(metric)({}) is None


def test_the_entries_name_the_four_routed_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    # looked up by name: later PRs append their own entries after these
    for name in METRICS:
        m = entries[name]
        assert (m["source"], m["layer"], m["moves"], m["workloads"]) == (
            "program_span", "device programs", "tokens_per_s", CELLS)
    assert entries["moe_rung_fill_pct"]["better"] == "higher"
    assert entries["moe_top_rung_share_pct"]["better"] == "lower"
    assert entries["moe_pairs_x_even_p50"]["better"] == "lower"
    from split_learning_tpu.obs import spans
    assert "counters_read" in spans.ALL_SPANS
