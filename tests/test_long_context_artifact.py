"""The committed long-context TPU artifact
(``artifacts/bench_tpu_transformer_*.json``; the script that wrote it
was deleted with bench.py by PR 28): dense (XLA) vs Pallas-flash
attention across context lengths on one v5e chip.

The two claims the docs make from it, pinned here so the artifact and the
prose cannot drift:
1. every published throughput leg passed bench.py's own gate
   (util <= 1.0, work-scaling window), and
2. the memory-ceiling story is real — at the longest context the dense
   path fails with an HBM OOM while the flash path trains.
"""

import glob
import json
import os

import pytest

_PAT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "artifacts", "bench_tpu_transformer_*.json")


@pytest.fixture(scope="module")
def artifact():
    paths = sorted(glob.glob(_PAT))
    assert paths, f"missing {_PAT}: a committed file, nothing makes it anew"
    with open(paths[-1]) as f:
        return json.load(f)


def test_every_ok_leg_passed_the_publication_gate(artifact):
    oks = [l for l in artifact["legs"] if l.get("status") == "ok"]
    assert oks, "artifact contains no successful legs"
    for leg in oks:
        assert leg["valid"] is True
        assert leg["util_vs_bf16_peak"] <= 1.0
        assert 1.5 <= leg["linearity_2x"] <= 2.6
        assert leg["platform"] == "tpu"
        assert leg["dtype"] == "bfloat16"


def test_memory_ceiling_dense_oom_flash_trains(artifact):
    legs = artifact["legs"]
    t_max = max(l["seq_len"] for l in legs)
    dense = next(l for l in legs
                 if l["seq_len"] == t_max and l["attn"] == "full")
    flash = next(l for l in legs
                 if l["seq_len"] == t_max and l["attn"] == "flash")
    assert dense["status"] == "oom", (
        f"dense at T={t_max} was expected to exceed HBM, got "
        f"{dense['status']}")
    assert flash["status"] == "ok" and flash["steps_per_sec"] > 0


def test_both_paths_measured_at_shared_contexts(artifact):
    """At every T where both paths succeeded, the artifact carries a
    comparable (same batch, same dtype) pair."""
    legs = artifact["legs"]
    by_t = {}
    for leg in legs:
        if leg.get("status") == "ok":
            by_t.setdefault(leg["seq_len"], {})[leg["attn"]] = leg
    pairs = {t: v for t, v in by_t.items() if {"full", "flash"} <= set(v)}
    assert pairs, "no context length has both dense and flash measured"
    for t, pair in pairs.items():
        assert pair["full"]["batch"] == pair["flash"]["batch"]
