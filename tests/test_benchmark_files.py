"""``BENCHMARK.json`` against the files it names under ``benchmarks/`` and
the program's registry, read and never changed: every per-layer entry has
its reader, every reader its entry, every cell its configuration and
traffic files, every configuration its source and a model the program
registers. ``benchmarks/tests/`` holds the harness's own tests and is
outside tier-1; this file is what tier-1 sees of the benchmark's layout."""

import glob
import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCHMARKS = os.path.join(ROOT, "benchmarks")
READERS = os.path.join(BENCHMARKS, "layer_metrics")
# traffic.py, and what the readers import beside themselves (flops/)
if BENCHMARKS not in sys.path:
    sys.path.insert(0, BENCHMARKS)

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = {w["name"]: w for w in BENCH["workloads"]}
CONFIGS = {c["name"]: c for c in BENCH["configs"]}
END_TO_END = {m["name"] for m in BENCH["end_to_end"]}


def by_name(entries):
    return pytest.mark.parametrize("entry", entries,
                                   ids=[e["name"] for e in entries])


@by_name(BENCH["per_layer"])
def test_a_per_layer_entry_has_its_reader_its_cells_and_what_it_moves(entry):
    path = os.path.join(READERS, entry["name"] + ".py")
    assert os.path.isfile(path), path
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + entry["name"], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    assert callable(module.read)
    assert set(entry.get("workloads", CELLS)) <= set(CELLS)
    assert entry["moves"] in END_TO_END


def test_every_reader_file_is_a_per_layer_entry():
    files = {os.path.basename(p)[:-3]
             for p in glob.glob(os.path.join(READERS, "[a-z]*.py"))}
    assert files == {m["name"] for m in BENCH["per_layer"]}


@by_name(BENCH["workloads"])
def test_a_cell_has_its_configuration_its_traffic_and_its_chips(entry):
    import traffic
    assert entry["chips"] in (1, 4)
    with open(os.path.join(ROOT, CONFIGS[entry["config"]]["file"])) as f:
        config = json.load(f)
    job = traffic.load(entry["traffic"])    # refuses a file that lacks a key
    assert job["path"] + ".py" in os.listdir(os.path.join(BENCHMARKS, "paths"))
    assert config["data"]["kind"] in ("tokens", "images")
    assert traffic.tokens_per_step(job) > 0


@by_name(BENCH["configs"])
def test_a_configuration_names_its_source_its_cuts_and_a_registered_model(
        entry):
    from split_learning_tpu.models import factory
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    assert entry["source"].startswith("https://") and config["source"]
    assert len(entry["source"]) <= 200
    assert config["reduced"] == entry["reduced"]
    # what a cut replaced: the published value of every reduced key
    assert set(config.get("published", {})) == set(entry["reduced"])
    assert config["plan"]["model"] in factory._FAMILIES
    assert any(w["config"] == entry["name"] for w in BENCH["workloads"])
