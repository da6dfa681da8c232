"""The benchmark is driven by data: every name in BENCHMARK.json finds its
file, the same seed gives the same inputs and weights, and a large seed
works."""

import json
import os

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import run
import traffic
import weights

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)


@pytest.mark.parametrize("cell", [w["name"] for w in BENCHMARK["workloads"]])
def test_every_cell_finds_its_files(cell):
    _, entry, config = run.load_cell(cell)
    job = traffic.load(entry["traffic"])
    assert os.path.exists(os.path.join(BENCH, "paths", job["path"] + ".py"))
    for module in ("flops", "reference"):
        assert os.path.exists(os.path.join(BENCH, module, config["family"] + ".py"))
    for key in ("source", "reduced", "assumed", "departures", "plan", "train", "data"):
        assert key in config
    assert set(job["limits"]) == {"loss_gap", "grad_norm_gap", "delta_norm_gap"}
    reported = {m["name"] for m in run.metrics_of(BENCHMARK, cell, "end_to_end")}
    assert {"setup_s", "tokens_per_s", "mfu_pct"} <= reported
    assert ("reply_ms_p50" in reported) == (job["path"] == "party")


@pytest.mark.parametrize("metric", [m["name"] for m in BENCHMARK["per_layer"]])
def test_every_per_layer_metric_has_a_reader(metric):
    assert callable(run.layer_reader(metric))
    moved = {m["name"] for m in BENCHMARK["end_to_end"]}
    entry = next(m for m in BENCHMARK["per_layer"] if m["name"] == metric)
    assert entry["moves"] in moved


def test_the_same_seed_gives_the_same_inputs_and_another_gives_others():
    _, entry, config = run.load_cell("gpt2m-fused-t1024")
    config, job = run.rehearsal_sizes(config, traffic.load(entry["traffic"]))
    big = 2 ** 31 + 12345
    a, b = (traffic.batches(job, config["data"], big) for _ in range(2))
    other = traffic.batches(job, config["data"], big + 1)
    assert len(a) == job["pool"] and len(a[0]) == job["clients"]
    for step_a, step_b, step_o in zip(a, b, other):
        for (xa, ya), (xb, yb), (xo, _) in zip(step_a, step_b, step_o):
            assert np.array_equal(xa, xb) and np.array_equal(ya, yb)
            assert not np.array_equal(xa, xo)
            assert np.array_equal(xa[:, 1:], ya[:, :-1])  # labels are the next token
    rows = {bytes(x) for step in a for x, _ in step for x in x}
    assert len(rows) == job["pool"] * job["clients"] * job["rows_per_client"]


def test_weights_come_from_the_seed_through_plan_init():
    import jax
    _, entry, config = run.load_cell("vitl16-party-224")
    config, job = run.rehearsal_sizes(config, traffic.load(entry["traffic"]))
    key = weights.seed_key(2 ** 31 + 7)
    pool = traffic.batches(job, config["data"], 1)
    plan, shapes, parties = run.seeded_model(config, job, key, pool)
    clients, server = parties()
    assert len(clients) == job["clients"] == 4
    # what the program's parties get from plan.init is what the reference is handed
    for i, client in enumerate(clients):
        got = plan.init(weights.client_key(key, i, job["clients"]), pool[0][0][0])[0]
        assert all(np.array_equal(a, b) for a, b in zip(
            jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(client)))
    served = plan.init(key, pool[0][0][0])[1]
    assert all(np.array_equal(a, b) for a, b in zip(
        jax.tree_util.tree_leaves(served), jax.tree_util.tree_leaves(server)))
    first, second = (jax.tree_util.tree_leaves(c)[0] for c in clients[:2])
    assert not np.array_equal(first, second)  # each client its own bottom
    scale = server["params"]["head"]["ln_f"]["scale"]
    assert abs(float(scale.mean()) - 1.0) < 0.02
