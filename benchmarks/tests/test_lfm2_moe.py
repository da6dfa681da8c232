"""The lfm2-24b-a2b configuration: FLOPs against a hand count, the kernels'
costs at the true head width, both readers on a made-up trace with and
without the step's ``counters_read`` records, the file against the catalog's
published sizes and the plan's arguments, the parameter counts of the cut and
of the whole model, and the CPU rehearsal of the cell."""

import importlib
import json
import os
import subprocess
import sys

import pytest

from flops import common, lfm2_moe as flops

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "lfm2-moe-fused-t8192"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", "lfm2-24b-a2b.json")) as f:
        return json.load(f)


def test_per_token_flops_against_a_hand_count(config):
    kw = config["plan"]["kwargs"]
    conv = 2048 * 6144 + 2048 * 2048
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512
    assert flops.conv_params(kw) == conv == 16777216
    assert flops.attention_params(kw) == attn == 10485760
    expert = 3 * 2048 * 1536                                  # 9 437 184
    # a token sends 4 * 8 / 64 = half a pair here under even routing
    assert flops.expected_pairs_per_token(kw) == 0.5
    dense = conv + 3 * 2048 * 11776
    assert flops.layer_matmul_params(kw, 1, 0.5) == dense == 89128960
    routed = 2048 * 64 + 0.5 * expert
    assert flops.layer_matmul_params(kw, 2, 0.5) == attn + routed
    assert flops.layer_matmul_params(kw, 3, 0.5) == conv + routed
    head = 2048 * 8192
    weights = dense + attn + 3 * conv + 4 * routed + head
    # the parts: four conv operators 67.1 M, the attention's projections 10.5,
    # the dense MLP 72.4, four routers 0.5, the routed experts 18.9, the head 16.8
    assert [round(x / 1e6, 1) for x in (
        4 * conv, attn, 3 * 2048 * 11776, 4 * 2048 * 64, 4 * 0.5 * expert,
        head, weights)] == [67.1, 10.5, 72.4, 0.5, 18.9, 16.8, 186.1]
    assert flops.attention_layers(kw) == 1
    scores = 2 * 2 * 32 * 64 * 4096.5                  # one layer, the keys seen
    assert flops.forward_flops_per_token(config, 8192) == 2 * weights + scores
    total = flops.train_flops_per_token(config, 8192)
    assert total == 3 * (2 * weights + scores)
    assert round(6 * weights / 1e9, 3) == 1.117 and round(3 * scores / 1e9, 3) == 0.101
    assert round(total / 1e9, 3) == 1.217
    assert round(total * 8192 / 1e12, 2) == 9.97              # a step
    share = lambda w: round(6 * w / total, 2)
    assert [share(4 * conv), share(3 * 2048 * 11776), share(4 * 0.5 * expert),
            round((6 * attn + 3 * scores) / total, 2), share(head)] == [
                0.33, 0.36, 0.09, 0.13, 0.08]


def test_the_whole_models_count_from_the_same_functions(config):
    kw = config["plan"]["kwargs"]
    published = config["published"]
    whole = flops.model_params(kw, range(published["num_hidden_layers"]),
                               published["num_experts"], published["vocab_size"])
    assert round(whole / 1e9, 2) == 23.98
    active = flops.model_params(kw, range(40), kw["experts_per_token"], 65536,
                                tied=True)
    assert round(active / 1e9, 2) == 2.33
    cut = flops.model_params(kw, kw["layers_kept"], kw["experts_held"], kw["vocab"])
    assert round(cut / 1e6, 1) == 486.1


def test_kernel_costs_at_the_true_head_width(config):
    shape = flops.attention_shape(config, 1, 8192)
    assert shape == dict(batch=1, heads=32, kv_heads=8, t=8192, head_dim=64)
    ops, moved = flops.attn_fwd(**shape, window=None)
    assert ops == 2 * 2 * 32 * 64 * 8192 * 4096.5
    assert moved == (2 * 32 + 2 * 8) * 8192 * 64 * 2
    ops_b, moved_b = flops.attn_bwd(**shape, window=None)
    assert ops_b == 2.5 * ops and moved_b == 2 * moved
    for cost in ((ops, moved), (ops_b, moved_b)):
        assert common.least_seconds(*cost, PEAK)[1] == "compute"
    # 1.40 ms forward and 3.49 ms backward a call at the peak: half of what
    # trinity-mini's full layer is held to, whose head fills the 128 lanes
    assert common.least_seconds(ops, moved, PEAK)[0] == pytest.approx(1.3955e-3, rel=1e-3)
    mm = flops.expert_mm_shape(config, 1, 8192)
    assert mm == dict(pairs=4096.0, experts=8, d_model=2048, width=1536)
    ops, moved = flops.expert_mm(**mm)
    assert ops == 2 * 4096 * 2048 * 1536
    assert moved == 4096 * (2048 + 1536) * 2 + 8 * 2048 * 1536 * 2
    assert common.least_seconds(ops, moved, PEAK)[1] == "compute"
    assert common.least_seconds(*flops.expert_mm(**mm, weight_itemsize=4), PEAK)[1] == "memory"


def test_the_file_holds_the_published_sizes(config):
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f if line.strip()]
    entry = next(r for r in rows if r["name"] == "LFM2-24B-A2B")
    assert config["source"] == entry["source_url"]
    reduced = ["num_dense_layers", "num_experts", "num_hidden_layers", "vocab_size"]
    assert sorted(config["reduced"]) == reduced
    for key, value in entry["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    assert config["published"] == {"num_hidden_layers": 40, "num_dense_layers": 2,
                                   "num_experts": 64, "vocab_size": 65536}
    kw = config["plan"]["kwargs"]
    for ours, theirs in (
            ("d_model", "hidden_size"), ("num_heads", "num_attention_heads"),
            ("num_kv_heads", "num_key_value_heads"), ("conv_taps", "conv_L_cache"),
            ("dense_width", "intermediate_size"),
            ("expert_width", "moe_intermediate_size"), ("experts_held", "num_experts"),
            ("experts_per_token", "num_experts_per_tok"),
            ("route_scale", "routed_scaling_factor"), ("layer_types", "layer_types"),
            ("norm_eps", "norm_eps"), ("vocab", "vocab_size")):
        assert kw[ours] == config[theirs], ours
    assert kw["rope_theta"] == config["rope_parameters"]["rope_theta"]
    assert kw["head_dim"] * kw["num_heads"] == kw["d_model"] and kw["head_dim"] == 64
    assert kw["experts_total"] == config["published"]["num_experts"]
    assert kw["dense_layers"] == config["published"]["num_dense_layers"]
    kept = kw["layers_kept"]
    assert len(kept) == config["num_hidden_layers"] == 5
    assert sum(i < kw["dense_layers"] for i in kept) == config["num_dense_layers"] == 1
    assert [kw["layer_types"][i] for i in kept] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    assert config["data"]["vocab"] == kw["vocab"] and kw["vocab"] * 8 == 65536
    assert kw["experts_held"] * 8 == kw["experts_total"] and kw["expert_offset"] == 0
    assert kw["client_depth"] == 1 and config["conv_bias"] is False
    for key in ("deployment", "layers_kept", "departures"):
        assert config[key]
    for key in ("head_dim", "qk_norm", "rope", "conv", "norms", "router",
                "tie_word_embeddings", "cut", "optimizer", "precision", "weights",
                "data", "fit", "remat"):
        assert config["assumed"][key], key
        assert "TO_BE_SETTLED" not in config["assumed"][key], key


def test_the_plan_takes_the_files_arguments_and_counts_the_cut(config):
    """The plan builds from ``plan.kwargs`` to the letter, and its stages
    hold what the file's ``cut`` says: 105.9 M on the client, 380.1 M on
    the server."""
    import jax
    import jax.numpy as jnp
    from split_learning_tpu.models.factory import get_plan
    spec = config["plan"]
    plan = get_plan(spec["model"], spec["mode"], jnp.dtype(spec["dtype"]), **spec["kwargs"])
    tokens = jax.ShapeDtypeStruct((1, 64), jnp.int32)
    client = jax.eval_shape(plan.stages[0].init, jax.random.PRNGKey(0), tokens)
    cut = jax.eval_shape(plan.stages[0].apply, client, tokens)
    server = jax.eval_shape(plan.stages[1].init, jax.random.PRNGKey(0), cut)
    count = lambda tree: sum(x.size for x in jax.tree_util.tree_leaves(tree))
    assert round(count(client) / 1e6, 1) == 105.9
    assert round(count(server) / 1e6, 1) == 380.1
    assert round(count(client["params"]["layer1"]) / 1e6, 2) == 89.14
    assert round(count(server["params"]["layer2"]) / 1e6, 2) == 86.12
    assert round(count(server["params"]["layer3"]) / 1e6, 2) == 92.42
    total = count(client) + count(server)
    assert round(total / 1e6, 1) == 486.1
    kw = spec["kwargs"]
    assert total == flops.model_params(kw, kw["layers_kept"], kw["experts_held"], kw["vocab"])
    from split_learning_tpu.models.afmoe import pair_rungs
    assert pair_rungs(8192 * 4, 8, 64) == (8192, 16384, 32768)


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "reference", "lfm2_moe.py")) as f:
        text = f.read()
    assert "split_learning_tpu" not in text


def fake_run(config, ops, module="flops.lfm2_moe", spans=None):
    run = {"trace": {"op_seconds": {n: s for n, (_, s) in ops.items()},
                     "op_counts": {n: c for n, (c, _) in ops.items()}},
           "job": {"rows_per_client": 1, "tokens_per_row": 8192, "clients": 1},
           "config": config, "flops": importlib.import_module(module), "peak": PEAK}
    if spans is not None:
        run["spans"] = spans
    return run


def counters(pairs_by_layer, steps):
    """``counters_read`` records as the fused step writes them."""
    layers = [f"trunk_head/layer{i}/experts" for i in range(2, 2 + len(pairs_by_layer))]
    return [{"name": "counters_read", "party": "client", "span_id": k, "parent_id": 0,
             "duration": 1e-3, "start_ns": k,
             "attrs": {"layers": layers, "pairs": pairs_by_layer,
                       "rows": [8192] * len(layers),
                       "ladder": [[8192, 16384, 32768]] * len(layers)}} for k in range(steps)]


def reader(name):
    sys.path.insert(0, BENCH)
    import run
    return run.layer_reader(name)


def test_the_attention_reader_on_a_made_up_trace(config):
    shape = flops.attention_shape(config, 1, 8192)
    least = lambda cost: common.least_seconds(*cost, PEAK)[0]
    fwd, bwd = (least(f(**shape, window=None)) for f in (flops.attn_fwd, flops.attn_bwd))
    ops = {
        # (calls, seconds): one forward and one backward call a step over
        # four steps, at a third of their roofline; other calls beside them
        "%attn_full.1 custom-call bf16[32,8192,128] tpu_custom_call/3": (4, 12 * fwd),
        "%attn_full.2 custom-call f32[32,8192,128] tpu_custom_call/6": (4, 12 * bwd),
        "%attn_window.1 custom-call bf16[32,8192,128] tpu_custom_call/3": (4, 1.0),
        "%gmm.3 custom-call bf16[8192,1536] tpu_custom_call/4": (36, 0.01),
        "%fusion.9 fusion bf16[8192,2048]": (100, 0.092),
    }
    read = reader("attn_full_roofline_pct")        # one reader, costed at this head of 64
    assert read(fake_run(config, ops)) == pytest.approx(100 / 3)
    only = dict(list(ops.items())[:1])
    assert read(fake_run(config, only)) == pytest.approx(100 / 3)
    # a rehearsal without a trace, a program without the scope: nothing to read
    assert read({**fake_run(config, ops), "trace": None}) is None
    assert read(fake_run(config, {"%fusion.1 fusion f32[8]": (1, 1.0)})) is None


def test_the_expert_reader_costs_the_pairs_the_records_hold(config):
    """Every ``gmm`` and ``tgmm`` call at the mean pairs a layer held in a
    step of the window, so a seed that routes twice the even share here is
    held to twice the work, not read as half as fast."""
    mm = flops.expert_mm_shape(config, 1, 8192)
    least = lambda pairs, **kw: common.least_seconds(
        *flops.expert_mm(**{**mm, "pairs": pairs}, **kw), PEAK)[0]
    # four layers, five steps: 9 gmm and 3 tgmm calls a layer and step
    held = [[1000] * 8, [1024] * 8, [1024] * 8, [1048] * 8]      # mean 8192
    spans = counters(held, 5)
    ops = {"%gmm.1 custom-call bf16[8192,1536] tpu_custom_call/4":
           (180, 2 * 180 * least(8192.0)),
           "%tgmm.1 custom-call f32[8,2048,1536] tpu_custom_call/4":
           (60, 2 * 60 * least(8192.0, weight_itemsize=4)),
           "%attn_full.1 custom-call bf16[32,8192,128] tpu_custom_call/3": (5, 1.0)}
    read = reader("moe_expert_mm_roofline_pct")
    got = read(fake_run(config, ops, spans=spans))
    assert got == pytest.approx(50.0)
    # held to the even count, 4096 pairs, the same calls would read about half
    even = (180 * least(4096.0) + 60 * least(4096.0, weight_itemsize=4)) / (
        2 * 180 * least(8192.0) + 2 * 60 * least(8192.0, weight_itemsize=4))
    assert 100 * even < 0.6 * got
    # no records (a program without the counters), too few of them, no trace
    # (a rehearsal), no grouped product in the trace: nothing to read
    assert read(fake_run(config, ops, spans=[])) is None
    assert read(fake_run(config, ops, spans=counters(held[:1], 2))) is None
    assert read({**fake_run(config, ops, spans=spans), "trace": None}) is None
    assert read(fake_run(config, dict(list(ops.items())[2:]), spans=spans)) is None
    # it cannot pass 100: a call cannot run faster than its least time
    fast = {k: (c, s / 2) for k, (c, s) in list(ops.items())[:2]}
    assert read(fake_run(config, fast, spans=spans)) == pytest.approx(100.0)


def test_the_new_entries_of_the_benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["config"] == "lfm2-24b-a2b" and cell["chips"] == 1
    assert cell["traffic"] == CELL and len(cell["why"]) <= 200
    entry = next(c for c in bench["configs"] if c["name"] == "lfm2-24b-a2b")
    assert entry["file"] == "benchmarks/configs/lfm2-24b-a2b.json"
    assert sorted(entry["reduced"]) == ["num_dense_layers", "num_experts",
                                        "num_hidden_layers", "vocab_size"]
    assert len(entry["why"]) <= 200
    # looked up by name: the cell reads the mechanisms it runs through the
    # readers every routed cell shares (PR 49), none under a name of its own
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert not [name for name in by_name if name.startswith("lfm2_")]
    for name in ("attn_full_roofline_pct", "moe_expert_mm_roofline_pct"):
        metric = by_name[name]
        assert {**metric, "workloads": None} == dict(
            name=name, unit="%", better="higher", source="device_trace",
            layer="kernels", moves="mfu_pct", workloads=None)
        assert CELL in metric["workloads"]
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", name + ".py"))
    assert [m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])] == [
        "attn_full_roofline_pct", "moe_expert_mm_roofline_pct",
        "moe_dispatch_ops_share_pct", "moe_rung_fill_pct", "moe_top_rung_share_pct",
        "moe_pairs_x_even_p50"]
    with open(os.path.join(BENCH, "traffic", CELL + ".json")) as f:
        job = json.load(f)
    assert (job["path"], job["clients"], job["rows_per_client"], job["tokens_per_row"],
            job["pool"], job["check_steps"], job["reference_row_block"]) == (
                "fused", 1, 1, 8192, 8, 3, 1)
    assert set(job["limits"]) == {"loss_gap", "grad_norm_gap", "delta_norm_gap"}
    assert job["limits_note"] and job["rehearsal"]["limits_note"] and job["fit"]
    assert "TO_BE_SETTLED" not in json.dumps(job)


def test_the_cpu_rehearsal_of_the_cell_prints_correct():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "2147489321", "--seconds", "2", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
