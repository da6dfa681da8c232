"""The nemotron-labs-twotower-30b-a3b-base configuration: FLOPs against a
hand count, the kernels' costs at the published heads and expert width, both
readers on a made-up trace with and without the step's ``counters_read``
records, the file against the catalog's published sizes and the plan's
arguments, the parameter counts of the cut and of the whole model, and the
CPU rehearsal of the cell."""

import importlib
import json
import os
import subprocess
import sys

import pytest

from flops import common, nemotron_h as flops

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "nemotronh-moe-fused-t8192"
NAME = "nemotron-labs-twotower-30b-a3b-base"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
READERS = ["attn_full_roofline_pct", "moe_expert_mm_roofline_pct"]   # shared (PR 49)
KERNELS = ["ssd_roofline_pct", "conv_silu_roofline_pct"]
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        return json.load(f)


def test_per_token_flops_against_a_hand_count(config):
    kw = config["plan"]["kwargs"]
    mamba = 2688 * (4096 + 6144 + 64) + 4096 * 2688
    attn = 2 * 2688 * 4096 + 2 * 2688 * 256
    assert flops.mamba_params(kw) == mamba == 38707200
    assert flops.attention_params(kw) == attn == 23396352
    expert = 2 * 2688 * 1856                                  # 9 977 856, no gate
    shared = 2 * 2688 * 3712
    # a token sends 6 * 8 / 128 = three eighths of a pair here under even routing
    assert flops.expected_pairs_per_token(kw) == 0.375
    routed = 2688 * 128 + shared + 0.375 * expert
    assert [flops.layer_matmul_params(kw, i, 0.375) for i in range(7)] == [
        mamba, routed, mamba, routed, mamba, attn, routed]
    head = 2688 * 16384
    weights = 3 * mamba + attn + 3 * routed + head
    # the parts: three Mamba-2 layers' two projections 116.1 M, attention's
    # 23.4, three shared experts 59.9, the routed experts 11.2, three routers
    # 1.0, the head 44.0
    assert [round(x / 1e6, 1) for x in (
        3 * mamba, attn, 3 * shared, 3 * 0.375 * expert, 3 * 2688 * 128, head,
        weights)] == [116.1, 23.4, 59.9, 11.2, 1.0, 44.0, 255.7]
    assert flops.layers_of(kw, "M") == 3 and flops.layers_of(kw, "E") == 3
    assert flops.layers_of(kw, "*") == 1
    scores = 2 * 2 * 32 * 128 * 4096.5                 # one layer, the keys seen
    ssd = 2 * (8 * 128 * 128 + 64 * 128 * 64 + 2 * 64 * 64 * 128)
    assert flops.ssd_products(**flops.ssd_shape(config, 1, 1)) == ssd == 3407872
    assert flops.forward_flops_per_token(config, 8192) == 2 * weights + scores + 3 * ssd
    total = flops.train_flops_per_token(config, 8192)
    assert total == 3 * (2 * weights + scores + 3 * ssd)
    assert [round(x / 1e9, 3) for x in (6 * weights, 3 * scores, 9 * ssd, total)] == [
        1.534, 0.201, 0.031, 1.766]
    assert round(total * 8192 / 1e12, 2) == 14.47              # a step
    share = lambda x: round(x / total, 2)
    # Mamba-2 41 % (its chunked part 1.7 % of the count), attention with its
    # projections 19 %, the feed-forward layers 24.5 %, the head 15 %
    assert [share(18 * mamba + 9 * ssd), round(9 * ssd / total, 3),
            share(6 * attn + 3 * scores), round(18 * routed / total, 3),
            share(6 * head)] == [0.41, 0.017, 0.19, 0.245, 0.15]
    # the program's own static counter says the same
    from split_learning_tpu.ops.ssd import ssd_product_flops
    assert ssd_product_flops(8192, 64, 64, 8, 128, 128) == 8192 * ssd


def test_the_whole_models_count_from_the_same_functions(config):
    kw = config["plan"]["kwargs"]
    published = config["published"]
    whole = flops.model_params(kw, range(published["num_hidden_layers"]),
                               published["n_routed_experts"], published["vocab_size"])
    assert round(whole / 1e9, 2) == 31.58
    # what a token meets beside the embedding: 6 experts a routed layer
    active = flops.model_params(kw, range(52), kw["experts_per_token"], 131072,
                                embedding=False)
    assert round(active / 1e9, 2) == 3.23
    cut = flops.model_params(kw, kw["layers_kept"], kw["experts_held"], kw["vocab"])
    assert round(cut / 1e6, 1) == 528.1
    # why not the nine-layer stretch (published 34-42), why not 16 experts
    assert [kw["pattern"][i] for i in range(34, 43)] == list("EMEMEMEM*")
    assert round(flops.model_params(kw, range(34, 43), 8, kw["vocab"]) / 1e6, 1) == 667.0
    assert round(flops.model_params(kw, kw["layers_kept"], 16, kw["vocab"]) / 1e6, 1) == 767.6


def test_kernel_costs_at_the_published_heads_and_width(config):
    shape = flops.attention_shape(config, 1, 8192)
    assert shape == dict(batch=1, heads=32, kv_heads=2, t=8192, head_dim=128)
    ops, moved = flops.attn_fwd(**shape, window=None)
    assert ops == 2 * 2 * 32 * 128 * 8192 * 4096.5
    assert moved == (2 * 32 + 2 * 2) * 8192 * 128 * 2
    ops_b, moved_b = flops.attn_bwd(**shape, window=None)
    assert ops_b == 2.5 * ops and moved_b == 2 * moved
    for cost in ((ops, moved), (ops_b, moved_b)):
        assert common.least_seconds(*cost, PEAK)[1] == "compute"
    # 2.79 ms forward and 6.98 backward a call at the peak: trinity-mini's
    # full layer's, whose 32 query heads of 128 these are
    assert common.least_seconds(ops, moved, PEAK)[0] == pytest.approx(2.791e-3, rel=1e-3)
    mm = flops.expert_mm_shape(config, 1, 8192)
    assert mm == dict(pairs=3072.0, experts=8, d_model=2688, width=1856)
    ops, moved = flops.expert_mm(**mm)
    assert ops == 2 * 3072 * 2688 * 1856
    assert moved == 3072 * (2688 + 1856) * 2 + 8 * 2688 * 1856 * 2
    assert common.least_seconds(ops, moved, PEAK)[1] == "compute"
    assert common.least_seconds(*flops.expert_mm(**mm, weight_itemsize=4), PEAK)[1] == "memory"


def test_the_file_holds_the_published_sizes(config):
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f if line.strip()]
    entry = next(r for r in rows if r["name"] == "Nemotron-Labs-TwoTower-30B-A3B-Base-BF16")
    assert config["source"] == entry["source_url"] and config["family"] == "nemotron_h"
    reduced = ["hybrid_override_pattern", "n_routed_experts", "num_hidden_layers",
               "vocab_size"]
    assert sorted(config["reduced"]) == reduced
    for key, value in entry["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    assert config["published"] == {
        "num_hidden_layers": 52, "hybrid_override_pattern": PATTERN,
        "n_routed_experts": 128, "vocab_size": 131072}
    assert (PATTERN.count("M"), PATTERN.count("E"), PATTERN.count("*")) == (23, 23, 6)
    kw = config["plan"]["kwargs"]
    for ours, theirs in (
            ("d_model", "hidden_size"), ("num_heads", "num_attention_heads"),
            ("num_kv_heads", "num_key_value_heads"), ("head_dim", "head_dim"),
            ("mamba_heads", "mamba_num_heads"), ("mamba_head_dim", "mamba_head_dim"),
            ("ssm_state", "ssm_state_size"), ("ssm_groups", "n_groups"),
            ("conv_taps", "conv_kernel"), ("chunk", "chunk_size"),
            ("time_step_min", "time_step_min"), ("time_step_max", "time_step_max"),
            ("expert_width", "moe_intermediate_size"),
            ("shared_width", "moe_shared_expert_intermediate_size"),
            ("experts_held", "n_routed_experts"),
            ("experts_per_token", "num_experts_per_tok"),
            ("route_scale", "routed_scaling_factor"), ("norm_eps", "norm_eps"),
            ("vocab", "vocab_size")):
        assert kw[ours] == config[theirs], ours
    assert kw["pattern"] == PATTERN
    assert kw["experts_total"] == config["published"]["n_routed_experts"]
    kept = kw["layers_kept"]
    assert len(kept) == config["num_hidden_layers"] == 7 and kept == list(range(7))
    assert "".join(PATTERN[i] for i in kept) == config["hybrid_override_pattern"] == "MEMEM*E"
    # one whole turn of the unit the pattern repeats over layers 6-33
    assert PATTERN[6:34] == "EMEMEM*" * 4
    assert sorted(config["hybrid_override_pattern"]) == sorted("EMEMEM*")
    assert config["data"]["vocab"] == kw["vocab"] and kw["vocab"] * 8 == 131072
    assert kw["experts_held"] * 16 == kw["experts_total"] and kw["expert_offset"] == 0
    assert kw["client_depth"] == 1 and config["n_shared_experts"] == 1
    assert config["mlp_hidden_act"] == "relu2" and config["use_conv_bias"] is True
    for key in ("deployment", "layers_kept", "departures"):
        assert config[key]
    assert any("denoising tower" in d for d in config["departures"])
    for key in ("d_inner", "mamba", "chunked_form", "attention", "routed", "norms",
                "tie_word_embeddings", "cut", "optimizer", "precision", "weights",
                "data", "fit", "remat"):
        assert config["assumed"][key], key
        assert "TO_BE_SETTLED" not in config["assumed"][key], key


def test_the_plan_takes_the_files_arguments_and_counts_the_cut(config):
    """The plan builds from ``plan.kwargs`` to the letter, and its stages
    hold what the file's ``cut`` says: 82.8 M on the client, 445.3 M on
    the server."""
    import jax
    import jax.numpy as jnp
    from split_learning_tpu.models.factory import get_plan
    spec = config["plan"]
    plan = get_plan(spec["model"], spec["mode"], jnp.dtype(spec["dtype"]), **spec["kwargs"])
    tokens = jax.ShapeDtypeStruct((1, 128), jnp.int32)
    client = jax.eval_shape(plan.stages[0].init, jax.random.PRNGKey(0), tokens)
    cut = jax.eval_shape(plan.stages[0].apply, client, tokens)
    server = jax.eval_shape(plan.stages[1].init, jax.random.PRNGKey(0), cut)
    count = lambda tree: sum(x.size for x in jax.tree_util.tree_leaves(tree))
    assert round(count(client) / 1e6, 1) == 82.8
    assert round(count(server) / 1e6, 1) == 445.3
    assert round(count(client["params"]["layer0"]) / 1e6, 2) == 38.74
    assert round(count(server["params"]["layer1"]) / 1e6, 2) == 100.13
    assert round(count(server["params"]["layer5"]) / 1e6, 2) == 23.40
    assert round(count(server["params"]["layer1"]["shared"]) / 1e6, 2) == 19.96
    assert server["params"]["layer1"]["experts"]["up"].shape == (8, 2688, 1856)
    assert "gate" not in server["params"]["layer1"]["experts"]
    assert client["params"]["layer0"]["mamba"]["in_proj"]["kernel"].shape == (2688, 10304)
    total = count(client) + count(server)
    assert round(total / 1e6, 1) == 528.1
    kw = spec["kwargs"]
    assert total == flops.model_params(kw, kw["layers_kept"], kw["experts_held"], kw["vocab"])
    from split_learning_tpu.models.afmoe import pair_rungs
    assert pair_rungs(8192 * 6, 8, 128) == (6144, 12288, 49152)


def test_the_reference_imports_nothing_of_the_program_and_scans_the_tokens():
    with open(os.path.join(BENCH, "reference", "nemotron_h.py")) as f:
        text = f.read()
    assert "split_learning_tpu" not in text.replace(
        "split_learning_tpu/ops/ssd.py", "")
    # the recurrence itself, one token a step: no chunk, no cumulative sum
    body = text.split('"""', 2)[2]
    assert "jax.lax.scan(token" in body and "cumsum" not in body and "chunk" not in body


def fake_run(config, ops, spans=None):
    run = {"trace": {"op_seconds": {n: s for n, (_, s) in ops.items()},
                     "op_counts": {n: c for n, (c, _) in ops.items()}},
           "job": {"rows_per_client": 1, "tokens_per_row": 8192, "clients": 1},
           "config": config, "flops": importlib.import_module("flops.nemotron_h"),
           "peak": PEAK}
    if spans is not None:
        run["spans"] = spans
    return run


def counters(pairs_by_layer, steps):
    """``counters_read`` records as the fused step writes them."""
    layers = [f"trunk_head/layer{i}/experts" for i in (1, 3, 6)][:len(pairs_by_layer)]
    return [{"name": "counters_read", "party": "client", "span_id": k, "parent_id": 0,
             "duration": 1e-3, "start_ns": k,
             "attrs": {"layers": layers, "pairs": pairs_by_layer,
                       "rows": [6144] * len(layers),
                       "ladder": [[6144, 12288, 49152]] * len(layers)}} for k in range(steps)]


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
        # four steps, at half of their roofline; other calls beside them
        "%attn_full.1 custom-call bf16[32,8192,128] tpu_custom_call/3": (4, 8 * fwd),
        "%attn_full.2 custom-call f32[32,8192,128] tpu_custom_call/6": (4, 8 * bwd),
        "%attn_window.1 custom-call bf16[32,8192,128] tpu_custom_call/3": (4, 1.0),
        "%gmm.3 custom-call bf16[6144,1856] tpu_custom_call/4": (24, 0.01),
        "%fusion.9 fusion bf16[8192,2688]": (100, 0.092),
    }
    read = reader(READERS[0])
    assert read(fake_run(config, ops)) == pytest.approx(50.0)
    only = dict(list(ops.items())[:1])
    assert read(fake_run(config, only)) == pytest.approx(50.0)
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
    # three layers, five steps: 6 gmm and 2 tgmm calls a layer and step
    held = [[700] * 8, [768] * 8, [836] * 8]                  # mean 6144
    spans = counters(held, 5)
    ops = {"%gmm.1 custom-call bf16[6144,1856] tpu_custom_call/4":
           (90, 2 * 90 * least(6144.0)),
           "%tgmm.1 custom-call f32[8,2688,1856] tpu_custom_call/4":
           (30, 2 * 30 * least(6144.0, weight_itemsize=4)),
           "%attn_full.1 custom-call bf16[32,8192,128] tpu_custom_call/3": (5, 1.0)}
    read = reader(READERS[1])
    got = read(fake_run(config, ops, spans=spans))
    assert got == pytest.approx(50.0)
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
    assert cell["config"] == NAME and cell["chips"] == 1
    assert cell["traffic"] == CELL and len(cell["why"]) <= 200
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert entry["file"] == f"benchmarks/configs/{NAME}.json"
    assert sorted(entry["reduced"]) == ["hybrid_override_pattern", "n_routed_experts",
                                        "num_hidden_layers", "vocab_size"]
    assert len(entry["why"]) <= 200
    # looked up by name: a later PR appends its own entries after these
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert not [name for name in by_name if name.startswith("nemotronh_")]
    for name in READERS + KERNELS:
        assert {**by_name[name], "workloads": None} == dict(
            name=name, unit="%", better="higher", source="device_trace",
            layer="kernels", moves="mfu_pct", workloads=None)
        assert CELL in by_name[name]["workloads"]
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", name + ".py"))
    assert by_name["ssd_roofline_pct"]["workloads"] == [CELL]
    assert [m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])] == [
        "attn_full_roofline_pct", "moe_expert_mm_roofline_pct",
        "moe_dispatch_ops_share_pct", "moe_rung_fill_pct", "moe_top_rung_share_pct",
        "moe_pairs_x_even_p50"] + KERNELS
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
