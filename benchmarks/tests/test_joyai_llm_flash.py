"""The joyai-llm-flash configuration: FLOPs against a hand count, the
kernels' costs at the true widths, the reader on a made-up trace, the file
against the catalog's published sizes and the plan's arguments, the
parameter count of the cut, and the CPU rehearsal of the cell."""

import importlib
import json
import os
import subprocess
import sys

import pytest

from flops import common, joyai_llm_flash as flops

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "joyai-flash-fused-t8192"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", "joyai-llm-flash.json")) as f:
        return json.load(f)


def test_per_token_flops_against_a_hand_count(config):
    kw = config["plan"]["kwargs"]
    mla = (2048 * 1536 + 1536 * 32 * 192 + 2048 * 576 + 512 * 32 * 256
           + 4096 * 2048)
    assert flops.attention_params(kw) == mla == 26345472
    expert = 3 * 2048 * 768                                  # 4 718 592
    dense = mla + 3 * 2048 * 7168
    # a token sends 8 * 8 / 256 = a quarter of a pair here under even routing
    assert flops.expected_pairs_per_token(kw) == 0.25
    routed = mla + expert + 2048 * 256 + 0.25 * expert
    assert flops.layer_matmul_params(kw, True) == dense == 70385664
    assert flops.layer_matmul_params(kw, False) == routed == 32768000
    head = 2048 * 16160
    module = 2 * 2048 * 2048 + routed + head
    weights = dense + 4 * routed + head + module
    assert round(weights / 1e6, 1) == 308.8
    # 32 heads: QK^T at 192 and PV at 128, six blocks, the keys a query sees
    assert flops.attention_flops_per_key(kw) == 2 * 32 * (192 + 128) == 20480
    scores = 6 * 20480 * 4096.5
    assert flops.forward_flops_per_token(config, 8192) == 2 * weights + scores
    total = flops.train_flops_per_token(config, 8192)
    assert total == 3 * (2 * weights + scores)
    assert round(total / 1e9, 3) == 3.363
    assert round(3 * scores / total, 3) == 0.449             # attention alone
    # without the module: five blocks and the head once
    plain = {"plan": {"kwargs": {**kw, "mtp_layers": 0}}}
    assert flops.forward_flops_per_token(plain, 8192) == (
        2 * (dense + 4 * routed + head) + 5 * 20480 * 4096.5)


def test_kernel_costs_at_the_true_widths(config):
    shape = flops.attention_shape(config, 1, 8192)
    assert shape == dict(batch=1, heads=32, t=8192, qk_dim=192, v_dim=128)
    ops, moved = flops.attn_fwd(**shape)
    assert ops == 32 * 2 * (192 + 128) * 8192 * 4096.5
    assert moved == 32 * (2 * 192 + 2 * 128) * 8192 * 2
    ops_b, moved_b = flops.attn_bwd(**shape)
    # S, dK, dQ at 192 and dP, dV at 128: 832 / 320 of the forward
    assert ops_b == pytest.approx(ops * (3 * 192 + 2 * 128) / 320)
    assert moved_b == 2 * moved
    for cost in ((ops, moved), (ops_b, moved_b)):
        assert common.least_seconds(*cost, PEAK)[1] == "compute"
    # 3.49 ms forward and 9.07 ms backward a call at the peak
    assert common.least_seconds(ops, moved, PEAK)[0] == pytest.approx(3.488e-3, rel=1e-3)


def test_the_file_holds_the_published_sizes(config):
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f if line.strip()]
    entry = next(r for r in rows if r["name"] == "JoyAI-LLM-Flash")
    assert config["source"] == entry["source_url"]
    assert sorted(config["reduced"]) == ["n_routed_experts", "num_hidden_layers",
                                         "vocab_size"]
    for key, value in entry["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    assert config["published"] == {"num_hidden_layers": 40, "n_routed_experts": 256,
                                   "vocab_size": 129280}
    kw = config["plan"]["kwargs"]
    for ours, theirs in (
            ("d_model", "hidden_size"), ("num_heads", "num_attention_heads"),
            ("q_lora_rank", "q_lora_rank"), ("kv_lora_rank", "kv_lora_rank"),
            ("qk_nope_head_dim", "qk_nope_head_dim"),
            ("qk_rope_head_dim", "qk_rope_head_dim"), ("v_head_dim", "v_head_dim"),
            ("dense_width", "intermediate_size"),
            ("expert_width", "moe_intermediate_size"),
            ("experts_held", "n_routed_experts"),
            ("experts_per_token", "num_experts_per_tok"),
            ("shared_experts", "n_shared_experts"),
            ("route_scale", "routed_scaling_factor"), ("layers", "num_hidden_layers"),
            ("dense_layers", "first_k_dense_replace"), ("rope_theta", "rope_theta"),
            ("rms_norm_eps", "rms_norm_eps"), ("mtp_layers", "num_nextn_predict_layers"),
            ("vocab", "vocab_size")):
        assert kw[ours] == config[theirs], ours
    assert kw["experts_total"] == config["published"]["n_routed_experts"]
    assert kw["qk_nope_head_dim"] + kw["qk_rope_head_dim"] == config["qk_head_dim"] == 192
    assert config["head_dim"] == kw["qk_rope_head_dim"] == 64     # the rotary width
    assert config["data"]["vocab"] == kw["vocab"] and kw["vocab"] * 8 == 129280
    assert kw["experts_held"] * 32 == kw["experts_total"] and kw["expert_offset"] == 0
    assert kw["layers"] - kw["dense_layers"] == 4 and kw["client_depth"] == 1
    for key in ("deployment", "layers_kept", "departures"):
        assert config[key]
    for key in ("mtp", "mtp_lambda", "mtp_input", "head_dim", "rope", "norms", "router",
                "cut", "optimizer", "precision", "weights", "data", "fit", "remat"):
        assert config["assumed"][key], key


def test_the_plan_takes_the_files_arguments_and_counts_the_cut(config):
    """The plan builds from ``plan.kwargs`` to the letter, and its stages
    hold what the file's ``cut`` says: 103.5 M on the client, 421.3 M on
    the server, the module's 110.8 M among them."""
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
    assert round(count(client) / 1e6, 1) == 103.5
    assert round(count(server) / 1e6, 1) == 421.3
    assert round(count(server["params"]["mtp"]) / 1e6, 1) == 110.8
    assert round(count(server["params"]["layer1"]) / 1e6, 2) == 69.34
    assert round((count(client) + count(server)) / 1e6, 1) == 524.8
    assert plan.stages[1].objective is not None and plan.stages[0].objective is None
    from split_learning_tpu.models.afmoe import pair_rungs
    assert pair_rungs(65536, 8, 256) == (4096, 8192, 65536)


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "reference", "joyai_llm_flash.py")) as f:
        text = f.read()
    assert "split_learning_tpu" not in text


def fake_run(config, ops, module="flops.joyai_llm_flash"):
    return {"trace": {"op_seconds": {n: s for n, (_, s) in ops.items()},
                      "op_counts": {n: c for n, (c, _) in ops.items()}},
            "job": {"rows_per_client": 1, "tokens_per_row": 8192}, "config": config,
            "flops": importlib.import_module(module), "peak": PEAK}


def reader(name):
    sys.path.insert(0, BENCH)
    import run
    return run.layer_reader(name)


def test_the_reader_on_a_made_up_trace(config):
    shape = flops.attention_shape(config, 1, 8192)
    least = lambda cost: common.least_seconds(*cost, PEAK)[0]
    fwd, bwd = least(flops.attn_fwd(**shape)), least(flops.attn_bwd(**shape))
    ops = {
        # (calls, seconds): six forward and six backward calls a step over
        # four steps, at half their roofline; another scope's call beside them
        "%attn_latent.1 custom-call bf16[32,8192,128] tpu_custom_call/3": (24, 48 * fwd),
        "%attn_latent.2 custom-call f32[32,8192,256] tpu_custom_call/6": (24, 48 * bwd),
        "%attn_full.1 custom-call bf16[32,8192,128] tpu_custom_call/3": (4, 1.0),
        "%gmm.3 custom-call bf16[4096,768] tpu_custom_call/4": (36, 0.01),
        "%fusion.9 fusion bf16[8192,2048]": (100, 0.092),
    }
    run = fake_run(config, ops)
    read = reader("mla_attn_roofline_pct")
    assert read(run) == pytest.approx(50.0)
    # forward calls alone read the forward's share
    only = dict(list(ops.items())[:1])
    assert read(fake_run(config, only)) == pytest.approx(50.0)
    # a rehearsal without a trace, a program without the scope (the parent
    # commit under these files), another family's cell: nothing to read
    assert read({**run, "trace": None}) is None
    assert read(fake_run(config, {"%fusion.1 fusion f32[8]": (1, 1.0)})) is None
    assert read(fake_run(config, dict(list(ops.items())[2:]), "flops.afmoe")) is None


def test_the_expert_reader_costs_the_calls_at_this_width(config):
    """``moe_expert_mm_roofline_pct`` takes the cell's own ``expert_mm_shape``
    (2048 x 768, 2048 pairs under even routing) and the pairs the step's
    ``counters_read`` records hold: five routed layers, the module's block
    among them."""
    assert flops.expert_mm_shape(config, 1, 8192) == dict(
        pairs=2048.0, experts=8, d_model=2048, width=768)
    least = lambda pairs, **kw: common.least_seconds(*flops.expert_mm(
        pairs=pairs, experts=8, d_model=2048, width=768, **kw), PEAK)[0]
    layers = [f"trunk_head/layer{i}/experts" for i in range(1, 5)] + ["trunk_head/mtp/block/experts"]
    spans = [{"name": "counters_read", "party": "client", "span_id": k, "parent_id": 0,
              "duration": 1e-3, "start_ns": k,
              "attrs": {"layers": layers, "pairs": [[384] * 8] * 5, "rows": [4096] * 5,
                        "ladder": [[4096, 8192, 65536]] * 5}} for k in range(4)]
    ops = {"%gmm.3 custom-call bf16[4096,768] tpu_custom_call/4": (180, 4 * 180 * least(3072.0)),
           "%tgmm.1 custom-call f32[8,2048,768] tpu_custom_call/4":
           (60, 4 * 60 * least(3072.0, weight_itemsize=4)),
           "%attn_latent.1 custom-call bf16[32,8192,128] tpu_custom_call/3": (24, 1.0)}
    run = {**fake_run(config, ops), "spans": spans}
    run["job"] = {**run["job"], "clients": 1}
    read = reader("moe_expert_mm_roofline_pct")
    assert read(run) == pytest.approx(25.0)
    assert read({**run, "spans": []}) is None
    assert read({**run, "trace": None}) is None


def test_the_new_entries_of_the_benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["config"] == "joyai-llm-flash" and cell["chips"] == 1
    assert cell["traffic"] == CELL and len(cell["why"]) <= 200
    entry = next(c for c in bench["configs"] if c["name"] == "joyai-llm-flash")
    assert entry["file"] == "benchmarks/configs/joyai-llm-flash.json"
    assert sorted(entry["reduced"]) == ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert len(entry["why"]) <= 200
    metric = next(m for m in bench["per_layer"] if m["name"] == "mla_attn_roofline_pct")
    assert metric == dict(name="mla_attn_roofline_pct", unit="%", better="higher",
                          source="device_trace", layer="kernels", moves="mfu_pct",
                          workloads=[CELL])
    assert os.path.exists(os.path.join(BENCH, "layer_metrics", "mla_attn_roofline_pct.py"))
    with open(os.path.join(BENCH, "traffic", CELL + ".json")) as f:
        job = json.load(f)
    assert (job["path"], job["clients"], job["rows_per_client"], job["tokens_per_row"],
            job["pool"], job["check_steps"], job["reference_row_block"]) == (
                "fused", 1, 1, 8192, 8, 3, 1)
    assert set(job["limits"]) == {"loss_gap", "grad_norm_gap", "delta_norm_gap"}


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
