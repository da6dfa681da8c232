"""The ouro-2.6b configuration: FLOPs against a hand count (the loop
multiplies the work and not the weights), the whole model's count from the
same functions, the kernels' costs at sixteen ungrouped heads of 128, both
readers on a made-up trace, the file against the catalog's published sizes
and the plan's arguments, the parameter counts of the cut, and the CPU
rehearsal of the cell."""

import importlib
import json
import os
import subprocess
import sys

import pytest

from flops import common, ouro as flops

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "ouro-loop-fused-t8192"
NAME = "ouro-2.6b"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
READERS = ["attn_full_roofline_pct", "ouro_attn_fwd_calls_per_step"]


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        return json.load(f)


def test_per_token_flops_against_a_hand_count(config):
    kw = config["plan"]["kwargs"]
    attn = 4 * 2048 * 2048
    mlp = 3 * 2048 * 5632
    assert flops.attention_params(kw) == attn == 16777216
    assert flops.layer_matmul_params(kw) == attn + mlp == 51380224
    assert flops.layer_applications(config) == 6 * 4 == 24
    head = 2048 * 6144
    one_pass = 6 * (attn + mlp) + head
    # four passes of 308.3 M and four uses of the head: 1283.5 M a token
    assert [round(x / 1e6, 1) for x in (6 * (attn + mlp), head, 4 * one_pass)] == [
        308.3, 12.6, 1283.5]
    scores = 2 * 2 * 16 * 128 * 4096.5                  # one application
    assert flops.forward_flops_per_token(config, 8192) == 4 * 2 * one_pass + 24 * scores
    total = flops.train_flops_per_token(config, 8192)
    assert total == 3 * (4 * 2 * one_pass + 24 * scores)
    assert [round(x / 1e9, 3) for x in (6 * 4 * one_pass, 3 * 24 * scores, total)] == [
        7.701, 2.416, 10.117]
    assert round(total * 8192 / 1e12, 1) == 82.9               # a step
    share = lambda x: round(x / total, 2)
    # the MLPs 49 %, attention 24 %, its projections 24 %, the head 3 %
    assert [share(6 * 24 * mlp), share(3 * 24 * scores), share(6 * 24 * attn),
            share(6 * 4 * head)] == [0.49, 0.24, 0.24, 0.03]
    # the model's own head share, whole: 48 layers, the whole vocabulary
    whole_head = 4 * 2048 * 49152
    whole = 4 * 48 * (attn + mlp) + whole_head
    assert round(whole_head / whole, 3) == round(4 * head / (4 * one_pass), 3) == 0.039
    # an eighth of the depth with the whole vocabulary would be a quarter head
    assert round(whole_head / (4 * 6 * (attn + mlp) + whole_head), 2) == 0.25


def test_the_whole_models_count_from_the_same_functions(config):
    kw = config["plan"]["kwargs"]
    published = config["published"]
    whole = flops.model_params(kw, published["num_hidden_layers"], published["vocab_size"])
    assert round(whole / 1e9, 3) == 2.668
    assert round((flops.layer_matmul_params(kw) + 4 * 2048) / 1e6, 2) == 51.39
    assert round(2048 * 49152 / 1e6, 2) == 100.66
    cut = flops.model_params(kw, kw["layers"], kw["vocab"])
    assert round(cut / 1e6, 1) == 333.5
    # resident at 12 B a parameter, with float32 gradients, in the reference's loop
    assert [round(cut * b / 1e9, 2) for b in (12, 16, 20)] == [4.0, 5.34, 6.67]
    # why not nine layers (36 applications), why not four (no remat needed)
    assert round(flops.model_params(kw, 9, kw["vocab"]) / 1e6, 1) == 487.7
    assert round(flops.model_params(kw, 4, kw["vocab"]) / 1e6, 1) == 230.7


def test_kernel_costs_at_sixteen_ungrouped_heads(config):
    shape = flops.attention_shape(config, 1, 8192)
    assert shape == dict(batch=1, heads=16, kv_heads=16, t=8192, head_dim=128)
    ops, moved = flops.attn_fwd(**shape, window=None)
    assert ops == 2 * 2 * 16 * 128 * 8192 * 4096.5
    assert moved == (2 * 16 + 2 * 16) * 8192 * 128 * 2
    ops_b, moved_b = flops.attn_bwd(**shape, window=None)
    assert ops_b == 2.5 * ops and moved_b == 2 * moved
    for cost in ((ops, moved), (ops_b, moved_b)):
        assert common.least_seconds(*cost, PEAK)[1] == "compute"
    # 1.40 ms forward and 3.49 backward a call at the peak: half of
    # trinity-mini's full layer's, whose 32 query heads these are 16 of
    assert common.least_seconds(ops, moved, PEAK)[0] == pytest.approx(1.3955e-3, rel=1e-3)
    # 24 of each a step: 117 ms of a step at the peak
    assert 24 * (common.least_seconds(ops, moved, PEAK)[0]
                 + common.least_seconds(ops_b, moved_b, PEAK)[0]) == pytest.approx(
                     0.1172, rel=1e-2)


def test_the_file_holds_the_published_sizes(config):
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f if line.strip()]
    entry = next(r for r in rows if r["name"] == "Ouro-2.6B")
    assert config["source"] == entry["source_url"] and config["family"] == "ouro"
    reduced = ["layer_types", "max_window_layers", "num_hidden_layers", "vocab_size"]
    assert sorted(config["reduced"]) == reduced
    for key, value in entry["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    assert config["published"] == {
        "num_hidden_layers": 48, "layer_types": ["full_attention"] * 48,
        "max_window_layers": 48, "vocab_size": 49152}
    assert config["layer_types"] == ["full_attention"] * 6
    assert config["total_ut_steps"] == 4 and config["early_exit_threshold"] == 1
    kw = config["plan"]["kwargs"]
    for ours, theirs in (
            ("d_model", "hidden_size"), ("num_heads", "num_attention_heads"),
            ("num_kv_heads", "num_key_value_heads"), ("head_dim", "head_dim"),
            ("width", "intermediate_size"), ("layers", "num_hidden_layers"),
            ("passes", "total_ut_steps"), ("rope_theta", "rope_theta"),
            ("rms_norm_eps", "rms_norm_eps"), ("vocab", "vocab_size")):
        assert kw[ours] == config[theirs], ours
    assert config["num_hidden_layers"] == config["max_window_layers"] == 6
    assert config["data"]["vocab"] == kw["vocab"] and kw["vocab"] * 8 == 49152
    assert kw["layers"] * 8 == 48 and kw["client_depth"] == 0 and kw["beta"] == 0.1
    assert kw["remat"] is True and 0 <= kw["remat_mlp_passes"] <= 4
    for key in ("deployment", "layers_kept", "departures"):
        assert config[key]
    assert any("adaptive exit" in d for d in config["departures"])
    assert any("second training stage" in d for d in config["departures"])
    for key in ("attention_bias", "norms", "loop", "gate", "objective", "beta",
                "cut", "optimizer", "precision", "weights", "data", "remat", "fit"):
        assert config["assumed"][key], key
        assert "TODO" not in config["assumed"][key], key
    assert "TODO" not in json.dumps(config)


def test_the_plan_takes_the_files_arguments_and_counts_the_cut(config):
    """The plan builds from ``plan.kwargs`` to the letter, and its stages
    hold what the file's ``cut`` says: the embedding on the client, six
    layers, one final norm, one gate and one head on the server."""
    import jax
    import jax.numpy as jnp
    from split_learning_tpu.models.factory import get_plan
    spec = config["plan"]
    plan = get_plan(spec["model"], spec["mode"], jnp.dtype(spec["dtype"]), **spec["kwargs"])
    tokens = jax.ShapeDtypeStruct((1, 128), jnp.int32)
    client = jax.eval_shape(plan.stages[0].init, jax.random.PRNGKey(0), tokens)
    cut = jax.eval_shape(plan.stages[0].apply, client, tokens)
    assert cut.shape == (1, 128, 2048)
    server = jax.eval_shape(plan.stages[1].init, jax.random.PRNGKey(0), cut)
    count = lambda tree: sum(x.size for x in jax.tree_util.tree_leaves(tree))
    assert sorted(server["params"]) == [
        "early_exit_gate", "layer0", "layer1", "layer2", "layer3", "layer4",
        "layer5", "lm_head", "norm_f"]
    assert sorted(client["params"]) == ["tok"]
    assert round(count(client) / 1e6, 2) == 12.58
    assert round(count(server) / 1e6, 1) == 320.9
    assert round(count(server["params"]["layer0"]) / 1e6, 2) == 51.39
    assert round(count(server["params"]["layer0"]["self_attn"]) / 1e6, 2) == 16.78
    assert round(count(server["params"]["layer0"]["mlp"]) / 1e6, 2) == 34.60
    assert count(server["params"]["early_exit_gate"]) == 2049
    assert server["params"]["lm_head"].shape == (2048, 6144)
    total = count(client) + count(server)
    kw = spec["kwargs"]
    assert total == flops.model_params(kw, kw["layers"], kw["vocab"])
    assert plan.stages[1].objective is not None


def test_the_reference_imports_nothing_of_the_program_and_runs_every_pass():
    with open(os.path.join(BENCH, "reference", "ouro.py")) as f:
        text = f.read()
    assert "split_learning_tpu" not in text
    body = text.split('"""', 2)[2]
    assert 'length=kw["passes"]' in body and "log_exit_distribution" in body
    assert 'kw["beta"] * entropy' in body


def fake_run(config, ops, spans=None):
    run = {"trace": {"op_seconds": {n: s for n, (_, s) in ops.items()},
                     "op_counts": {n: c for n, (c, _) in ops.items()}},
           "job": {"rows_per_client": 1, "tokens_per_row": 8192, "clients": 1},
           "config": config, "flops": importlib.import_module("flops.ouro"),
           "peak": PEAK}
    if spans is not None:
        run["spans"] = spans
    return run


def step_spans(steps):
    """``step_total`` records as the fused step writes them."""
    return [{"name": "step_total", "party": "client", "span_id": k + 1,
             "parent_id": 0, "duration": 0.8, "start_ns": k, "attrs": {}}
            for k in range(steps)]


def reader(name):
    sys.path.insert(0, BENCH)
    import run
    return run.layer_reader(name)


def test_the_attention_reader_on_a_made_up_trace(config):
    shape = flops.attention_shape(config, 1, 8192)
    least = lambda cost: common.least_seconds(*cost, PEAK)[0]
    fwd, bwd = (least(f(**shape, window=None)) for f in (flops.attn_fwd, flops.attn_bwd))
    ops = {
        # (calls, seconds): 24 forward and 24 backward calls a step over
        # four steps, at half of their roofline; other calls beside them
        "%attn_full.1 custom-call bf16[16,8192,128] tpu_custom_call/3": (96, 192 * fwd),
        "%attn_full.2 custom-call f32[16,8192,128] tpu_custom_call/6": (96, 192 * bwd),
        "%attn_window.1 custom-call bf16[16,8192,128] tpu_custom_call/3": (4, 1.0),
        "%fusion.9 fusion bf16[8192,2048]": (100, 0.092),
    }
    read = reader(READERS[0])
    assert read(fake_run(config, ops)) == pytest.approx(50.0)
    only = dict(list(ops.items())[:1])
    assert read(fake_run(config, only)) == pytest.approx(50.0)
    # a rehearsal without a trace, a program without the scope: nothing to read
    assert read({**fake_run(config, ops), "trace": None}) is None
    assert read(fake_run(config, {"%fusion.1 fusion f32[8]": (1, 1.0)})) is None


def test_the_call_counter_reads_what_the_loop_multiplies(config):
    ops = {"%attn_full.1 custom-call bf16[16,8192,128] tpu_custom_call/3": (60, 0.1),
           "%attn_full.7 custom-call bf16[16,8192,128] tpu_custom_call/3": (60, 0.1),
           "%attn_full.2 custom-call f32[16,8192,128] tpu_custom_call/6": (120, 0.3),
           "%fusion.9 fusion bf16[8192,2048]": (100, 0.092)}
    read = reader(READERS[1])
    assert read(fake_run(config, ops, spans=step_spans(5))) == 24.0
    assert flops.layer_applications(config) == 24
    # a whole-layer remat of every pass would read twice that
    twice = {k: (2 * c, s) for k, (c, s) in ops.items()}
    assert read(fake_run(config, twice, spans=step_spans(5))) == 48.0
    # no trace, no step span, no such call, a family that is no loop: nothing
    assert read({**fake_run(config, ops, spans=step_spans(5)), "trace": None}) is None
    assert read(fake_run(config, ops, spans=[])) is None
    assert read(fake_run(config, dict(list(ops.items())[2:]), spans=step_spans(5))) is None
    other = {**fake_run(config, ops, spans=step_spans(5)),
             "flops": importlib.import_module("flops.lfm2_moe")}
    assert read(other) is None


def test_the_new_entries_of_the_benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["config"] == NAME and cell["chips"] == 1
    assert cell["traffic"] == CELL and len(cell["why"]) <= 200
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert entry["file"] == f"benchmarks/configs/{NAME}.json"
    assert sorted(entry["reduced"]) == ["layer_types", "max_window_layers",
                                        "num_hidden_layers", "vocab_size"]
    assert len(entry["why"]) <= 200
    # looked up by name: a later PR appends its own entries after these
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert "ouro_attn_roofline_pct" not in by_name           # folded into the shared reader
    assert {**by_name[READERS[0]], "workloads": None} == dict(
        name=READERS[0], unit="%", better="higher", source="device_trace",
        layer="kernels", moves="mfu_pct", workloads=None)
    assert CELL in by_name[READERS[0]]["workloads"]
    assert by_name[READERS[1]] == dict(
        name=READERS[1], unit="count", better="lower", source="device_trace",
        layer="device programs", moves="tokens_per_s", workloads=[CELL])
    for name in READERS:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", name + ".py"))
    assert [m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])] == READERS
    with open(os.path.join(BENCH, "traffic", CELL + ".json")) as f:
        job = json.load(f)
    assert (job["path"], job["clients"], job["rows_per_client"], job["tokens_per_row"],
            job["pool"], job["check_steps"], job["reference_row_block"]) == (
                "fused", 1, 1, 8192, 8, 3, 1)
    assert set(job["limits"]) == {"loss_gap", "grad_norm_gap", "delta_norm_gap"}
    assert job["limits_note"] and job["rehearsal"]["limits_note"] and job["fit"]
    assert "TODO" not in json.dumps(job)


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
