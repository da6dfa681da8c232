"""The phi4flash configuration: FLOPs against a hand count, keys seen under
the window, the kernels' costs, the readers on a made-up trace, the file
against the published sizes, and the CPU rehearsal of the cell."""

import importlib
import json
import os
import subprocess
import sys

import pytest

from flops import common, phi4flash

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "phi4flash-fused-t8192"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", "phi-4-mini-flash-reasoning.json")) as f:
        return json.load(f)


def test_keys_seen_under_the_window():
    assert phi4flash.keys_seen(8, 4) == 26 / 8
    assert phi4flash.keys_seen(8192, None) == 4096.5
    # 512 - 512 * 511 / 16384
    assert phi4flash.keys_seen(8192, 512) == 496.03125
    assert phi4flash.keys_seen(8192, 512) == sum(min(i + 1, 512) for i in range(8192)) / 8192


def test_per_token_flops_against_a_hand_count(config):
    kw = {"expand": 2, **config["plan"]["kwargs"]}
    mlp = 2560 * 20480 + 10240 * 2560                        # 78 643 200
    attn = 2560 * 5120 + 2560 * 2560                         # W_qkv, W_o
    mamba = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    gmu = 2 * 2560 * 5120
    cross = 2 * 2560 * 2560
    assert phi4flash.mixer_matmul_params(kw, "window") == attn == 19660800
    assert phi4flash.mixer_matmul_params(kw, "full") == attn
    assert phi4flash.mixer_matmul_params(kw, "mamba") == mamba == 41123840
    assert phi4flash.mixer_matmul_params(kw, "gmu") == gmu == 26214400
    assert phi4flash.mixer_matmul_params(kw, "cross") == cross == 13107200
    # 40 (query head, key) products: QK^T at 64, PV at 128
    assert phi4flash.attention_flops_per_key(kw) == 40 * 2 * (64 + 128) == 15360
    products = 2 * (5 * mlp + 2 * attn + mamba + gmu + cross + 2560 * 25008)
    assert products == 2 * 577003520                        # 577 M weights a token
    scores = 15360 * (496.03125 + 2 * 4096.5)
    assert phi4flash.forward_flops_per_token(config, 8192) == products + scores
    assert phi4flash.train_flops_per_token(config, 8192) == 3 * (products + scores)
    assert round(phi4flash.train_flops_per_token(config, 8192) / 1e9, 4) == 3.8624


def test_kernel_costs(config):
    shape = phi4flash.attention_shape(config, 1, 8192)
    assert shape == dict(batch=1, heads=40, kv_heads=20, t=8192, head_dim=64)
    ops, moved = phi4flash.attn_fwd(**shape, window=None)
    assert ops == 40 * 2 * 192 * 8192 * 4096.5
    # q 40 x 64, o 40 x 128, k 20 x 64, the paired values 10 x 128
    assert moved == (40 * 64 + 40 * 128 + 20 * 64 + 10 * 128) * 8192 * 2
    ops_w, _ = phi4flash.attn_fwd(**shape, window=512)
    assert ops_w == 40 * 2 * 192 * 8192 * 496.03125
    ops_b, moved_b = phi4flash.attn_bwd(**shape, window=512)
    # S, dK, dQ at 64 and dP, dV at 128: 7 / 3 of the forward
    assert ops_b == pytest.approx(ops_w * 7 / 3)
    assert moved_b == (40 * (64 + 128 + 128 + 64) + 2 * (20 * 64 + 10 * 128)) * 8192 * 2
    scan = phi4flash.scan_shape(config, 1, 8192)
    assert scan == dict(batch=1, t=8192, d_inner=5120, d_state=16)
    ops_s, moved_s = phi4flash.scan_fwd(**scan)
    assert ops_s == 7 * 8192 * 5120 * 16
    assert moved_s == 4 * (8192 * (3 * 5120 + 32) + 5120 * 16)
    # bound by bytes against the published peaks, forward and backward
    assert common.least_seconds(ops_s, moved_s, PEAK)[1] == "memory"
    assert common.least_seconds(*phi4flash.scan_bwd(**scan), PEAK)[1] == "memory"
    assert phi4flash.scan_bwd(**scan)[1] == 4 * (8192 * (5 * 5120 + 64) + 2 * 5120 * 16)


def test_the_file_holds_the_published_sizes(config):
    published = dict(hidden_size=2560, intermediate_size=10240, num_attention_heads=40,
                     num_key_value_heads=20, sliding_window=512, mb_per_layer=2,
                     layer_norm_eps=1e-5, max_position_embeddings=262144,
                     embd_pdrop=0, resid_pdrop=0, tie_word_embeddings=True,
                     mlp_bias=False, lm_head_bias=False, hidden_act="silu",
                     model_type="phi4flash")
    for key, value in published.items():
        assert config[key] == value, key
    assert sorted(config["reduced"]) == ["num_hidden_layers", "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 32, "vocab_size": 200064}
    kw = config["plan"]["kwargs"]
    for ours, theirs in (("d_model", "hidden_size"), ("num_heads", "num_attention_heads"),
                         ("num_kv_heads", "num_key_value_heads"),
                         ("mlp_width", "intermediate_size"), ("window", "sliding_window"),
                         ("mb_per_layer", "mb_per_layer"), ("eps", "layer_norm_eps"),
                         ("vocab", "vocab_size")):
        assert kw[ours] == config[theirs], ours
    assert kw["head_dim"] * kw["num_heads"] == 2560 and kw["head_dim"] == 64
    assert kw["expand"] * kw["d_model"] == 5120 and kw["d_state"] == 16
    assert kw["dt_rank"] == -(-2560 // 16) == 160 and kw["d_conv"] == 4
    assert kw["layers_published"] == config["published"]["num_hidden_layers"]
    assert len(kw["layers_kept"]) == config["num_hidden_layers"] == 5
    kinds = [phi4flash.layer_kind(i, 32, 2) for i in kw["layers_kept"]]
    assert kinds == ["window", "mamba", "full", "gmu", "cross"]
    assert config["data"]["vocab"] == kw["vocab"] and kw["vocab"] * 8 == 200064
    for key in ("deployment", "layers_kept", "departures"):
        assert config[key]
    for key in ("mamba", "memory", "shared_kv", "differential_attention", "biases",
                "cut", "optimizer", "precision", "weights", "data", "fit", "remat"):
        assert config["assumed"][key], key


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "reference", "phi4flash.py")) as f:
        text = f.read()
    assert "split_learning_tpu" not in text


def fake_run(config, ops, flops="flops.phi4flash"):
    return {"trace": {"op_seconds": {n: s for n, (_, s) in ops.items()},
                      "op_counts": {n: c for n, (c, _) in ops.items()}},
            "job": {"rows_per_client": 1, "tokens_per_row": 8192}, "config": config,
            "flops": importlib.import_module(flops), "peak": PEAK}


def reader(name):
    sys.path.insert(0, BENCH)
    import run
    return run.layer_reader(name)


def test_the_readers_on_a_made_up_trace(config):
    shape = phi4flash.attention_shape(config, 1, 8192)
    least = lambda cost: common.least_seconds(*cost, PEAK)[0]
    w_fwd, w_bwd = (least(f(**shape, window=512)) for f in (phi4flash.attn_fwd, phi4flash.attn_bwd))
    f_fwd, f_bwd = (least(f(**shape, window=None)) for f in (phi4flash.attn_fwd, phi4flash.attn_bwd))
    scan = phi4flash.scan_shape(config, 1, 8192)
    s_fwd, s_bwd = least(phi4flash.scan_fwd(**scan)), least(phi4flash.scan_bwd(**scan))
    ops = {
        # (calls, seconds): the window kernels at a tenth of their roofline,
        # full and cross together at a quarter, the scan at a fifth
        "%attn_window.1 custom-call bf16[40,8192,128] tpu_custom_call/3": (4, 40 * w_fwd),
        "%attn_window.2 custom-call f32[40,8192,128] tpu_custom_call/6": (4, 40 * w_bwd),
        "%attn_full.1 custom-call bf16[40,8192,128] tpu_custom_call/3": (4, 16 * f_fwd),
        "%attn_cross.1 custom-call bf16[40,8192,128] tpu_custom_call/3": (4, 16 * f_fwd),
        "%attn_full.2 custom-call f32[40,8192,128] tpu_custom_call/6": (4, 16 * f_bwd),
        "%attn_cross.2 custom-call f32[40,8192,128] tpu_custom_call/6": (4, 16 * f_bwd),
        "%ssm_scan.1 custom-call f32[1,8192,5120] tpu_custom_call/6": (4, 20 * s_fwd),
        "%ssm_scan.2 custom-call f32[1,8192,5120] tpu_custom_call/8": (4, 20 * s_bwd),
        "%fusion.9 fusion bf16[8192,2560]": (100, 0.092),
    }
    run = fake_run(config, ops)
    assert reader("diffattn_window_roofline_pct")(run) == pytest.approx(10.0)
    assert reader("diffattn_full_roofline_pct")(run) == pytest.approx(25.0)
    assert reader("ssm_scan_roofline_pct")(run) == pytest.approx(20.0)
    names = ("ssm_scan_roofline_pct", "diffattn_window_roofline_pct",
             "diffattn_full_roofline_pct")
    # a rehearsal without a trace, a program without the scopes (the parent
    # commit under these files), another family's cell: nothing to read
    bare = fake_run(config, {"%fusion.1 fusion f32[8]": (1, 1.0)})
    other = fake_run(config, ops, flops="flops.afmoe")
    for name in names:
        assert reader(name)({**run, "trace": None}) is None
        assert reader(name)(bare) is None
        assert reader(name)(other) is None


def test_the_new_metrics_are_entries_of_the_benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["config"] == "phi-4-mini-flash-reasoning" and cell["chips"] == 1
    assert cell["traffic"] == CELL
    for name in ("ssm_scan_roofline_pct", "diffattn_window_roofline_pct",
                 "diffattn_full_roofline_pct"):
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert entry == dict(name=name, unit="%", better="higher", source="device_trace",
                             layer="kernels", moves="mfu_pct", workloads=[CELL])
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", name + ".py"))


def test_the_cpu_rehearsal_of_the_cell_prints_correct():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "2147489123", "--seconds", "2", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
