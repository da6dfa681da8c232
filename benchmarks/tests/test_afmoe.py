"""The afmoe configuration: FLOPs against a hand count, keys seen under the
window, the kernels' costs, the readers on a made-up trace, the file against
the published sizes, and the CPU rehearsal of the cell."""

import json
import os
import subprocess
import sys

import pytest

from flops import afmoe, common

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "trinity-mini-fused-t8192"


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", "trinity-mini.json")) as f:
        return json.load(f)


def test_keys_seen_under_the_window():
    # query i sees min(i + 1, 4) keys: 1, 2, 3, 4, 4, 4, 4, 4 -> 26 / 8
    assert afmoe.keys_seen(8, 4) == 26 / 8
    assert afmoe.keys_seen(8, None) == afmoe.keys_seen(8, 8) == 4.5
    # 2048 - 2048 * 2047 / 16384
    assert afmoe.keys_seen(8192, 2048) == 1792.125
    brute = sum(min(i + 1, 2048) for i in range(8192)) / 8192
    assert afmoe.keys_seen(8192, 2048) == brute


def test_per_token_flops_against_a_hand_count(config):
    kw = config["plan"]["kwargs"]
    # q, gate, out 3 x 2048 x 4096; k, v 2 x 2048 x 512
    assert afmoe.attention_params(kw) == 25165824 + 2097152 == 27262976
    assert afmoe.expected_pairs_per_token(kw) == 0.5      # 8 x 8 / 128
    dense = 27262976 + 3 * 2048 * 6144                    # + 37748736
    expert = 27262976 + 6291456 + 262144 + 3145728        # shared, router, routed
    assert afmoe.layer_matmul_params(kw, True) == dense == 65011712
    assert afmoe.layer_matmul_params(kw, False) == expert == 36962304
    products = 2 * (dense + 4 * expert + 2048 * 25024)    # 528.2 MFLOP
    scores = 4 * 4096 * (4 * 1792.125 + 4096.5)           # 184.6 MFLOP
    assert afmoe.forward_flops_per_token(config, 8192) == products + scores == 712785920
    assert afmoe.train_flops_per_token(config, 8192) == 3 * 712785920   # 2.1384 GFLOP


def test_kernel_costs(config):
    shape = afmoe.attention_shape(config, 1, 8192)
    assert shape == dict(batch=1, heads=32, kv_heads=4, t=8192, head_dim=128)
    ops, moved = afmoe.attn_fwd(**shape, window=None)
    assert ops == 4 * 32 * 128 * 8192 * 4096.5 and moved == (64 + 8) * 8192 * 128 * 2
    ops_w, _ = afmoe.attn_fwd(**shape, window=2048)
    assert ops_w == 4 * 32 * 128 * 8192 * 1792.125
    ops_b, moved_b = afmoe.attn_bwd(**shape, window=2048)
    assert ops_b == 2.5 * ops_w and moved_b == 2 * moved
    mm = afmoe.expert_mm_shape(config, 1, 8192)
    assert mm == dict(pairs=4096.0, experts=8, d_model=2048, width=1024)
    ops_e, moved_e = afmoe.expert_mm(**mm)
    assert ops_e == 2 * 4096 * 2048 * 1024
    assert moved_e == 4096 * 3072 * 2 + 8 * 2048 * 1024 * 2
    assert afmoe.expert_mm(**mm, weight_itemsize=4)[1] == moved_e + 8 * 2048 * 1024 * 2


def test_plan_arguments_repeat_the_published_sizes(config):
    kw = config["plan"]["kwargs"]
    for ours, theirs in (
            ("d_model", "hidden_size"), ("num_heads", "num_attention_heads"),
            ("num_kv_heads", "num_key_value_heads"), ("head_dim", "head_dim"),
            ("dense_width", "intermediate_size"), ("expert_width", "moe_intermediate_size"),
            ("experts_per_token", "num_experts_per_tok"), ("shared_experts", "num_shared_experts"),
            ("route_scale", "route_scale"), ("window", "sliding_window"),
            ("rope_theta", "rope_theta"), ("rms_norm_eps", "rms_norm_eps"),
            ("experts_held", "num_experts"), ("vocab", "vocab_size")):
        assert kw[ours] == config[theirs], ours
    assert kw["experts_total"] == config["published"]["num_experts"] == 128
    assert len(kw["layer_types"]) == config["num_hidden_layers"] == 5
    # the layers kept are published layers 0 and 4-7
    assert kw["layer_types"] == [config["layer_types"][i] for i in (0, 4, 5, 6, 7)]
    assert kw["layer_types"].count("full_attention") * config["global_attn_every_n_layers"] == 4
    assert config["data"]["vocab"] == kw["vocab"] and kw["vocab"] * 8 == 200192
    assert sorted(config["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "reference", "afmoe.py")) as f:
        text = f.read()
    assert "split_learning_tpu" not in text


def counters(pairs: int, steps: int = 5) -> list:
    """``counters_read`` records of four routed layers that hold ``pairs``
    each, on the lowest rung of trinity-mini's ladder."""
    return [{"name": "counters_read", "party": "client", "span_id": k, "parent_id": 0,
             "duration": 1e-3, "start_ns": k,
             "attrs": {"layers": [f"trunk_head/layer{i}/experts" for i in range(1, 5)],
                       "pairs": [[pairs // 8] * 8] * 4, "rows": [8192] * 4,
                       "ladder": [[8192, 16384, 65536]] * 4}} for k in range(steps)]


def fake_run(config, ops, spans=None):
    import importlib
    seconds = {name: s for name, (_, s) in ops.items()}
    counts = {name: c for name, (c, _) in ops.items()}
    return {"trace": {"op_seconds": seconds, "op_counts": counts},
            "spans": counters(4096) if spans is None else spans,
            "job": {"rows_per_client": 1, "tokens_per_row": 8192, "clients": 1},
            "config": config,
            "flops": importlib.import_module("flops.afmoe"),
            "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


def reader(name):
    sys.path.insert(0, BENCH)
    import run
    return run.layer_reader(name)


def test_the_readers_on_a_made_up_trace(config):
    shape = afmoe.attention_shape(config, 1, 8192)
    fwd = common.least_seconds(*afmoe.attn_fwd(**shape, window=2048),
                               {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})[0]
    bwd = 2.5 * fwd
    ops = {
        # (calls, seconds): the window kernels at exactly half their roofline
        "%attn_window.1 custom-call bf16[32,8192,128] tpu_custom_call/3": (8, 16 * fwd),
        "%attn_window.2 custom-call f32[32,8192,128] tpu_custom_call/6": (4, 8 * bwd),
        "%gmm.3 custom-call bf16[65536,1024] tpu_custom_call/6": (36, 36 * 1e-3),
        "%tgmm.1 custom-call f32[8,2048,1024] tpu_custom_call/7": (12, 12 * 1e-3),
        "%sort.5 sort s32[65536]": (8, 0.002), "%gather.2 gather bf16[65536,2048]": (8, 0.006),
        "%fusion.9 fusion bf16[8192,2048]": (100, 0.092),
    }
    run = fake_run(config, ops)
    assert reader("attn_window_roofline_pct")(run) == pytest.approx(50.0)
    assert reader("attn_full_roofline_pct")(run) is None       # no such event
    rows = 2 * 4096 * 2048 * 1024 / 197e12                     # compute-bound
    weights = (4096 * 3072 * 2 + 8 * 2048 * 1024 * 4) / 819e9   # float32 out: memory-bound
    assert weights > rows
    assert reader("moe_expert_mm_roofline_pct")(run) == pytest.approx(
        100 * (36 * rows + 12 * weights) / 0.048)
    # the calls are costed at the pairs the step's records hold, not at the
    # even count: the same trace with 6144 pairs a layer is held to more work
    rows, weights = (2 * 6144 * 2048 * 1024 / 197e12,
                     (6144 * 3072 * 2 + 8 * 2048 * 1024 * 4) / 819e9)
    assert reader("moe_expert_mm_roofline_pct")(fake_run(config, ops, counters(6144))) == (
        pytest.approx(100 * (36 * rows + 12 * max(rows, weights)) / 0.048))
    # and without the records there is nothing to read
    assert reader("moe_expert_mm_roofline_pct")(fake_run(config, ops, [])) is None
    total = sum(s for _, s in ops.values())
    assert reader("moe_dispatch_ops_share_pct")(run) == pytest.approx(100 * 0.008 / total)
    # a program without the scopes, or a rehearsal without a trace: nothing to read
    for name in ("attn_window_roofline_pct", "attn_full_roofline_pct",
                 "moe_expert_mm_roofline_pct", "moe_dispatch_ops_share_pct"):
        assert reader(name)({**run, "trace": None}) is None
    bare = fake_run(config, {"%fusion.1 fusion f32[8]": (1, 1.0)})
    assert reader("attn_window_roofline_pct")(bare) is None
    assert reader("moe_expert_mm_roofline_pct")(bare) is None
    assert reader("moe_dispatch_ops_share_pct")(bare) == 0.0


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
