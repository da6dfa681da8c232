"""The FLOP and byte functions against counts worked by hand for one block
at d 1024 and for the two configurations as published."""

import json
import os

import pytest

from flops import common, transformer_lm, vit

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_one_block_at_d1024():
    # q, k, v, out: 4 x 1024 x 1024; up and down: 2 x 1024 x 4096
    assert common.block_matmul_params(1024) == 4 * 1048576 + 2 * 4194304 == 12582912
    # QK^T and PV for one token against 1024 keys over 16 heads of 64:
    # 2 products x 2 x 1024 x 1024 = 4194304; causal sees half of the keys
    assert common.attention_flops_per_token(1024, 1024, causal=False) == 4194304
    assert common.attention_flops_per_token(1024, 1024, causal=True) == 2097152


def test_gpt2_medium_per_token():
    c = config("gpt2-medium")
    # 24 blocks and the untied 1024 x 50257 head; embeddings are lookups
    assert transformer_lm.matmul_params(c) == 24 * 12582912 + 51463168 == 353453056
    # 3 x (2 x 353453056 + 24 x 2097152) = 2.2717 GFLOP
    assert transformer_lm.train_flops_per_token(c, 1024) == 2271713280


def test_vit_large_per_patch_token():
    c = config("vit-large-patch16-224")
    assert vit.tokens_per_image(c) == 196
    stem, head = 16 * 16 * 3 * 1024, 1024 * 1000 / 196
    assert vit.matmul_params(c) == pytest.approx(24 * 12582912 + stem + head)
    # 3 x (2 x 302781544.5 + 24 x 4 x 196 x 1024) = 1.8745 GFLOP
    assert vit.train_flops_per_token(c, 196) == pytest.approx(
        3 * (2 * (301989888 + 786432 + 5224.49) + 24 * 802816), rel=1e-9)


def test_flash_kernel_costs_and_roofline():
    shape = dict(batch=8, heads=16, t=1024, head_dim=64, causal=True)
    ops, moved = common.flash_fwd(**shape)
    assert ops == 4 * 8 * 16 * 1024 * 1024 * 64 / 2 == 17179869184
    assert moved == 4 * 8 * 16 * 1024 * 64 * 2 == 67108864
    ops_b, moved_b = common.flash_bwd(**shape)
    assert ops_b == 2.5 * ops and moved_b == 2 * moved
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least, bound = common.least_seconds(ops, moved, peak)
    assert bound == "compute" and least == pytest.approx(17179869184 / 197e12)
    assert common.least_seconds(1e6, 1e9, peak)[1] == "memory"


def test_plan_arguments_repeat_the_published_sizes():
    g = config("gpt2-medium")
    k = g["plan"]["kwargs"]
    assert (k["d_model"], k["num_heads"], k["vocab"], k["max_len"]) == (
        g["n_embd"], g["n_head"], g["vocab_size"], g["n_positions"])
    assert k["client_depth"] + k["server_depth"] == g["n_layer"]
    v = config("vit-large-patch16-224")
    k = v["plan"]["kwargs"]
    assert (k["d_model"], k["num_heads"], k["patch"], k["num_classes"]) == (
        v["hidden_size"], v["num_attention_heads"], v["patch_size"], v["num_labels"])
    assert k["client_depth"] + k["server_depth"] == v["num_hidden_layers"]
    assert k["max_tokens"] == vit.tokens_per_image(v)
    assert v["intermediate_size"] == 4 * v["hidden_size"]  # Block's mlp_ratio
