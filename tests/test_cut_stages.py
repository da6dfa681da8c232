"""models/cut.py — the one scaffold from a family's layers to the stages of
a split plan. The parameter trees of the five families that go through it
are pinned leaf by leaf as they were at the parent of PR 43 (6eb63af):
benchmarks/weights.py cuts one draw of normals into the leaves in flatten
order and a checkpoint names them by path, so a renamed, reordered or lost
leaf is another model. The refusals that moved there are raised through
two families each.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from split_learning_tpu.models import cut
from split_learning_tpu.models.factory import get_plan

# -- the families at their test files' sizes ------------------------------ #

KW = {
    "afmoe": dict(
        vocab=300, d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
        dense_width=192, expert_width=32, experts_total=8, experts_held=4,
        expert_offset=2, experts_per_token=2, shared_experts=1,
        route_scale=2.826, window=8,
        layer_types=["sliding_attention"] * 4 + ["full_attention"],
        dense_layers=1, client_depth=1),
    "phi4flash": dict(
        vocab=300, d_model=64, num_heads=8, num_kv_heads=4, head_dim=8,
        mlp_width=128, window=8, d_state=4, d_conv=4, expand=2, dt_rank=4,
        layers_published=32, mb_per_layer=2, layers_kept=[15, 16, 17, 18, 19],
        client_depth=1),
    "joyai_llm_flash": dict(
        vocab=300, d_model=64, num_heads=4, q_lora_rank=48, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        dense_width=192, expert_width=32, experts_total=8, experts_held=4,
        expert_offset=0, experts_per_token=2, shared_experts=1, layers=5,
        dense_layers=1, client_depth=1, mtp_layers=1),
    "lfm2_moe": dict(
        vocab=300, d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
        conv_taps=3, dense_width=192, expert_width=32, experts_total=8,
        experts_held=4, expert_offset=0, experts_per_token=2,
        layer_types=("conv", "conv", "full_attention", "conv") * 2,
        dense_layers=2, layers_kept=(1, 2, 3, 4, 5), client_depth=1),
    "nemotron_h": dict(
        vocab=300, d_model=64, pattern="MEMEM*EMEMEM*E",
        layers_kept=(0, 1, 2, 3, 4, 5, 6), client_depth=1, mamba_heads=8,
        mamba_head_dim=8, ssm_state=16, ssm_groups=2, conv_taps=4, chunk=8,
        num_heads=4, num_kv_heads=2, head_dim=16, expert_width=32,
        shared_width=64, experts_total=8, experts_held=4, expert_offset=0,
        experts_per_token=2),
}

# -- the leaves, as the parent commit had them ---------------------------- #
# a block maps a path under its place to "dtype[shape]"

TOK = {"embedding": "f32[300,64]"}
SCALE = {"scale": "f32[64]"}
HEAD = {"lm_head": "f32[64,300]", "norm_f": SCALE}
SWIGLU_192 = {"down/kernel": "f32[192,64]", "gate/kernel": "f32[64,192]",
              "up/kernel": "f32[64,192]"}
SWIGLU_32 = {"down/kernel": "f32[32,64]", "gate/kernel": "f32[64,32]",
             "up/kernel": "f32[64,32]"}
EXPERTS = {"down": "f32[4,32,64]", "expert_bias": "f32[8]",
           "gate": "f32[4,64,32]", "router": "f32[64,8]",
           "up": "f32[4,64,32]"}
GROUPED_HEADS = {"k/kernel": "f32[64,32]", "out/kernel": "f32[64,64]",
                 "q/kernel": "f32[64,64]", "v/kernel": "f32[64,32]"}
NORMED_HEADS = {**GROUPED_HEADS, "k_norm/scale": "f32[16]",
                "q_norm/scale": "f32[16]"}

AFMOE_NORMS = {"norm_in": SCALE, "norm_post_attn": SCALE,
               "norm_post_mlp": SCALE, "norm_pre_mlp": SCALE}
AFMOE_ATTN = {**NORMED_HEADS, "gate/kernel": "f32[64,64]"}
AFMOE_DENSE = {"attn": AFMOE_ATTN, "mlp": SWIGLU_192, **AFMOE_NORMS}
AFMOE_ROUTED = {"attn": AFMOE_ATTN, "experts": EXPERTS, "shared": SWIGLU_32,
                **AFMOE_NORMS}
AFMOE_REST = {f"layer{i}": AFMOE_ROUTED for i in (1, 2, 3, 4)}

LN = {"bias": "f32[64]", "scale": "f32[64]"}
PHI_MLP = {"down/kernel": "f32[128,64]", "gate_up/kernel": "f32[64,256]",
           "ln": LN}
PHI_LAMBDAS = {"lambda_k1": "f32[8]", "lambda_k2": "f32[8]",
               "lambda_q1": "f32[8]", "lambda_q2": "f32[8]",
               "out": {"bias": "f32[64]", "kernel": "f32[64,64]"},
               "subln/scale": "f32[16]"}
PHI_ATTN = {"ln1": LN, "mlp": PHI_MLP, "attn": {
    **PHI_LAMBDAS, "k_bias": "f32[32]", "q_bias": "f32[64]",
    "qkv/kernel": "f32[64,128]", "v_bias": "f32[32]"}}
PHI_MAMBA = {"ln1": LN, "mlp": PHI_MLP, "mamba": {
    "A_log": "f32[4,128]", "D": "f32[128]", "conv_bias": "f32[128]",
    "conv_kernel": "f32[4,128]", "dt_bias": "f32[128]",
    "dt_proj": "f32[4,128]", "in_proj/kernel": "f32[64,256]",
    "out_proj/kernel": "f32[128,64]", "x_proj": "f32[128,12]"}}
PHI_GMU = {"ln1": LN, "mlp": PHI_MLP, "gmu": {
    "in_proj/kernel": "f32[64,128]", "out_proj/kernel": "f32[128,64]"}}
PHI_CROSS = {"ln1": LN, "mlp": PHI_MLP, "attn": {
    **PHI_LAMBDAS, "q": {"bias": "f32[64]", "kernel": "f32[64,64]"}}}
PHI_REST = {"layer16": PHI_MAMBA, "layer17": PHI_ATTN, "layer18": PHI_GMU,
            "layer19": PHI_CROSS}
PHI_HEAD = {"lm_head": "f32[64,300]", "norm_f": LN}

JOYAI_ATTN = {"kv_a/kernel": "f32[64,40]", "kv_a_norm/scale": "f32[32]",
              "kv_b": "f32[32,128]", "out/kernel": "f32[64,64]",
              "q_a/kernel": "f32[64,48]", "q_a_norm/scale": "f32[48]",
              "q_b": "f32[48,96]"}
JOYAI_DENSE = {"attn": JOYAI_ATTN, "mlp": SWIGLU_192, "norm_attn": SCALE,
               "norm_mlp": SCALE}
JOYAI_ROUTED = {"attn": JOYAI_ATTN, "experts": EXPERTS, "shared": SWIGLU_32,
                "norm_attn": SCALE, "norm_mlp": SCALE}
JOYAI_REST = {f"layer{i}": JOYAI_ROUTED for i in (1, 2, 3, 4)}
# flat: one set of leaves under the stage's two methods
JOYAI_HEAD = {**HEAD, "mtp": {
    "block": JOYAI_ROUTED, "eh/kernel": "f32[128,64]", "norm_e": SCALE,
    "norm_h": SCALE, "norm_s": SCALE, "tok": TOK}}

LFM2_NORMS = {"ffn_norm": SCALE, "operator_norm": SCALE}
LFM2_CONV = {"conv_kernel": "f32[3,64]", "in_proj/kernel": "f32[64,192]",
             "out_proj/kernel": "f32[64,64]"}
LFM2_REST = {
    "layer2": {"attn": NORMED_HEADS, "experts": EXPERTS, **LFM2_NORMS},
    **{f"layer{i}": {"conv": LFM2_CONV, "experts": EXPERTS, **LFM2_NORMS}
       for i in (3, 4, 5)}}

NEMO_M = {"norm": SCALE, "mamba": {
    "A_log": "f32[8]", "D": "f32[8]", "conv_bias": "f32[128]",
    "conv_kernel": "f32[4,128]", "dt_bias": "f32[8]",
    "in_proj/kernel": "f32[64,200]", "norm": SCALE,
    "out_proj/kernel": "f32[64,64]"}}
NEMO_E = {"norm": SCALE,
          "experts": {k: v for k, v in EXPERTS.items() if k != "gate"},
          "shared": {"down/kernel": "f32[64,64]", "up/kernel": "f32[64,64]"}}
NEMO_REST = {"layer1": NEMO_E, "layer2": NEMO_M, "layer3": NEMO_E,
             "layer4": NEMO_M, "layer5": {"norm": SCALE,
                                          "attn": GROUPED_HEADS},
             "layer6": NEMO_E}


def nested(embed, rest, head):
    """Both modes' trees of a family whose server stage holds the head
    under ``head``."""
    return {"split": {"embed": embed, "trunk_head": {**rest, "head": head}},
            "u_split": {"embed": embed, "trunk": rest, "head": head}}


TREES = {
    "afmoe": nested({"tok": TOK, "layer0": AFMOE_DENSE}, AFMOE_REST, HEAD),
    "phi4flash": nested({"tok": TOK, "layer15": PHI_ATTN}, PHI_REST,
                        PHI_HEAD),
    "joyai_llm_flash": {
        "split": {"embed": {"tok": TOK, "layer0": JOYAI_DENSE},
                  "trunk_head": {**JOYAI_REST, **JOYAI_HEAD}},
        "u_split": {"embed": {"tok": TOK, "layer0": JOYAI_DENSE},
                    "trunk": JOYAI_REST, "head": JOYAI_HEAD}},
    "lfm2_moe": nested(
        {"tok": TOK, "layer1": {"conv": LFM2_CONV, "mlp": SWIGLU_192,
                                **LFM2_NORMS}}, LFM2_REST, HEAD),
    "nemotron_h": nested({"tok": TOK, "layer0": NEMO_M}, NEMO_REST, HEAD),
}
LEAVES = {"afmoe": 93, "phi4flash": 76, "joyai_llm_flash": 105,
          "lfm2_moe": 54, "nemotron_h": 56}


def paths(block, prefix=""):
    """``["path dtype[shape]", ...]`` of a nested block, unordered."""
    if isinstance(block, str):
        return [f"{prefix[:-1]} {block}"]
    return [line for key, sub in block.items()
            for line in paths(sub, f"{prefix}{key}/")]


@pytest.mark.parametrize("mode", ["split", "u_split"])
@pytest.mark.parametrize("family", sorted(KW))
def test_the_parameter_trees_are_the_parents(family, mode):
    plan = get_plan(family, mode, jnp.float32, **KW[family])
    shapes = jax.eval_shape(plan.init, jax.random.PRNGKey(0),
                            np.zeros((2, 24), np.int32))
    want = TREES[family][mode]
    assert [s.name for s in plan.stages] == list(want)
    found = []
    for stage, tree in zip(plan.stages, shapes):
        assert set(tree) == {"params"}
        flat, _ = jax.tree_util.tree_flatten_with_path(tree["params"])
        got = ["/".join(p.key for p in path) + " " + "{}[{}]".format(
            jnp.dtype(leaf.dtype).name.replace("float", "f"),
            ",".join(map(str, leaf.shape))) for path, leaf in flat]
        # flatten order is the sorted paths' ("/" sorts under a name's letters)
        assert got == sorted(paths(want[stage.name])), stage.name
        found += got
    assert len(found) == LEAVES[family]


# -- the refusals that moved, through two families each ------------------- #

@pytest.mark.parametrize("families,change,match", [
    (("afmoe", "phi4flash"), dict(attn="ring"),
     r"Unknown attn impl: 'ring' \(expected \('auto', 'full', 'flash'\)\)"),
    (("joyai_llm_flash", "nemotron_h"), dict(experts_held=4, expert_offset=6),
     r"experts \[6, 10\) are not among the router's 8"),
    (("afmoe", "lfm2_moe"), dict(experts_held=0), "not among the router's"),
    (("joyai_llm_flash", "phi4flash"), dict(client_depth=6),
     "client_depth 6 of 5 layers"),
    (("afmoe", "lfm2_moe"), dict(client_depth=-1), "client_depth -1 of 5"),
    (("afmoe", "nemotron_h"), dict(num_kv_heads=3),
     "3 key/value heads do not divide 4 query heads"),
    (("phi4flash", "lfm2_moe"), dict(layers_kept=(3, 2)),
     r"layers_kept \[3, 2\] are not distinct rising indices of \d+ published"),
    (("phi4flash", "nemotron_h"), dict(layers_kept=()), "distinct rising"),
    (("lfm2_moe", "nemotron_h"), dict(layers_kept=(0, 1)),
     r"keep no \['(full_attention|\*)'\] layer, a kind"),
])
def test_shared_refusals_through_two_families(families, change, match):
    for family in families:
        for mode in ("split", "u_split"):
            with pytest.raises(ValueError, match=match):
                get_plan(family, mode, **{**KW[family], **change})


@pytest.mark.parametrize("family", sorted(KW))
def test_no_stage_decodes_and_one_message_says_why(family):
    """Every stage of both plans refuses a cache with the one message,
    which names what each kind of layer would need."""
    for mode in ("split", "u_split"):
        plan = get_plan(family, mode, jnp.float32, **KW[family])
        x = np.zeros((2, 8), np.int32)
        params = jax.eval_shape(plan.init, jax.random.PRNGKey(0), x)
        for stage, p in zip(plan.stages, params):
            for kw in (dict(cache_len=8), dict(decode_cache={})):
                with pytest.raises(NotImplementedError) as err:
                    stage.apply(p, x, **kw)
                for words in ("KV-cache", "a cache that forgets",
                              "short convolution", "recurrent state",
                              "state-space layer its state", "latent cache"):
                    assert words in str(err.value)


def test_the_scaffold_knows_no_family():
    """models/cut.py imports no family, and no stage's ``run`` defaults to
    one family's layers."""
    import ast
    import dataclasses
    import inspect
    imported = {node.module for node in ast.walk(ast.parse(
        inspect.getsource(cut))) if isinstance(node, ast.ImportFrom)}
    assert not [m for m in imported if m.startswith(
        "split_learning_tpu.models")]
    for stage in (cut.EmbedStage, cut.TrunkStage):
        run = {f.name: f for f in dataclasses.fields(stage)}["run"]
        assert run.default is dataclasses.MISSING
