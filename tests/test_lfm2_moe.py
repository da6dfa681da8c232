"""The lfm2_moe family (models/lfm2_moe.py) against the benchmark's plain
reference (benchmarks/reference/lfm2_moe.py): the loss and every gradient,
the fused first steps, the two-party path, the shares of the experts, the
gated short convolution (its taps, its causality, its start), grouped heads
of 64 through the flash kernels, the order of norm and rotary, the plans
and what they refuse, the scopes and the step's counters, and ``remat``.
CPU, small sizes; the flash kernels (where forced) and the grouped products
in interpret mode."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from split_learning_tpu import obs
from split_learning_tpu.core.losses import plan_loss
from split_learning_tpu.models import get_plan
from split_learning_tpu.models import lfm2_moe as family
from split_learning_tpu.models.afmoe import pair_rungs, rope
from split_learning_tpu.obs import spans
from split_learning_tpu.ops.common import causal_depthwise_conv
from split_learning_tpu.ops.flash_attention import flash_attention
from split_learning_tpu.ops.ring_attention import full_attention
from split_learning_tpu.runtime import ServerRuntime, SplitClientTrainer
from split_learning_tpu.runtime.fused import FusedSplitTrainer
from split_learning_tpu.transport import LocalTransport
from split_learning_tpu.utils import Config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.join(ROOT, "benchmarks") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
from reference import lfm2_moe as reference          # noqa: E402
from reference import common as ref_common           # noqa: E402

# the rehearsal's sizes: the published pattern of kinds, a dense conv
# layer and one whole period of routed ones (attention, conv, conv, conv),
# 4 query heads over 2 key/value heads of 16, 4 of 8 experts held, 2 a token
TYPES = ("conv", "conv", "full_attention", "conv") * 2
KW = dict(vocab=300, d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
          conv_taps=3, dense_width=192, expert_width=32, experts_total=8,
          experts_held=4, expert_offset=0, experts_per_token=2, route_scale=1.0,
          layer_types=TYPES, dense_layers=2, layers_kept=(1, 2, 3, 4, 5),
          client_depth=1, rope_theta=1e6, norm_eps=1e-5, attn="auto",
          remat=True)
B, T, LR = 2, 16, 1e-3
CONFIG = {"plan": {"kwargs": KW}}


def sizes(**over):
    """The family's ``Sizes`` at ``KW``, float32."""
    names = {f.name for f in dataclasses.fields(family.Sizes)}
    return family.Sizes(**{**{k: v for k, v in KW.items() if k in names},
                           "eps": KW["norm_eps"], "dtype": jnp.float32, **over})


def batches(n, seed=0):
    rs = np.random.RandomState(seed)
    ids = rs.randint(0, KW["vocab"], (n, B, T + 1)).astype(np.int32)
    return [(a[:, :-1], a[:, 1:]) for a in ids]


def seeded(plan, x, seed=1):
    """``plan.init``'s weights moved off their constants (norm scales
    around 1, the selection bias around 0), in float32."""
    params = plan.init(jax.random.PRNGKey(seed), x)
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 100), len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        leaf + 0.02 * jax.random.normal(k, leaf.shape, leaf.dtype)
        for leaf, k in zip(leaves, keys)])


def flat(tree):
    return {jax.tree_util.keystr(k): np.asarray(v, np.float32)
            for k, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


def config():
    return Config(mode="split", model="lfm2_moe", optimizer="adamw", lr=LR,
                  batch_size=B)


# float32 on the CPU: both sides are the same arithmetic in another order
# (whole arrays against blocks of heads, queries and tokens; the routed
# part by sorted rows against a scan over experts), so a leaf's gradient
# agrees to 2e-4 of its largest entry. bfloat16 products against the
# float32 reference: 8 mantissa bits through five layers; the loss within
# 0.05, a leaf's gradient norm within 8 % of the reference's or of the
# median leaf's (the measure benchmarks/check.py takes).
@pytest.mark.parametrize("dtype,loss_tol,grad_tol", [
    ("float32", 2e-5, 2e-4), ("bfloat16", 0.05, 0.08)])
def test_loss_and_every_gradient_match_the_reference(dtype, loss_tol, grad_tol):
    plan = get_plan("lfm2_moe", "split", jnp.dtype(dtype), **KW)
    (x, y), = batches(1)
    params = seeded(plan, x)
    want, want_g = jax.value_and_grad(
        reference.loss_fn(CONFIG, "f32"), argnums=(0, 1))(
            params[0], params[1], x, y)
    got, got_g = jax.jit(jax.value_and_grad(
        lambda p: plan_loss(plan, p, x, y)))(params)
    assert abs(float(got) - float(want)) <= loss_tol
    ref, prog = flat(want_g), flat(got_g)
    assert ref.keys() == prog.keys()
    # every kind of leaf the family adds takes gradient
    for leaf in ("[0]['params']['layer1']['conv']['conv_kernel']",
                 "[1]['params']['layer2']['attn']['q_norm']['scale']",
                 "[1]['params']['layer3']['conv']['in_proj']['kernel']"):
        assert np.abs(ref[leaf]).max() > 0, leaf
    if dtype == "float32":
        for name, g in ref.items():
            np.testing.assert_allclose(
                prog[name], g, rtol=0, atol=grad_tol * max(np.abs(g).max(), 1e-6),
                err_msg=name)
    else:
        norms = {k: np.linalg.norm(g) for k, g in ref.items()}
        median = np.median(list(norms.values()))
        for name, g in prog.items():
            gap = abs(np.linalg.norm(g) - norms[name]) / max(norms[name], median)
            assert gap <= grad_tol, (name, gap)


def test_three_adamw_steps_match_the_reference():
    """FusedSplitTrainer's first three steps against the reference's
    training loop from the same weights: each loss, and every leaf's
    change (float32: 1e-4 and 2 % of the change's norm). The selection
    bias takes no gradient and does not move."""
    plan = get_plan("lfm2_moe", "split", jnp.float32, **KW)
    steps = batches(3)
    start = seeded(plan, steps[0][0])

    class Seeded(type(plan)):
        def init(self, rng, sample):
            return jax.tree_util.tree_map(jnp.copy, start)

    plan = Seeded(stages=plan.stages, owners=plan.owners)
    trainer = FusedSplitTrainer(plan, config(), jax.random.PRNGKey(0),
                                steps[0][0])
    losses = [trainer.train_step(x, y) for x, y in steps]
    want = ref_common.train(
        reference.loss_fn(CONFIG, "f32"),
        lambda: ([jax.tree_util.tree_map(jnp.copy, start[0])],
                 jax.tree_util.tree_map(jnp.copy, start[1])),
        [[xy] for xy in steps], LR, B)
    np.testing.assert_allclose(losses, [l[0] for l in want["losses"]], atol=1e-4)
    got = {"client0": ref_common.named(ref_common.leaf_delta_norms(
        trainer.state.params[0], start[0])),
        "server": ref_common.named(ref_common.leaf_delta_norms(
            trainer.state.params[1], start[1]))}
    for party, leaves in want["delta_norms"].items():
        for name, norm in leaves.items():
            if name.endswith("expert_bias"):
                assert got[party][name] == norm == 0.0
            else:
                assert got[party][name] == pytest.approx(norm, rel=0.02), name


def test_fused_step_equals_the_two_party_step():
    """One program for the whole split step against a SplitClientTrainer
    and a ServerRuntime of the same plan over the local wire: only the
    cut tensor and its gradient cross."""
    plan = get_plan("lfm2_moe", "split", jnp.float32, **KW)
    steps = batches(3)
    trainer = FusedSplitTrainer(plan, config(), jax.random.PRNGKey(3),
                                steps[0][0])
    fused = [trainer.train_step(x, y) for x, y in steps]
    server = ServerRuntime(plan, config(), jax.random.PRNGKey(3), steps[0][0])
    client = SplitClientTrainer(plan, config(), jax.random.PRNGKey(3),
                                LocalTransport(server))
    party = [client.train_step(x, y, i) for i, (x, y) in enumerate(steps)]
    np.testing.assert_allclose(fused, party, rtol=1e-5, atol=1e-6)


def test_the_shares_add_up():
    """8 experts in 4 shares of 2: the routed parts that all the shares
    give are the uncut layer's (there is no shared expert to count
    once), and the uncut reference gives the same layer."""
    h = jax.random.normal(jax.random.PRNGKey(0), (B, T, 64), jnp.float32)
    whole = family.Lfm2Layer(sizes(experts_held=8), 3)
    p = whole.init(jax.random.PRNGKey(1), h)["params"]
    assert set(p) == {"operator_norm", "conv", "ffn_norm", "experts"}
    p["experts"]["expert_bias"] = 0.1 * jax.random.normal(
        jax.random.PRNGKey(2), (8,))
    routed = lambda layer, params: layer.apply(
        {"params": params}, h, capture_intermediates=lambda m, _: m.name == (
            "experts"))[1]["intermediates"]["experts"]["__call__"][0]
    parts = 0.0
    for share in range(4):
        cut = {**p, "experts": {**p["experts"], **{
            n: p["experts"][n][2 * share:2 * share + 2]
            for n in ("gate", "up", "down")}}}
        parts = parts + routed(family.Lfm2Layer(sizes(
            experts_held=2, expert_offset=2 * share), 3), cut)
    np.testing.assert_allclose(parts, routed(whole, p), atol=1e-5)
    kwr = dict(KW, experts_held=8, expert_offset=0)
    mm = ref_common.matmul("f32")
    want = jax.vmap(lambda one: reference.layer(p, one, kwr, mm))(h)
    np.testing.assert_allclose(whole.apply({"params": p}, h), want, atol=2e-5)


def test_short_conv_is_the_direct_sum_is_causal_and_starts_from_zeros():
    """``y_t = (C_t * sum_k w_k (B x)_{t-2+k}) W_out`` written out token by
    token; a change at token t moves nothing before t; the first two
    tokens' sums run over zeros where the sequence has no past."""
    d, t = 8, 12
    conv = family.ShortConv(sizes())
    u = jax.random.normal(jax.random.PRNGKey(0), (B, t, d))
    p = seeded(conv, u)["params"]
    assert p["conv_kernel"].shape == (3, d) and set(p) == {
        "in_proj", "conv_kernel", "out_proj"}
    got = np.asarray(conv.apply({"params": p}, u), np.float64)
    w_in, w_out, w = (np.asarray(a, np.float64) for a in (
        p["in_proj"]["kernel"], p["out_proj"]["kernel"], p["conv_kernel"]))
    bcx = np.asarray(u, np.float64) @ w_in
    gate_b, gate_c, x = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
    g = gate_b * x
    want = np.zeros_like(got)
    for i in range(t):
        c = sum(w[k] * g[:, i - 2 + k] for k in range(3) if i - 2 + k >= 0)
        want[:, i] = (gate_c[:, i] * c) @ w_out
    np.testing.assert_allclose(got, want, atol=1e-5)
    # token 0 sees tap 2 alone, token 1 taps 1 and 2
    np.testing.assert_allclose(
        got[:, 0], (gate_c[:, 0] * w[2] * g[:, 0]) @ w_out, atol=1e-5)
    np.testing.assert_allclose(
        got[:, 1], (gate_c[:, 1] * (w[1] * g[:, 0] + w[2] * g[:, 1])) @ w_out,
        atol=1e-5)
    # causal, and three taps long: token 5 moves tokens 5, 6, 7 and no other
    moved = np.asarray(conv.apply({"params": p}, u.at[:, 5].add(1.0)))
    changed = np.abs(moved - got).max(axis=(0, 2)) > 1e-7
    assert changed.tolist() == [i in (5, 6, 7) for i in range(t)]
    # the shared function is the family's taps and phi4flash's alike
    taps4 = jax.random.normal(jax.random.PRNGKey(1), (4, d))
    y = np.asarray(causal_depthwise_conv(u, taps4))
    np.testing.assert_allclose(
        y[:, 3], sum(np.asarray(taps4[k]) * np.asarray(u[:, k]) for k in range(4)),
        atol=1e-5)
    np.testing.assert_allclose(y[:, 0], np.asarray(taps4[3] * u[:, 0]), atol=1e-6)


@pytest.mark.parametrize("onepass", [None, False], ids=["onepass", "split"])
@pytest.mark.parametrize("t", [300, 384], ids=["ragged", "whole-blocks"])
def test_grouped_heads_of_64_through_the_flash_kernels_equal_the_dense_form(
        monkeypatch, onepass, t):
    """8 query heads over 2 key/value heads at a head of 64, which the
    kernels pad to 128 lanes (the published 32 over 8 at the same group of
    four), through both backward forms: the output and the gradient of q,
    k and v equal the dense path's."""
    import importlib
    fa = importlib.import_module("split_learning_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "ONEPASS", onepass)
    fa._make_flash.cache_clear()
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (1, t, 8, 64))
    k = jax.random.normal(ks[1], (1, t, 2, 64))
    v = jax.random.normal(ks[2], (1, t, 2, 64))
    w = jax.random.normal(ks[3], q.shape)
    f = lambda fn: jax.value_and_grad(
        lambda *ops: jnp.sum(fn(*ops, causal=True) * w), argnums=(0, 1, 2))
    want, got = f(full_attention)(q, k, v), f(flash_attention)(q, k, v)
    fa._make_flash.cache_clear()
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-3)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(a, b, atol=5e-5 * max(1.0, float(
            jnp.abs(b).max())), rtol=5e-5)


def test_the_norms_come_before_the_rotary():
    """``rope(norm(q))``, not ``norm(rope(q))``: with a scale that differs
    by lane the two orders differ, and the layer equals the first written
    out by hand over the dense attention."""
    s = sizes(attn="full")
    attn = family.Lfm2Attention(s)
    u = jax.random.normal(jax.random.PRNGKey(0), (B, T, 64))
    p = attn.init(jax.random.PRNGKey(1), u)["params"]
    ramp = 1.0 + jnp.arange(16.0) / 8
    p = {**p, "q_norm": {"scale": ramp}, "k_norm": {"scale": ramp[::-1]}}
    norm = lambda scale, x: x * jax.lax.rsqrt(
        jnp.mean(x * x, -1, keepdims=True) + 1e-5) * scale
    q = (u @ p["q"]["kernel"]).reshape(B, T, 4, 16)
    k = (u @ p["k"]["kernel"]).reshape(B, T, 2, 16)
    v = (u @ p["v"]["kernel"]).reshape(B, T, 2, 16)

    def by_hand(turn_q, turn_k):
        o = full_attention(turn_q(q), turn_k(k), v, causal=True)
        return o.reshape(B, T, 64) @ p["out"]["kernel"]

    first = by_hand(lambda x: rope(norm(ramp, x), 1e6),
                    lambda x: rope(norm(ramp[::-1], x), 1e6))
    other = by_hand(lambda x: norm(ramp, rope(x, 1e6)),
                    lambda x: norm(ramp[::-1], rope(x, 1e6)))
    got = attn.apply({"params": p}, u)
    np.testing.assert_allclose(got, first, atol=1e-5)
    assert float(jnp.abs(first - other).max()) > 1e-3


@pytest.mark.parametrize("mode,stages", [("split", 2), ("u_split", 3),
                                         ("federated", 2)])
def test_every_mode_builds_and_none_decodes(mode, stages):
    plan = get_plan("lfm2_moe", mode, jnp.float32, **KW)
    assert plan.num_stages == stages
    assert plan.owners == ("client", "server", "client")[:stages]
    assert all(s.objective is None for s in plan.stages)
    (x, y), = batches(1)
    params = plan.init(jax.random.PRNGKey(0), x)
    assert plan.apply(params, x).shape == (B, T, KW["vocab"])
    # the client holds the embedding and published layer 1, dense
    assert set(params[0]["params"]) == {"tok", "layer1"}
    assert set(params[0]["params"]["layer1"]) == {
        "operator_norm", "conv", "ffn_norm", "mlp"}
    kept = {k for k in params[1]["params"] if k.startswith("layer")}
    assert kept == {"layer2", "layer3", "layer4", "layer5"}
    assert "attn" in params[1]["params"]["layer2"]
    assert all("shared" not in params[1]["params"][k] for k in kept)
    assert np.isfinite(float(plan_loss(plan, params, x, y)))
    # the embedding is not scaled (afmoe's is, by sqrt(d_model))
    h = plan.stages[0].apply(
        {"params": {**params[0]["params"], "layer1": jax.tree_util.tree_map(
            jnp.zeros_like, params[0]["params"]["layer1"])}}, x)
    np.testing.assert_allclose(
        h, params[0]["params"]["tok"]["embedding"][x], atol=1e-6)
    with pytest.raises(NotImplementedError, match="KV-cache"):
        plan.stages[0].apply(params[0], x, cache_len=T)
    with pytest.raises(NotImplementedError, match="short convolution"):
        plan.stages[1].apply(params[1], jnp.zeros((B, T, 64)), decode_cache={})


@pytest.mark.parametrize("change,match", [
    (dict(layers_kept=(0, 1, 3, 4, 5)), "keep no .'full_attention'. layer"),
    (dict(layers_kept=(2, 6)), "keep no .'conv'. layer"),
    (dict(layers_kept=(1, 2, 2)), "distinct rising"),
    (dict(layers_kept=(1, 2, 8)), "8 published layers"),
    (dict(layer_types=("conv", "sliding_attention")), "Unknown layer types"),
    (dict(client_depth=6), "client_depth"),
    (dict(experts_held=4, expert_offset=6), "router's 8"),
    (dict(num_kv_heads=3), "do not divide"),
    (dict(head_dim=15), "even head_dim"),
    (dict(attn="ring"), "attn impl"),
])
def test_refused_plans(change, match):
    with pytest.raises(ValueError, match=match):
        get_plan("lfm2_moe", "split", **{**KW, **change})


def test_the_scopes_name_the_new_parts_and_the_step_counts_four_layers():
    assert spans.SHORT_CONV in spans.DEVICE_SCOPES
    plan = get_plan("lfm2_moe", "split", jnp.float32, **KW)
    (x, y), = batches(1)
    shapes = jax.eval_shape(plan.init, jax.random.PRNGKey(0), x)
    text = jax.jit(lambda p: plan_loss(plan, p, x, y)).lower(shapes).as_text(
        debug_info=True)
    for scope in ("short_conv", "attn_full", "moe_route", "moe_experts"):
        assert scope in text, scope
    assert "moe_shared" not in text and "attn_window" not in text
    # the two products of a convolution lie outside its scope
    assert "conv/short_conv" in text and "short_conv/in_proj" not in text
    trainer = FusedSplitTrainer(plan, config(), jax.random.PRNGKey(0), x)
    tr = obs.enable()
    try:
        trainer.train_step(x, y)
    finally:
        obs.disable()
    read, = [r["attrs"] for r in tr.spans() if r["name"] == spans.COUNTERS_READ]
    assert read["layers"] == [f"trunk_head/layer{i}/experts" for i in (2, 3, 4, 5)]
    # 2 x 16 tokens x 2 a token = 64 pairs; 4 of 8 experts held: one rung
    assert read["ladder"] == [[64]] * 4 and read["rows"] == [64] * 4
    assert all(len(p) == 4 and 0 < sum(p) <= 64 for p in read["pairs"])
    # at the published sizes: 8192 tokens x 4 a token, 8 of 64 held
    assert pair_rungs(32768, 8, 64) == (8192, 16384, 32768)


@pytest.mark.parametrize("attn", ["full", "flash"])
def test_remat_changes_no_number(attn):
    """The routed part recomputed or kept, under the dense attention and
    under the flash kernels: the same loss and the same gradients
    (float32: the recomputed forward is the forward)."""
    (x, y), = batches(1)
    out = []
    for remat in (True, False):
        plan = get_plan("lfm2_moe", "split", jnp.float32,
                        **{**KW, "remat": remat, "attn": attn})
        params = seeded(plan, x)
        out.append(jax.jit(jax.value_and_grad(
            lambda p, plan=plan: plan_loss(plan, p, x, y)))(params))
    (l1, g1), (l0, g0) = out
    assert float(l1) == pytest.approx(float(l0), abs=1e-6)
    for (name, a), b in zip(flat(g1).items(), flat(g0).values()):
        np.testing.assert_allclose(a, b, rtol=0,
                                   atol=1e-5 * max(np.abs(b).max(), 1e-3),
                                   err_msg=name)
