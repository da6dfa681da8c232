"""The ouro family (models/ouro.py: one stack of sandwich-normed dense layers
run several times with the same weights, an exit gate and a loss read after
every pass through one head) against the benchmark's plain reference
(benchmarks/reference/ouro.py): the loss and every gradient, the fused first
steps, the two-party path, the loop's one set of leaves and its summed
gradient, one pass against the plain model, where the norms stand, the exit
distribution, the logits ``apply`` gives, ``remat`` in every form, sixteen
ungrouped heads with rotary through the flash kernels, the plans and what
they refuse, the scope and the step's counters. CPU, small sizes; the flash
kernels (where forced) in interpret mode."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from split_learning_tpu import obs
from split_learning_tpu.core.losses import plan_loss
from split_learning_tpu.models import get_plan
from split_learning_tpu.models import ouro as family
from split_learning_tpu.models.afmoe import AfmoeAttention, RMSNorm, SwiGLU, rope
from split_learning_tpu.obs import spans
from split_learning_tpu.ops.flash_attention import flash_attention
from split_learning_tpu.ops.ring_attention import full_attention
from split_learning_tpu.runtime import ServerRuntime, SplitClientTrainer
from split_learning_tpu.runtime.fused import FusedSplitTrainer
from split_learning_tpu.transport import LocalTransport
from split_learning_tpu.utils import Config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.join(ROOT, "benchmarks") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
from reference import ouro as reference              # noqa: E402
from reference import common as ref_common           # noqa: E402

# the rehearsal's sizes: two layers run four times, 4 ungrouped heads of 16
KW = dict(vocab=300, d_model=64, num_heads=4, num_kv_heads=4, head_dim=16,
          width=176, layers=2, passes=4, beta=0.1, client_depth=0,
          rope_theta=1e6, rms_norm_eps=1e-6, attn="auto", remat=True,
          remat_mlp_passes=2)
B, T, LR = 2, 16, 1e-3
CONFIG = {"plan": {"kwargs": KW}}
NORMS = ("input_layernorm", "input_layernorm_2", "post_attention_layernorm",
         "post_attention_layernorm_2")


def batches(n, seed=0):
    rs = np.random.RandomState(seed)
    ids = rs.randint(0, KW["vocab"], (n, B, T + 1)).astype(np.int32)
    return [(a[:, :-1], a[:, 1:]) for a in ids]


def seeded(plan, x, seed=1):
    """``plan.init``'s weights moved off their constants (norm scales
    around 1, the gate's bias around 0), in float32."""
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
    return Config(mode="split", model="ouro", optimizer="adamw", lr=LR,
                  batch_size=B)


def top_stage(**over):
    """The server's top stage as a flax module, float32."""
    kw = {**KW, **over}
    sizes = family.Sizes(
        d_model=kw["d_model"], num_heads=kw["num_heads"],
        num_kv_heads=kw["num_kv_heads"], head_dim=kw["head_dim"],
        width=kw["width"], rope_theta=kw["rope_theta"],
        eps=kw["rms_norm_eps"], attn=kw["attn"], dtype=jnp.float32)
    return family.LoopStage(kw["vocab"], sizes, kw["layers"], kw["passes"],
                            kw["beta"], kw["remat"], kw["remat_mlp_passes"])


# float32 on the CPU: both sides are the same arithmetic in another order
# (whole arrays against blocks of heads, queries and tokens), so a leaf's
# gradient agrees to 2e-4 of its largest entry. bfloat16 products against
# the float32 reference: 8 mantissa bits through eight layer applications;
# the loss within 0.05, a leaf's gradient norm within 8 % of the
# reference's or of the median leaf's (the measure benchmarks/check.py takes).
@pytest.mark.parametrize("dtype,loss_tol,grad_tol", [
    ("float32", 2e-5, 2e-4), ("bfloat16", 0.05, 0.08)])
def test_loss_and_every_gradient_match_the_reference(dtype, loss_tol, grad_tol):
    plan = get_plan("ouro", "split", jnp.dtype(dtype), **KW)
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
    # every kind of leaf the family adds takes gradient: the norm after a
    # branch, and the gate's two leaves (from the differences of the
    # passes' losses and from the entropy term)
    for leaf in ("[1]['params']['layer0']['input_layernorm_2']['scale']",
                 "[1]['params']['layer1']['post_attention_layernorm_2']['scale']",
                 "[1]['params']['early_exit_gate']['kernel']",
                 "[1]['params']['early_exit_gate']['bias']"):
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
    change (float32: 1e-4 and 2 % of the change's norm)."""
    plan = get_plan("ouro", "split", jnp.float32, **KW)
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
            assert got[party][name] == pytest.approx(norm, rel=0.02), name


def test_fused_step_equals_the_two_party_step():
    """One program for the whole split step against a SplitClientTrainer
    and a ServerRuntime of the same plan over the local wire: the server
    runs the whole loop and its objective, and only the embedded tokens
    and their gradient cross, once each way."""
    plan = get_plan("ouro", "split", jnp.float32, **KW)
    steps = batches(3)
    trainer = FusedSplitTrainer(plan, config(), jax.random.PRNGKey(3),
                                steps[0][0])
    fused = [trainer.train_step(x, y) for x, y in steps]
    server = ServerRuntime(plan, config(), jax.random.PRNGKey(3), steps[0][0])
    client = SplitClientTrainer(plan, config(), jax.random.PRNGKey(3),
                                LocalTransport(server))
    party = [client.train_step(x, y, i) for i, (x, y) in enumerate(steps)]
    np.testing.assert_allclose(fused, party, rtol=1e-5, atol=1e-6)


def test_the_tree_holds_the_layers_once_and_a_gradient_sums_four_uses():
    """Two layers run four times are two layers in the tree, and a leaf's
    gradient is the sum of the gradients of four untied copies: the same
    objective written with a set of layer weights a pass (the reference's
    layer, exits and objective, each pass given its own copy)."""
    plan = get_plan("ouro", "split", jnp.float32, **KW)
    (x, y), = batches(1)
    params = seeded(plan, x)
    server = params[1]["params"]
    assert set(server) == {"layer0", "layer1", "norm_f", "early_exit_gate",
                           "lm_head"}
    assert set(params[0]["params"]) == {"tok"}
    assert set(server["layer0"]) == {*NORMS, "self_attn", "mlp"}
    assert set(server["layer0"]["self_attn"]) == {"q", "k", "v", "out"}
    assert server["early_exit_gate"]["kernel"].shape == (64, 1)
    assert server["early_exit_gate"]["bias"].shape == (1,)
    kw, mm = {**KW}, ref_common.matmul("f32")
    layers = {n: server[n] for n in ("layer0", "layer1")}
    rest = {n: v for n, v in server.items() if n not in layers}

    def untied(copies, rest, tokens, labels):
        h = params[0]["params"]["tok"]["embedding"][tokens]
        found = []
        for one in copies:
            for name in ("layer0", "layer1"):
                h = reference.layer(one[name], h, kw, mm)
            h = reference.rms_norm(rest["norm_f"], h, kw["rms_norm_eps"])
            found.append(h)
        return reference.objective(rest, jnp.stack(found), labels, kw, mm)

    mean = lambda copies: np.mean([float(untied(copies, rest, a, b))
                                   for a, b in zip(x, y)])
    tied, tied_g = jax.value_and_grad(lambda p: plan_loss(plan, p, x, y))(params)
    assert float(tied) == pytest.approx(mean([layers] * 4), abs=2e-5)
    per_copy = jax.grad(lambda copies: sum(
        untied(copies, rest, a, b) for a, b in zip(x, y)) / B)([layers] * 4)
    summed = jax.tree_util.tree_map(lambda *g: sum(g), *per_copy)
    for (name, want), got in zip(
            flat(summed).items(),
            flat({n: tied_g[1]["params"][n] for n in layers}).values()):
        np.testing.assert_allclose(
            got, want, rtol=0, atol=2e-4 * max(np.abs(want).max(), 1e-6),
            err_msg=name)
    # and a single copy's gradient is not the tied leaf's: the sum matters
    one = flat(per_copy[0])["['layer0']['mlp']['down']['kernel']"]
    tied_leaf = flat(tied_g[1]["params"]["layer0"])["['mlp']['down']['kernel']"]
    assert np.abs(one - tied_leaf).max() > 1e-3 * np.abs(tied_leaf).max()


def test_one_pass_without_entropy_is_the_plain_model_with_cross_entropy():
    """``passes`` 1: the one exit takes all the mass, the entropy of a
    point is 0, and the objective is the cross-entropy of ``apply``'s
    logits; with ``beta`` 0 nothing is left of the gate."""
    (x, y), = batches(1)
    for beta in (0.0, 0.1):
        plan = get_plan("ouro", "split", jnp.float32,
                        **{**KW, "passes": 1, "beta": beta,
                           "remat_mlp_passes": 0})
        params = seeded(plan, x)
        ce = optax.softmax_cross_entropy_with_integer_labels(
            plan.apply(params, x), y).mean()
        loss, grads = jax.value_and_grad(
            lambda p: plan_loss(plan, p, x, y))(params)
        assert float(loss) == pytest.approx(float(ce), abs=1e-6)
        gate = grads[1]["params"]["early_exit_gate"]
        assert float(jnp.abs(gate["kernel"]).max()) == 0.0


def test_the_norm_after_a_branch_is_inside_the_sum_and_norm_f_closes_a_pass():
    """A layer by hand over the shared modules: ``h + N2(Attn(N1(h)))``,
    then ``h + N4(MLP(N3(h)))``, which is not the pre-norm layer; and the
    second pass's input is the final norm's output, not the residual
    stream."""
    top = top_stage(attn="full", remat=False, passes=2, remat_mlp_passes=0)
    h = jax.random.normal(jax.random.PRNGKey(0), (B, T, 64))
    p = seeded(top, h)["params"]
    layer = family.OuroLayer(top.sizes)
    lp = p["layer0"]
    norm = lambda name, v: RMSNorm(1e-6).apply({"params": lp[name]}, v)
    attn = AfmoeAttention(4, 4, 16, None, 1e6, 1e-6, "full", jnp.float32,
                          plain=True, rope_always=True)
    a = attn.apply({"params": lp["self_attn"]}, norm(NORMS[0], h))
    mid = h + norm(NORMS[1], a)
    m = SwiGLU(176).apply({"params": lp["mlp"]}, norm(NORMS[2], mid))
    want = mid + norm(NORMS[3], m)
    np.testing.assert_allclose(layer.apply({"params": lp}, h), want, atol=1e-5)
    # the norm after a branch stands: a layer that adds the branch's
    # output as it is (every other family's) is another layer
    assert float(jnp.abs(want - (mid + m)).max()) > 0.1
    # two passes by hand: norm_f after every pass, its output fed back
    both = lambda v: layer.apply({"params": p["layer1"]},
                                 layer.apply({"params": p["layer0"]}, v))
    norm_f = lambda v: RMSNorm(1e-6).apply({"params": p["norm_f"]}, v)
    e2 = norm_f(both(norm_f(both(h))))
    np.testing.assert_allclose(top.apply({"params": p}, h),
                               e2 @ p["lm_head"], atol=2e-4)
    unclosed = norm_f(both(both(h)))
    assert float(jnp.abs(unclosed - e2).max()) > 1e-3


def test_q_sums_to_one_and_the_last_pass_takes_the_rest():
    z = 3.0 * jax.random.normal(jax.random.PRNGKey(0), (3, 5, 4))
    q = np.exp(np.asarray(family.log_exit_distribution(z)))
    lam = np.asarray(jax.nn.sigmoid(z))
    np.testing.assert_allclose(q.sum(-1), 1.0, atol=1e-6)
    np.testing.assert_allclose(q[..., 0], lam[..., 0], atol=1e-6)
    np.testing.assert_allclose(
        q[..., 2], lam[..., 2] * (1 - lam[..., 0]) * (1 - lam[..., 1]), atol=1e-6)
    np.testing.assert_allclose(
        q[..., 3], (1 - lam[..., 0]) * (1 - lam[..., 1]) * (1 - lam[..., 2]),
        atol=1e-6)
    # the last gate decides nothing
    other = np.asarray(family.log_exit_distribution(z.at[..., 3].set(0.123)))
    np.testing.assert_array_equal(np.exp(other), q)
    # and the reference's own writing of it agrees
    ref = reference.log_exit_distribution([z[..., s] for s in range(4)])
    np.testing.assert_allclose(np.exp(np.stack(ref, -1)), q, atol=1e-6)


# AdamW's first steps swing the gate (on the chip a window's mean exit mass
# reads 1e-7 in a pass by step 11, PERF.md Findings PR 45) until float32's
# sigmoid reads exactly 1 or 0 for some tokens: q is then exactly 0 in a pass,
# and the objective written over q (q log q) gave every leaf a NaN gradient.
@pytest.mark.parametrize("bias", [20.0, 200.0, -120.0])
def test_a_saturated_gate_leaves_the_objective_and_every_gradient_finite(bias):
    plan = get_plan("ouro", "split", jnp.float32, **KW)
    (x, y), = batches(1)
    params = seeded(plan, x)
    params[1]["params"]["early_exit_gate"]["bias"] = jnp.full((1,), bias)
    got, got_g = jax.jit(jax.value_and_grad(
        lambda p: plan_loss(plan, p, x, y)))(params)
    want, want_g = jax.value_and_grad(
        reference.loss_fn(CONFIG, "f32"), argnums=(0, 1))(
            params[0], params[1], x, y)
    assert np.isfinite(float(got)) and np.isfinite(float(want))
    assert float(got) == pytest.approx(float(want), abs=2e-5)
    ref, prog = flat(want_g), flat(got_g)
    for name, g in prog.items():
        assert np.isfinite(g).all() and np.isfinite(ref[name]).all(), name
        np.testing.assert_allclose(
            g, ref[name], rtol=0, atol=2e-4 * max(np.abs(ref[name]).max(), 1e-6),
            err_msg=name)
    if bias < 0:
        # no pass before the last exits: the last takes all the mass, the
        # entropy term is gone and the loss is ``apply``'s cross-entropy
        ce = optax.softmax_cross_entropy_with_integer_labels(
            plan.apply(params, x), y).mean()
        assert float(got) == pytest.approx(float(ce), abs=1e-5)


def test_apply_gives_the_last_pass_logits():
    """``apply`` is ``logits_4``: the reference's fourth exit through the
    head, and not an earlier pass's."""
    plan = get_plan("ouro", "split", jnp.float32, **KW)
    (x, _), = batches(1)
    params = seeded(plan, x)
    c, s = params[0]["params"], params[1]["params"]
    mm = ref_common.matmul("f32")
    found = reference.exits(s, c["tok"]["embedding"][x[0]], {**KW}, mm)
    got = plan.apply(params, x)[0]
    np.testing.assert_allclose(got, found[3] @ s["lm_head"], atol=2e-4)
    assert float(jnp.abs(got - found[2] @ s["lm_head"]).max()) > 1e-3


@pytest.mark.parametrize("attn", ["full", "flash"])
def test_no_form_of_remat_changes_a_number(attn):
    """Nothing recomputed, the elementwise passes alone, and the MLPs'
    ``gate`` and ``up`` again in the first 1, 2, 3 or all 4 passes, under
    the dense attention, and three of the forms under the flash kernels
    (the interpreter is slow): the same loss and the same gradients
    (float32: the recomputed forward is the forward)."""
    (x, y), = batches(1)
    out = []
    forms = [dict(remat=False, remat_mlp_passes=0)] + [
        dict(remat=True, remat_mlp_passes=p)
        for p in (range(5) if attn == "full" else (0, 2))]
    for form in forms:
        plan = get_plan("ouro", "split", jnp.float32,
                        **{**KW, **form, "attn": attn})
        params = seeded(plan, x)
        out.append(jax.jit(jax.value_and_grad(
            lambda p, plan=plan: plan_loss(plan, p, x, y)))(params))
    l0, g0 = out[0]
    for loss, grads in out[1:]:
        assert float(loss) == pytest.approx(float(l0), abs=1e-6)
        for (name, a), b in zip(flat(grads).items(), flat(g0).values()):
            np.testing.assert_allclose(
                a, b, rtol=0, atol=1e-5 * max(np.abs(b).max(), 1e-3),
                err_msg=name)


def test_remat_runs_no_kernel_twice_and_only_the_named_products():
    """Counted in the jaxpr of the loss's gradient, flash attention forced:
    no form runs a flash forward a second time, the elementwise form runs
    no product a second time, and each pass of ``remat_mlp_passes`` adds
    two products a layer (``gate`` and ``up``; ``down`` runs once)."""
    (x, y), = batches(1)

    def count(**form):
        plan = get_plan("ouro", "split", jnp.float32,
                        **{**KW, **form, "attn": "flash"})
        shapes = jax.eval_shape(plan.init, jax.random.PRNGKey(0), x)
        text = str(jax.make_jaxpr(jax.grad(
            lambda p: plan_loss(plan, p, x, y)))(shapes))
        return text.count("pallas_call"), text.count("dot_general")

    kernels, products = count(remat=False, remat_mlp_passes=0)
    assert count(remat=True, remat_mlp_passes=0) == (kernels, products)
    for p in (1, 2, 4):
        assert count(remat=True, remat_mlp_passes=p) == (
            kernels, products + p * KW["layers"] * 2)


@pytest.mark.parametrize("onepass", [None, False], ids=["onepass", "split"])
@pytest.mark.parametrize("t", [300, 384], ids=["ragged", "whole-blocks"])
def test_ungrouped_heads_with_rotary_through_the_flash_kernels_equal_the_dense_form(
        monkeypatch, onepass, t):
    """16 query heads on 16 key/value heads of 128 (the published heads,
    group 1), q and k turned, through both backward forms: the output and
    the gradient of q, k and v equal the dense path's."""
    import importlib
    fa = importlib.import_module("split_learning_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "ONEPASS", onepass)
    fa._make_flash.cache_clear()
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, w = (jax.random.normal(key, (1, t, 16, 128)) for key in ks)
    f = lambda fn: jax.value_and_grad(
        lambda q, k, v: jnp.sum(fn(rope(q, 1e6), rope(k, 1e6), v,
                                   causal=True) * w), argnums=(0, 1, 2))
    want, got = f(full_attention)(q, k, v), f(flash_attention)(q, k, v)
    fa._make_flash.cache_clear()
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-3)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(a, b, atol=5e-5 * max(1.0, float(
            jnp.abs(b).max())), rtol=5e-5)


def test_a_full_layer_turns_q_and_k_only_where_asked():
    """``rope_always`` is the one thing the shared attention gains: a
    plain full layer with it is the dense attention of turned q and k,
    and without it (models/nemotron_h.py's) of q and k as they are."""
    u = jax.random.normal(jax.random.PRNGKey(0), (B, T, 64))
    turned = AfmoeAttention(4, 4, 16, None, 1e6, 1e-6, "full", jnp.float32,
                            plain=True, rope_always=True)
    p = turned.init(jax.random.PRNGKey(1), u)["params"]
    assert set(p) == {"q", "k", "v", "out"}
    q, k, v = ((u @ p[n]["kernel"]).reshape(B, T, 4, 16) for n in "qkv")
    by_hand = lambda turn: full_attention(
        turn(q), turn(k), v, causal=True).reshape(B, T, 64) @ p["out"]["kernel"]
    np.testing.assert_allclose(turned.apply({"params": p}, u),
                               by_hand(lambda a: rope(a, 1e6)), atol=1e-5)
    still = AfmoeAttention(4, 4, 16, None, 1e6, 1e-6, "full", jnp.float32,
                           plain=True)
    np.testing.assert_allclose(still.apply({"params": p}, u),
                               by_hand(lambda a: a), atol=1e-5)


@pytest.mark.parametrize("mode", ["split", "federated"])
def test_the_modes_that_build(mode):
    plan = get_plan("ouro", mode, jnp.float32, **KW)
    assert plan.num_stages == 2 and plan.owners == ("client", "server")
    assert [s.name for s in plan.stages] == ["embed", "trunk_head"]
    assert plan.stages[0].objective is None
    assert plan.stages[1].objective is not None
    (x, y), = batches(1)
    params = plan.init(jax.random.PRNGKey(0), x)
    assert plan.apply(params, x).shape == (B, T, KW["vocab"])
    assert np.isfinite(float(plan_loss(plan, params, x, y)))
    # the client's stage is the embedding rows and nothing else
    np.testing.assert_allclose(
        plan.stages[0].apply(params[0], x),
        params[0]["params"]["tok"]["embedding"][x], atol=1e-6)


@pytest.mark.parametrize("mode,change,error,match", [
    ("split", dict(client_depth=1), NotImplementedError,
     "crossed at every pass"),
    ("split", dict(client_depth=2), NotImplementedError, "4 times up, 3 back"),
    ("u_split", {}, NotImplementedError, "every pass's hidden state"),
    ("split", dict(remat_mlp_passes=5), ValueError, "of 4 passes"),
    ("split", dict(passes=0), ValueError, "run 0 times"),
    ("split", dict(num_kv_heads=3), ValueError, "do not divide"),
    ("split", dict(head_dim=15), ValueError, "even head_dim"),
    ("split", dict(attn="ring"), ValueError, "attn impl"),
])
def test_refused_plans(mode, change, error, match):
    with pytest.raises(error, match=match):
        get_plan("ouro", mode, **{**KW, **change})


def test_a_decode_is_refused_by_name():
    plan = get_plan("ouro", "split", jnp.float32, **KW)
    (x, _), = batches(1)
    params = plan.init(jax.random.PRNGKey(0), x)
    with pytest.raises(NotImplementedError, match="KV-cache"):
        plan.stages[0].apply(params[0], x, cache_len=T)
    with pytest.raises(NotImplementedError, match="KV-cache"):
        plan.stages[1].apply(params[1], jnp.zeros((B, T, 64)), decode_cache={})


def test_the_scope_names_the_flash_calls_and_the_step_reports_its_exits():
    plan = get_plan("ouro", "split", jnp.float32, **KW)
    (x, y), = batches(1)
    shapes = jax.eval_shape(plan.init, jax.random.PRNGKey(0), x)
    text = jax.jit(lambda p: plan_loss(plan, p, x, y)).lower(shapes).as_text(
        debug_info=True)
    assert "attn_full" in text and "attn_window" not in text
    assert "moe_" not in text
    assert {spans.EXIT_MASS, spans.EXIT_LOSS} == {"exit_mass", "exit_loss"}
    trainer = FusedSplitTrainer(plan, config(), jax.random.PRNGKey(0), x)
    loss = trainer.train_step(x, y)       # recording off: nothing is read
    tr = obs.enable()
    try:
        trainer.train_step(x, y)
    finally:
        obs.disable()
    read, = [r["attrs"] for r in tr.spans() if r["name"] == spans.COUNTERS_READ]
    assert read["layers"] == ["trunk_head"]
    (mass,), (per_pass,) = read["exit_mass"], read["exit_loss"]
    assert len(mass) == len(per_pass) == 4
    assert sum(mass) == pytest.approx(1.0, abs=1e-5)
    assert all(0.0 < m < 1.0 for m in mass)
    # near its start every exit reads about log(vocab), and the objective
    # is their mean under the exit distribution less beta times an entropy
    assert all(abs(l - np.log(KW["vocab"])) < 0.5 for l in per_pass)
    assert min(per_pass) - 0.1 * np.log(4) - 0.05 <= loss <= max(per_pass) + 0.05
    # a caller that does not ask gets a program without them
    assert "exit_mass" not in str(jax.make_jaxpr(
        lambda p: plan_loss(plan, p, x, y))(shapes))
