"""The joyai_llm_flash family (models/joyai_llm_flash.py) against the
benchmark's plain reference (benchmarks/reference/joyai_llm_flash.py): the
objective and every gradient, the fused first steps, the two-party path
with the objective on the server, the shares of the experts, latent
attention through the flash kernels at unequal widths, interleaved rotary,
what the objective is made of, the paths that refuse it, and that the
other families' calls and steps trace as they did. CPU, small sizes; the
flash kernels (where forced) and the grouped products in interpret mode."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from split_learning_tpu.core.losses import (
    cross_entropy, final_loss, per_example_cross_entropy, plan_loss,
    refuse_objective)
from split_learning_tpu.core.stage import remat_plan
from split_learning_tpu.models import get_plan
from split_learning_tpu.models import joyai_llm_flash as family
from split_learning_tpu.models.cut import TrunkStage
from split_learning_tpu.obs import spans
from split_learning_tpu.ops.flash_attention import flash_attention
from split_learning_tpu.ops.ring_attention import full_attention
from split_learning_tpu.runtime import ServerRuntime, SplitClientTrainer
from split_learning_tpu.runtime.fused import FusedSplitTrainer
from split_learning_tpu.transport import LocalTransport
from split_learning_tpu.utils import Config

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
from reference import joyai_llm_flash as reference   # noqa: E402
from reference import common as ref_common           # noqa: E402

# the rehearsal's sizes: a dense layer and four expert layers, 4 heads
# with keys of 16 + 8 and values of 16, 4 of 8 experts held, 2 a token
KW = dict(vocab=300, d_model=64, num_heads=4, q_lora_rank=48,
          kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
          v_head_dim=16, dense_width=192, expert_width=32, experts_total=8,
          experts_held=4, expert_offset=0, experts_per_token=2,
          shared_experts=1, route_scale=2.5, layers=5, dense_layers=1,
          client_depth=1, rope_theta=32e6, rms_norm_eps=1e-6, mtp_layers=1,
          mtp_lambda=0.3, attn="auto", remat=True)
B, T, LR = 2, 16, 1e-3
CONFIG = {"plan": {"kwargs": KW}}


def sizes(**over):
    """The family's ``Sizes`` at ``KW``, float32."""
    names = {f.name for f in dataclasses.fields(family.Sizes)}
    return family.Sizes(**{**{k: v for k, v in KW.items() if k in names},
                           "eps": KW["rms_norm_eps"], "dtype": jnp.float32,
                           **over})


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


def objective(plan, params, x, y):
    """The plan's loss as every runtime takes it."""
    return plan_loss(plan, params, x, y)


# float32 on the CPU: both sides are the same arithmetic in another order
# (whole arrays against blocks of heads, queries and tokens; the routed
# part by sorted rows against a scan over experts), so a leaf's gradient
# agrees to 2e-4 of its largest entry. bfloat16 products against the
# float32 reference: 8 mantissa bits through six blocks; the loss within
# 0.05, a leaf's gradient norm within 8 % of the reference's or of the
# median leaf's (the measure benchmarks/check.py takes).
@pytest.mark.parametrize("dtype,loss_tol,grad_tol", [
    ("float32", 2e-5, 2e-4), ("bfloat16", 0.05, 0.08)])
def test_loss_and_every_gradient_match_the_reference(dtype, loss_tol, grad_tol):
    plan = get_plan("joyai_llm_flash", "split", jnp.dtype(dtype), **KW)
    (x, y), = batches(1)
    params = seeded(plan, x)
    want, want_g = jax.value_and_grad(
        reference.loss_fn(CONFIG, "f32"), argnums=(0, 1))(
            params[0], params[1], x, y)
    got, got_g = jax.jit(jax.value_and_grad(
        lambda p: objective(plan, p, x, y)))(params)
    assert abs(float(got) - float(want)) <= loss_tol
    ref, prog = flat(want_g), flat(got_g)
    assert ref.keys() == prog.keys()
    # the module's own embedding and the head it shares both take gradient
    assert np.abs(ref["[1]['params']['mtp']['tok']['embedding']"]).max() > 0
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


def trained(make, steps):
    trainer = make()
    return trainer, [trainer.train_step(x, y) for x, y in steps]


def test_three_adamw_steps_match_the_reference():
    """FusedSplitTrainer's first three steps against the reference's
    training loop from the same weights: each loss (both predictions in
    it), and every leaf's change (float32: 1e-4 and 2 % of the change's
    norm). The selection bias takes no gradient and does not move."""
    plan = get_plan("joyai_llm_flash", "split", jnp.float32, **KW)
    steps = batches(3)
    cfg = Config(mode="split", model="joyai_llm_flash", optimizer="adamw",
                 lr=LR, batch_size=B)
    start = seeded(plan, steps[0][0])

    class Seeded(type(plan)):
        def init(self, rng, sample):
            return jax.tree_util.tree_map(jnp.copy, start)

    plan = Seeded(stages=plan.stages, owners=plan.owners)
    trainer, losses = trained(lambda: FusedSplitTrainer(
        plan, cfg, jax.random.PRNGKey(0), steps[0][0]), steps)
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
    and a ServerRuntime of the same plan over the local wire: the
    objective is the server's, which reads the labels it is sent, and
    only the cut tensor and its gradient cross."""
    plan = get_plan("joyai_llm_flash", "split", jnp.float32, **KW)
    cfg = Config(mode="split", model="joyai_llm_flash", optimizer="adamw",
                 lr=LR, batch_size=B)
    steps = batches(3)
    _, fused = trained(lambda: FusedSplitTrainer(
        plan, cfg, jax.random.PRNGKey(3), steps[0][0]), steps)
    server = ServerRuntime(plan, cfg, jax.random.PRNGKey(3), steps[0][0])
    client = SplitClientTrainer(plan, cfg, jax.random.PRNGKey(3),
                                LocalTransport(server))
    party = [client.train_step(x, y, i) for i, (x, y) in enumerate(steps)]
    np.testing.assert_allclose(fused, party, rtol=1e-5, atol=1e-6)
    # both losses are in it: the main cross-entropy alone reads lower
    params = plan.init(jax.random.PRNGKey(3), steps[0][0])
    x, y = steps[0]
    main = float(cross_entropy(plan.apply(params, x), y))
    assert fused[0] > main + 0.2 * main


def test_the_shares_add_up():
    """8 experts in 4 shares of 2: the routed parts that all the shares
    give, with the shared expert counted once, are the uncut layer, and
    the uncut reference gives the same layer."""
    h = jax.random.normal(jax.random.PRNGKey(0), (B, T, 64), jnp.float32)
    whole = family.Layer(sizes(experts_held=8), False)
    p = whole.init(jax.random.PRNGKey(1), h)["params"]
    p["experts"]["expert_bias"] = 0.1 * jax.random.normal(
        jax.random.PRNGKey(2), (8,))
    inner = lambda layer, params: layer.apply(
        {"params": params}, h, capture_intermediates=lambda m, _: m.name in (
            "shared", "experts"))[1]["intermediates"]
    got = inner(whole, p)
    shared, routed = got["shared"]["__call__"][0], got["experts"]["__call__"][0]
    parts = 0.0
    for share in range(4):
        cut = {**p, "experts": {**p["experts"], **{
            n: p["experts"][n][2 * share:2 * share + 2]
            for n in ("gate", "up", "down")}}}
        part = inner(family.Layer(sizes(
            experts_held=2, expert_offset=2 * share), False), cut)
        np.testing.assert_array_equal(part["shared"]["__call__"][0], shared)
        parts = parts + part["experts"]["__call__"][0]
    np.testing.assert_allclose(parts, routed, atol=1e-5)
    kwr = dict(KW, experts_held=8, expert_offset=0)
    mm = ref_common.matmul("f32")
    want = jax.vmap(lambda one: reference.layer(p, one, kwr, mm))(h)
    np.testing.assert_allclose(whole.apply({"params": p}, h), want, atol=2e-5)


def mla_operands(t, heads, rank_q=48, rank_kv=32, d_n=16, d_r=8, d_v=16,
                 seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(ks[0], (1, t, rank_q)),
            jax.random.normal(ks[1], (1, t, rank_kv)),
            jax.random.normal(ks[2], (1, t, d_r)),
            0.2 * jax.random.normal(ks[3], (rank_q, heads * (d_n + d_r))),
            0.2 * jax.random.normal(ks[4], (rank_kv, heads * (d_n + d_v)))), ks[5]


@pytest.mark.parametrize("onepass", [None, False], ids=["onepass", "split"])
@pytest.mark.parametrize("t", [300, 384], ids=["ragged", "whole-blocks"])
def test_latent_attention_through_the_flash_kernels_equals_the_dense_form(
        monkeypatch, onepass, t):
    """Keys of 24 under values of 16 (each padded to its own lane tile,
    so the second product and the output run at the values' width),
    through both backward forms: the output and the gradient of every
    operand, the up-projections' kernels among them, equal the dense
    path's."""
    import importlib
    fa = importlib.import_module("split_learning_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "ONEPASS", onepass)
    fa._make_flash.cache_clear()
    operands, key = mla_operands(t, 4)
    make = lambda *ops: family._up_projection(sizes(rope_theta=1e4), *ops)
    q, k, v = make(*operands)
    assert q.shape == k.shape == (1, t, 4, 24) and v.shape == (1, t, 4, 16)
    w = jax.random.normal(key, v.shape)
    dense = lambda *ops: full_attention(*make(*ops), causal=True)
    flash = lambda *ops: flash_attention(*make(*ops), causal=True)
    f = lambda fn: jax.value_and_grad(
        lambda *ops: jnp.sum(fn(*ops) * w), argnums=(0, 1, 2, 3, 4))
    want, got = f(dense)(*operands), f(flash)(*operands)
    fa._make_flash.cache_clear()
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-4)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(a, b, atol=5e-5 * max(1.0, float(
            jnp.abs(b).max())), rtol=5e-5)


def test_flash_refuses_keys_of_another_width_than_the_queries():
    q = jnp.zeros((1, 64, 2, 24))
    with pytest.raises(ValueError, match="as wide as a query"):
        flash_attention(q, jnp.zeros((1, 64, 2, 16)), jnp.zeros((1, 64, 2, 16)))
    assert flash_attention(q, q, jnp.zeros((1, 64, 2, 16)),
                           causal=True).shape == (1, 64, 2, 16)


def test_interleaved_rotary_is_the_pairwise_rotation():
    """Lanes 2i and 2i+1 of position p turn by ``p * theta^(-2i/d)``: as
    complex numbers, a multiplication; position 0 is left as it is, norms
    are kept, and the reference's form agrees."""
    theta, d = 32e6, 8
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 3, d))
    got = np.asarray(family.rope_interleaved(x, theta), np.float64)
    z = np.asarray(x, np.float64).reshape(2, 5, 3, d // 2, 2)
    z = z[..., 0] + 1j * z[..., 1]
    ang = np.arange(5)[:, None] * theta ** (-np.arange(0, d, 2) / d)[None]
    want = z * np.exp(1j * ang)[None, :, None, :]
    want = np.stack([want.real, want.imag], -1).reshape(2, 5, 3, d)
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_array_equal(got[:, 0], np.asarray(x[:, 0], np.float64))
    np.testing.assert_allclose(np.linalg.norm(got, axis=-1),
                               np.linalg.norm(np.asarray(x), axis=-1), rtol=1e-5)
    one = np.asarray(reference.rope_pairs(x[0], theta))
    np.testing.assert_allclose(one, got[0], atol=1e-5)
    # it is not the rotate-half form the afmoe family takes
    from split_learning_tpu.models.afmoe import rope
    assert np.abs(np.asarray(rope(x, theta)) - got).max() > 0.1


def test_the_objective_is_main_ce_plus_lambda_times_the_modules():
    """One loss a token whose mean is ``mean CE(logits, t_{i+1}) + lambda
    * mean_{i < T-1} CE(logits', t_{i+2})``: the module's logits made by
    hand from the stage's own parts, through the main head's leaf; the
    last position has no second target; ``mtp_layers`` 0 is the main
    cross-entropy alone and carries no objective."""
    plan = get_plan("joyai_llm_flash", "split", jnp.float32, **KW)
    (x, y), = batches(1)
    params = seeded(plan, x)
    stage, p = plan.stages[1], params[1]["params"]
    h = plan.stages[0].apply(params[0], x)
    losses = stage.objective(params[1], h, y)
    assert losses.shape == (B, T) and losses.dtype == jnp.float32
    ce = optax.softmax_cross_entropy_with_integer_labels
    main = ce(stage.apply(params[1], h), y)
    eps = KW["rms_norm_eps"]
    norm = lambda scale, v: v * jax.lax.rsqrt(
        jnp.mean(v * v, -1, keepdims=True) + eps) * scale["scale"]
    trunk = TrunkStage(family._run_layers, (sizes(), 1, 4, 1))
    g = norm(p["norm_f"], trunk.apply(
        {"params": {k: v for k, v in p.items() if k.startswith("layer")}}, h))
    m = p["mtp"]
    u = jnp.concatenate([norm(m["norm_e"], m["tok"]["embedding"][y]),
                         norm(m["norm_h"], g)], -1) @ m["eh"]["kernel"]
    block = family.Layer(sizes(), False).apply({"params": m["block"]}, u)
    second = ce(norm(m["norm_s"], block) @ p["lm_head"], jnp.roll(y, -1, 1))
    want = main.mean() + KW["mtp_lambda"] * second[:, :-1].mean()
    assert float(losses.mean()) == pytest.approx(float(want), abs=2e-5)
    # the last position carries the main loss alone
    np.testing.assert_allclose(losses[:, -1], main[:, -1], atol=1e-6)
    assert float(jnp.abs(losses[:, :-1] - main[:, :-1]).min()) > 0.1
    assert float(final_loss(stage, params[1], h, y)) == pytest.approx(
        float(losses.mean()))
    np.testing.assert_array_equal(
        final_loss(stage, params[1], h, y, per_example_cross_entropy), losses)
    plain = get_plan("joyai_llm_flash", "split", jnp.float32,
                     **{**KW, "mtp_layers": 0})
    assert plain.stages[1].objective is None
    shapes = jax.eval_shape(plain.init, jax.random.PRNGKey(0), x)
    assert "mtp" not in shapes[1]["params"]


def test_paths_without_an_objective_refuse_the_stage_by_name():
    """The SPMD pipeline gathers logits first and split-party evaluation
    is handed logits by the server: both refuse a final stage that
    carries its own objective; a plain stage passes; ``remat_plan``
    keeps the objective."""
    plan = get_plan("joyai_llm_flash", "split", jnp.float32, **KW)
    with pytest.raises(ValueError, match="'trunk_head' carries its own "
                                         "objective.*the SPMD pipeline"):
        refuse_objective(plan, "the SPMD pipeline (PipelinedTrainer)")
    refuse_objective(get_plan("transformer_lm", "split"), "anything")
    from split_learning_tpu.parallel.pipeline import PipelinedTrainer
    from split_learning_tpu.parallel.mesh import make_mesh
    cfg = Config(mode="split", model="joyai_llm_flash", batch_size=4)
    with pytest.raises(ValueError, match="trunk_head"):
        PipelinedTrainer(plan, cfg, jax.random.PRNGKey(0),
                         np.zeros((4, T), np.int32),
                         make_mesh(num_clients=1, num_stages=2))
    from split_learning_tpu.runtime.evaluate import evaluate_remote
    (x, _), = batches(1)
    params = plan.init(jax.random.PRNGKey(0), x)
    with pytest.raises(ValueError, match="split-party evaluation"):
        evaluate_remote(plan, [params[0]], None, None)
    kept = remat_plan(plan)
    assert kept.stages[1].objective is not None
    (x, y), = batches(1)
    assert float(objective(kept, params, x, y)) == pytest.approx(
        float(objective(plan, params, x, y)), abs=1e-6)


@pytest.mark.parametrize("mode,stages", [("split", 2), ("u_split", 3),
                                         ("federated", 2)])
def test_every_mode_builds_and_none_decodes(mode, stages):
    plan = get_plan("joyai_llm_flash", mode, jnp.float32, **KW)
    assert plan.num_stages == stages
    assert plan.owners == ("client", "server", "client")[:stages]
    # the objective rides on the stage that holds head and module
    assert [s.objective is not None for s in plan.stages] == (
        [False] * (stages - 1) + [True])
    (x, y), = batches(1)
    params = plan.init(jax.random.PRNGKey(0), x)
    assert plan.apply(params, x).shape == (B, T, KW["vocab"])
    assert "mtp" in params[-1]["params"]
    assert np.isfinite(float(objective(plan, params, x, y)))
    with pytest.raises(NotImplementedError, match="latent cache"):
        plan.stages[0].apply(params[0], x, cache_len=T)
    with pytest.raises(NotImplementedError, match="latent cache"):
        plan.stages[1].apply(params[1], jnp.zeros((B, T, 64)), decode_cache={})


@pytest.mark.parametrize("change,match", [
    (dict(client_depth=6), "client_depth"),
    (dict(experts_held=4, expert_offset=6), "router's 8"),
    (dict(mtp_layers=2), "one prediction module"),
    (dict(qk_rope_head_dim=7), "even"),
    (dict(attn="ring"), "attn impl"),
])
def test_refused_plans(change, match):
    with pytest.raises(ValueError, match=match):
        get_plan("joyai_llm_flash", "split", **{**KW, **change})


def test_scopes_name_the_new_parts():
    assert {spans.ATTN_LATENT, spans.MLA_PROJ, spans.MTP} <= set(
        spans.DEVICE_SCOPES)
    plan = get_plan("joyai_llm_flash", "split", jnp.float32, **KW)
    (x, y), = batches(1)
    shapes = jax.eval_shape(plan.init, jax.random.PRNGKey(0), x)
    text = jax.jit(lambda p: objective(plan, p, x, y)).lower(shapes).as_text(
        debug_info=True)
    for scope in ("attn_latent", "mla_proj", "mtp", "moe_route", "moe_experts",
                  "moe_shared"):
        assert scope in text, scope
    # the module's block nests its own scopes under the module's
    assert "mtp/" in text and "mtp/block" in text.replace("mtp/mtp", "mtp")


@pytest.mark.parametrize("attn", ["full", "flash"])
def test_remat_changes_no_number(attn):
    """The routed part recomputed or kept, under the dense attention and
    under the flash kernels: the same objective and the same gradients
    (float32: the recomputed forward is the forward)."""
    (x, y), = batches(1)
    out = []
    for remat in (True, False):
        plan = get_plan("joyai_llm_flash", "split", jnp.float32,
                        **{**KW, "remat": remat, "attn": attn})
        params = seeded(plan, x)
        out.append(jax.jit(jax.value_and_grad(
            lambda p, plan=plan: objective(plan, p, x, y)))(params))
    (l1, g1), (l0, g0) = out
    assert float(l1) == pytest.approx(float(l0), abs=1e-6)
    for (name, a), b in zip(flat(g1).items(), flat(g0).values()):
        np.testing.assert_allclose(a, b, rtol=0,
                                   atol=1e-5 * max(np.abs(b).max(), 1e-3),
                                   err_msg=name)


# -- what this family's needs must not move ------------------------------- #

@pytest.mark.parametrize("kw,heads,kv_heads,d", [
    (dict(causal=True), 16, 16, 64),                     # GF, GP
    (dict(causal=True, window=2048), 32, 4, 128),        # TF, sliding
    (dict(causal=True), 32, 4, 128),                     # TF, full
    (dict(causal=True, window=512), 40, 20, 128),        # PF, window
    (dict(causal=True), 40, 20, 128),                    # PF, full and cross
], ids=["gpt2", "trinity-window", "trinity-full", "phi4-window", "phi4-full"])
@pytest.mark.parametrize("onepass", [None, False], ids=["onepass", "split"])
def test_equal_width_flash_calls_trace_as_without_a_value_width(
        monkeypatch, kw, heads, kv_heads, d, onepass):
    """A call whose values are as wide as its keys is the call every other
    family makes: the kernels' function is built without a value width,
    and its gradient traces to the same text as one built with the value
    width named (the builder compared that text with the parent commit's
    for these shapes, forward and both backward forms: equal)."""
    import importlib
    fa = importlib.import_module("split_learning_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "ONEPASS", onepass)
    fa._make_flash.cache_clear()
    t = 2048
    q = jnp.zeros((1, t, heads, d), jnp.bfloat16)
    k = jnp.zeros((1, t, kv_heads, d), jnp.bfloat16)
    built = []
    real = fa._make_flash

    def spy(*args, **kwargs):
        built.append(kwargs)
        return real(*args, **kwargs)

    monkeypatch.setattr(fa, "_make_flash", spy)
    grad = lambda: str(jax.make_jaxpr(jax.grad(
        lambda a, b, c: jnp.sum(flash_attention(a, b, c, **kw).astype(
            jnp.float32)), argnums=(0, 1, 2)))(q, k, k))
    plain = grad()
    assert built and all("d_v" not in kwargs for kwargs in built)
    monkeypatch.setattr(fa, "_make_flash", lambda *a, **kws: real(
        *a, **{**kws, "d_v": d}))
    assert grad() == plain
    real.cache_clear()


def _step_text(plan, cfg, x, y, loss):
    """The fused step's jaxpr with ``loss(params, x, y)`` as its loss."""
    from split_learning_tpu.runtime.state import (
        apply_grads, make_state, make_tx)
    tx = make_tx(cfg)
    shapes = jax.eval_shape(plan.init, jax.random.PRNGKey(0), x)
    state = jax.eval_shape(lambda p: make_state(tuple(p), tx), shapes)

    def step(state, x, y):
        value, grads = jax.value_and_grad(loss)(state.params, x, y)
        return apply_grads(tx, state, grads), value

    return str(jax.make_jaxpr(step)(state, x, y))


@pytest.mark.parametrize("model,kw,x,y", [
    ("transformer_lm", dict(d_model=64, num_heads=4, max_len=32, vocab=97),
     np.zeros((2, 32), np.int32), np.zeros((2, 32), np.int32)),
    ("vit", dict(d_model=64, num_heads=4, patch=16, num_classes=10,
                 max_tokens=4),
     np.zeros((2, 32, 32, 3), np.float32), np.zeros((2,), np.int32)),
], ids=["gpt2-family", "vit-family"])
def test_steps_of_stages_without_an_objective_trace_as_before(model, kw, x, y):
    """GF, GP, VF and VP's programs: a step whose loss goes through
    ``final_loss`` traces to the text of the step as it stood,
    ``loss_op(plan.apply(params, x), y)`` (fused), and the server's
    ``loss(stage.apply(params, acts), labels)`` likewise (party)."""
    plan = get_plan(model, "split", jnp.bfloat16, **kw)
    cfg = Config(mode="split", model=model, optimizer="adamw", lr=1e-4,
                 batch_size=2)
    assert all(s.objective is None for s in plan.stages)
    last = plan.num_stages - 1
    before = _step_text(plan, cfg, x, y,
                        lambda p, x, y: cross_entropy(plan.apply(p, x), y))
    after = _step_text(plan, cfg, x, y,
                       lambda p, x, y: plan_loss(plan, p, x, y))
    assert after == before
    # the server's step, scalar and per example
    stage = plan.stages[last]
    shapes = jax.eval_shape(plan.init, jax.random.PRNGKey(0), x)
    acts = jax.eval_shape(lambda p: plan.apply_range(p, x, 0, last), shapes)
    for op in (cross_entropy, per_example_cross_entropy):
        old = jax.make_jaxpr(jax.grad(lambda p, a: jnp.sum(op(
            stage.apply(p, a), y)), argnums=(0, 1)))(shapes[last], acts)
        new = jax.make_jaxpr(jax.grad(lambda p, a: jnp.sum(final_loss(
            stage, p, a, y, op)), argnums=(0, 1)))(shapes[last], acts)
        assert str(new) == str(old)
