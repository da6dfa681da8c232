"""The afmoe family (models/afmoe.py) against the benchmark's plain
reference (benchmarks/reference/afmoe.py), its routed layer's shares
against the whole, the grouped products against a loop, and the windowed
grouped-head flash kernels (interpret mode) against the dense banded
path. CPU, small sizes."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from split_learning_tpu.core.losses import cross_entropy
from split_learning_tpu.models import get_plan
from split_learning_tpu.models import afmoe
from split_learning_tpu.models.afmoe import AfmoeLayer, RoutedExperts
from split_learning_tpu.obs import spans
from split_learning_tpu.ops.flash_attention import (
    flash_attention, flash_attention_with_lse)
from split_learning_tpu.ops.grouped_matmul import (
    grouped_matmul, grouped_matmul_reference)
from split_learning_tpu.ops.ring_attention import full_attention
from split_learning_tpu.runtime import ServerRuntime, SplitClientTrainer
from split_learning_tpu.runtime.fused import FusedSplitTrainer
from split_learning_tpu.transport import LocalTransport
from split_learning_tpu.utils import Config

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
from reference import afmoe as reference      # noqa: E402
from reference import common as ref_common    # noqa: E402

# the rehearsal's sizes: 1 dense + 4 routed layers (3 window, 1 full),
# 8 experts of which 4 held from the third on, 2 a token, window 8 of T 16
KW = dict(vocab=300, d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
          dense_width=192, expert_width=32, experts_total=8, experts_held=4,
          expert_offset=2, experts_per_token=2, shared_experts=1,
          route_scale=2.826, window=8,
          layer_types=["sliding_attention"] * 4 + ["full_attention"],
          dense_layers=1, client_depth=1, attn="auto", remat=True)
B, T, LR = 2, 16, 1e-3


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


# float32 on the CPU: both sides are the same arithmetic in another order
# (sorted pairs and grouped products against a masked loop over experts;
# one-pass softmax against blocks), so a leaf's gradient agrees to 2e-4 of
# its largest entry. bfloat16 compute against the float32 reference: 8
# mantissa bits through five layers, and the odd token whose 2nd and 3rd
# router scores lie within that noise picks another expert: the loss
# within 0.05, a leaf's gradient norm within 8 % of the reference's or of
# the median leaf's.
@pytest.mark.parametrize("dtype,loss_tol,grad_tol", [
    ("float32", 2e-5, 2e-4), ("bfloat16", 0.05, 0.08)])
def test_loss_and_every_gradient_match_the_reference(dtype, loss_tol, grad_tol):
    plan = get_plan("afmoe", "split", jnp.dtype(dtype), **KW)
    (x, y), = batches(1)
    params = seeded(plan, x)
    want, want_g = jax.value_and_grad(
        reference.loss_fn({"plan": {"kwargs": KW}}, "f32"), argnums=(0, 1))(
            params[0], params[1], x, y)
    got, got_g = jax.jit(jax.value_and_grad(
        lambda p: cross_entropy(plan.apply(p, x), y)))(params)
    assert abs(float(got) - float(want)) <= loss_tol
    ref, prog = flat(want_g), flat(got_g)
    assert ref.keys() == prog.keys()
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
    # the selection bias takes no gradient on either side
    for name, g in prog.items():
        if name.endswith("['expert_bias']"):
            assert not g.any() and not ref[name].any()


def trained(make, steps):
    trainer = make()
    losses = [trainer.train_step(x, y) for x, y in steps]
    return trainer, losses


def test_three_adamw_steps_match_the_reference():
    """FusedSplitTrainer's first three steps against the reference's
    training loop from the same weights: each loss, and every leaf's
    change (float32: 1e-4 and 2 % of the change's norm; Adam's first steps
    are lr-sized whatever the gradient, so a changed leaf moves by
    about lr * sqrt(size))."""
    plan = get_plan("afmoe", "split", jnp.float32, **KW)
    steps = batches(3)
    cfg = Config(mode="split", model="afmoe", optimizer="adamw", lr=LR,
                 batch_size=B)
    start = seeded(plan, steps[0][0])

    class Seeded(type(plan)):
        def init(self, rng, sample):
            return jax.tree_util.tree_map(jnp.copy, start)

    plan = Seeded(stages=plan.stages, owners=plan.owners)
    trainer, losses = trained(lambda: FusedSplitTrainer(
        plan, cfg, jax.random.PRNGKey(0), steps[0][0]), steps)
    want = ref_common.train(
        reference.loss_fn({"plan": {"kwargs": KW}}, "f32"),
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
                assert norm == 0 and got[party][name] == 0
            else:
                assert got[party][name] == pytest.approx(norm, rel=0.02), name


def test_fused_step_equals_the_two_party_step():
    """One program for the whole split step against a SplitClientTrainer
    and a ServerRuntime of the same plan over the local wire."""
    plan = get_plan("afmoe", "split", jnp.float32, **KW)
    cfg = Config(mode="split", model="afmoe", optimizer="adamw", lr=LR,
                 batch_size=B)
    steps = batches(3)
    _, fused = trained(lambda: FusedSplitTrainer(
        plan, cfg, jax.random.PRNGKey(3), steps[0][0]), steps)
    server = ServerRuntime(plan, cfg, jax.random.PRNGKey(3), steps[0][0])
    client = SplitClientTrainer(plan, cfg, jax.random.PRNGKey(3),
                                LocalTransport(server))
    party = [client.train_step(x, y, i) for i, (x, y) in enumerate(steps)]
    np.testing.assert_allclose(fused, party, rtol=1e-5, atol=1e-6)


def test_u_split_and_no_decode_cache():
    plan = get_plan("afmoe", "u_split", jnp.float32, **KW)
    assert plan.owners == ("client", "server", "client")
    (x, _), = batches(1)
    params = plan.init(jax.random.PRNGKey(0), x)
    assert plan.apply(params, x).shape == (B, T, KW["vocab"])
    with pytest.raises(NotImplementedError, match="KV-cache"):
        plan.stages[0].apply(params[0], x, cache_len=T)
    with pytest.raises(ValueError, match="experts"):
        get_plan("afmoe", "split", **{**KW, "expert_offset": 6})


@pytest.mark.parametrize("mode", ["split", "u_split"])
def test_remat_changes_no_number(mode):
    """``remat`` decides what the backward keeps and what it makes again,
    never a value: float32 loss and every gradient leaf as without it."""
    (x, y), = batches(1)
    out = {}
    for remat in (True, False):
        plan = get_plan("afmoe", mode, jnp.float32, **{**KW, "remat": remat})
        params = seeded(plan, x)
        out[remat] = jax.jit(jax.value_and_grad(
            lambda p: cross_entropy(plan.apply(p, x), y)))(params)
    (loss, grads), (want, want_g) = out[True], out[False]
    assert abs(float(loss) - float(want)) <= 1e-6
    got, ref = flat(grads), flat(want_g)
    assert got.keys() == ref.keys() and len(got) > 40
    for name, g in ref.items():
        np.testing.assert_allclose(got[name], g, rtol=0, atol=1e-6,
                                   err_msg=name)


def _pallas_calls(jaxpr, found):
    """Every ``pallas_call`` equation of a jaxpr and of those inside it."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _pallas_calls(sub, found)
    return found


@pytest.mark.parametrize("remat", [True, False], ids=["remat", "kept"])
def test_remat_recomputes_the_routed_part_and_nothing_else(remat):
    """One expert layer with the flash kernel (interpreted): under
    ``remat`` the backward is handed no rows of tokens x experts per
    token (without it, the pair buffers are there: the test sees them;
    the sort's two index vectors of that length are kept either way, the
    sort is not made again), and either way the gradient holds the attention's forward
    kernel once (3 operands: q, k, v) beside its one-pass backward (6),
    and the grouped products' 3 forward calls a second time only under
    ``remat`` (3 + 6 backward, + 3 recomputed)."""
    n, k = 24, KW["experts_per_token"]
    fields = {f: KW[f] for f in (
        "num_heads", "num_kv_heads", "head_dim", "window", "expert_width",
        "experts_total", "experts_held", "expert_offset",
        "experts_per_token", "shared_experts", "route_scale")}
    layer = AfmoeLayer(**fields, layer_type="sliding_attention",
                       dense_width=0, attn="flash", remat=remat)
    h = jax.random.normal(jax.random.PRNGKey(0), (1, n, KW["d_model"]))
    params = layer.init(jax.random.PRNGKey(1), h)
    f = lambda p, x: jnp.sum(layer.apply(p, x) ** 2)
    _, vjp = jax.vjp(f, params, h)
    pair_rows = [a.shape for a in jax.tree_util.tree_leaves(vjp)
                 if a.ndim > 1 and a.shape[0] == n * k]
    assert (pair_rows == []) == remat, pair_rows
    calls = _pallas_calls(jax.make_jaxpr(jax.grad(f))(params, h).jaxpr, [])
    attn = [len(e.invars) for e in calls
            if spans.ATTN_WINDOW in str(e.source_info.name_stack)]
    assert sorted(attn) == [3, 6]
    assert len(calls) - len(attn) == (12 if remat else 9)


# 32 tokens, 2 of 16 experts a token, experts 5 and 6 held: 64 pairs, and
# the rungs are 16 rows (twice even routing's 8) and the worst case's 64
LADDER = dict(width=32, experts_total=16, experts_held=2, expert_offset=5,
              per_token=2, route_scale=2.826)
LADDER_N, LADDER_D = 32, 64


def routed_to(filled, dtype, gated=True):
    """Parameters and tokens of a ``LADDER`` layer whose router sends
    exactly ``filled`` pairs to the two held experts: its first 16 rows
    are ten times the identity, and every token's first 16 features are
    +1 at the two experts it shall pick and -1 elsewhere."""
    n, d, total = LADDER_N, LADDER_D, LADDER["experts_total"]
    rs = np.random.RandomState(filled)
    absent = [e for e in range(total) if e not in (5, 6)]
    both = max(filled - n, 0)     # tokens that pick both held experts
    picks = []
    for t in range(n):
        if t < both:
            picks.append((5, 6))
        elif t < filled - both:    # one held: two of three go to expert 5
            picks.append((5 if t % 3 else 6, absent[rs.randint(len(absent))]))
        else:
            picks.append(tuple(rs.choice(absent, 2, replace=False)))
    x = 0.3 * rs.randn(n, d).astype(np.float32)
    x[:, :total] = -1.0
    for t, pair in enumerate(picks):
        x[t, list(pair)] = 1.0 + 0.1 * rs.rand(2)
    layer = RoutedExperts(**LADDER, dtype=dtype, remat=True, gated=gated)
    x = jnp.asarray(x)
    params = layer.init(jax.random.PRNGKey(filled), x)["params"]
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(filled + 1), len(leaves))
    params = jax.tree_util.tree_unflatten(tree, [
        leaf + 0.05 * jax.random.normal(k, leaf.shape) for leaf, k in zip(leaves, keys)])
    router = 0.01 * np.asarray(params["router"])
    router[:total] += 10.0 * np.eye(total, dtype=np.float32)
    return {**params, "router": jnp.asarray(router),
            "expert_bias": jnp.zeros((total,))}, x


def plain_routed(params, x, dtype):
    """The layer with no sort taken away: every one of the ``n * k`` sorted
    rows through ``grouped_matmul_reference``, every pair gathered back."""
    k = LADDER["per_token"]
    chosen, weights = afmoe.route(x, params["router"], params["expert_bias"],
                                  k, LADDER["route_scale"])
    order, inverse, sizes = afmoe.held_pairs(chosen, 5, 2)
    rows = x.astype(dtype)[order // k]
    mm = lambda a, w: grouped_matmul_reference(a, params[w], sizes)
    act = jax.nn.silu(mm(rows, "gate")) * mm(rows, "up") if "gate" in params \
        else jnp.square(jax.nn.relu(mm(rows, "up")))
    out = mm(act, "down")
    per_pair = out[inverse].reshape(x.shape[0], k, -1).astype(jnp.float32)
    return jnp.einsum("nkd,nk->nd", per_pair, weights).astype(dtype), sizes


@functools.lru_cache(maxsize=None)
def _ladder_step(dtype, remat, gated=True):
    """One compile a form and type for all the routings below."""
    layer = RoutedExperts(**LADDER, dtype=dtype, remat=remat, gated=gated)
    c = jax.random.normal(jax.random.PRNGKey(9), (LADDER_N, LADDER_D))
    f = lambda p, x: (lambda y: (jnp.sum(y.astype(jnp.float32) * c), y))(
        layer.apply({"params": p}, x))
    return jax.jit(jax.value_and_grad(f, argnums=(0, 1), has_aux=True))


# rungs 16, 32 and 64: inside each, on each edge, one past each lower edge,
# nothing routed here, and every pair held (what the top rung is for); the
# form without a gate on each edge and one past it
FILLS = [(0, 16), (5, 16), (15, 16), (16, 16), (17, 32), (24, 32), (31, 32),
         (32, 32), (33, 64), (50, 64), (63, 64), (64, 64)]
UNGATED = [(16, 16), (17, 32), (24, 32), (32, 32), (33, 64)]


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-6), ("bfloat16", 1e-2)])
@pytest.mark.parametrize("filled,rung,gated", [
    *((f, r, True) for f, r in FILLS), *((f, r, False) for f, r in UNGATED)])
def test_every_rung_gives_the_top_rungs_numbers(filled, rung, gated, dtype, tol):
    """The rung that holds the pairs routed here against the kept form
    (``remat=False``: the top rung alone) and against the plain layer:
    output, loss and every gradient, with no pair's part missing."""
    dtype = jnp.dtype(dtype)
    rungs = afmoe.pair_rungs(LADDER_N * 2, 2, 16)
    assert rungs == (16, 32, 64)
    params, x = routed_to(filled, dtype, gated)
    want_y, sizes = plain_routed(params, x, dtype)
    assert int(sizes.sum()) == filled
    assert rungs[afmoe.rung_of(filled, rungs)] == rung
    assert rungs[int(afmoe.rung_of(sizes.sum(), rungs))] == rung
    run = lambda remat: _ladder_step(dtype, remat, gated)(params, x)
    (loss, y), grads = run(True)
    (top_loss, top_y), top_grads = run(False)
    scale = lambda a: max(float(np.abs(np.asarray(a, np.float32)).max()), 1e-6)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(top_y, np.float32),
                               rtol=0, atol=tol * scale(top_y))
    # the plain layer multiplies in another order: the file's float32 room
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(want_y, np.float32), rtol=0,
        atol=max(tol, 2e-5) * scale(want_y))
    assert abs(float(loss) - float(top_loss)) <= tol * max(abs(float(top_loss)), 1.0)
    got, ref = flat(grads), flat(top_grads)
    assert got.keys() == ref.keys() and len(got) == 5 + gated
    for name, g in ref.items():
        np.testing.assert_allclose(got[name], g, rtol=0, atol=tol * scale(g),
                                   err_msg=name)
    if filled:   # the held experts' weights do take a gradient
        assert np.abs(ref["[0]['up']"]).max() > 0


def _conds(jaxpr, found):
    """Every ``cond`` equation of a jaxpr and of those inside it, a Pallas
    kernel's own body apart (megablox branches inside its kernels)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        if eqn.primitive.name == "cond":
            found.append(eqn)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _conds(sub, found)
    return found


@pytest.mark.parametrize("held,remat,ladder", [
    (8, True, (48,)), (4, True, (48,)), (2, True, (32, 48)),
    (1, True, (16, 32, 48)), (1, False, (48,))],
    ids=["whole-layer", "half", "a-quarter", "an-eighth", "an-eighth-kept"])
def test_a_layer_whose_rows_are_all_filled_has_no_conditional(held, remat, ladder):
    """``experts_held == experts_total`` (and any share of a half or more:
    twice even routing is the worst case) is one rung, so the gradient
    traces to no ``cond``; a quarter is two rungs, an eighth three, and
    either two ``cond``s, the forward's and the backward's, of a branch a
    rung. Without ``remat`` the layer runs ``n * k`` rows whatever its
    share, and no ``cond``."""
    layer = RoutedExperts(width=32, experts_total=8, experts_held=held,
                          expert_offset=0, per_token=2, route_scale=2.826,
                          remat=remat)
    x = jax.random.normal(jax.random.PRNGKey(0), (24, 64))
    params = layer.init(jax.random.PRNGKey(1), x)
    f = lambda p, x: jnp.sum(layer.apply(p, x) ** 2)
    found = _conds(jax.make_jaxpr(jax.grad(f, argnums=(0, 1)))(params, x).jaxpr, [])
    if remat:
        assert afmoe.pair_rungs(48, held, 8) == ladder
    assert [len(e.params["branches"]) for e in found] == \
        [len(ladder)] * (2 if len(ladder) > 1 else 0)
    _, sown = layer.apply(params, x, mutable=[spans.STEP_COUNTERS])
    assert sown[spans.STEP_COUNTERS][spans.MOE_LADDER].tolist() == list(ladder)


# (pairs a step, experts held, experts in all) of the four routed cells
CELL_SHAPES = {"trinity-mini": (65536, 8, 128), "joyai-flash": (65536, 8, 256),
               "lfm2-moe": (32768, 8, 64), "nemotronh-moe": (49152, 8, 128)}
CELL_LADDERS = {"trinity-mini": (8192, 16384, 65536),
                "joyai-flash": (4096, 8192, 65536),
                "lfm2-moe": (8192, 16384, 32768),
                "nemotronh-moe": (6144, 12288, 49152)}


@pytest.mark.parametrize("cell", sorted(CELL_SHAPES))
@pytest.mark.parametrize("divisor,count", [(None, 3), (4, 2), (2, 1), (1, 1)],
                         ids=["cell", "quarter", "half", "all"])
def test_the_ladder_is_twice_even_twice_that_and_the_worst_case(cell, divisor, count):
    """One rule from the shapes the layer is given: three rungs at the
    cells' shares, two where a quarter of the experts is held (twice the
    lower rung is the worst case itself), one from a half on."""
    pairs, held, total = CELL_SHAPES[cell]
    held = total // divisor if divisor else held
    even = pairs * held // total
    rungs = afmoe.pair_rungs(pairs, held, total)
    assert rungs == tuple(sorted({min(2 * even, pairs), min(4 * even, pairs), pairs}))
    assert len(rungs) == count and all(rows % 512 == 0 for rows in rungs)
    if divisor is None:
        assert rungs == CELL_LADDERS[cell]


@pytest.mark.parametrize("cell", sorted(CELL_SHAPES))
@pytest.mark.parametrize("at,rung", [
    ("nothing", 0), ("low", 0), ("low+1", 1), ("middle", 1), ("middle+1", 2),
    ("top", 2)])
def test_rung_of_turns_at_each_threshold_and_one_past_it(cell, at, rung):
    rungs = CELL_LADDERS[cell]
    filled = {"nothing": 0, "low": rungs[0], "low+1": rungs[0] + 1,
              "middle": rungs[1], "middle+1": rungs[1] + 1,
              "top": rungs[2]}[at]
    assert afmoe.rung_of(filled, rungs) == rung
    assert int(afmoe.rung_of(jnp.int32(filled), rungs)) == rung   # on the device
    assert rungs[rung] >= filled and (rung == 0 or rungs[rung - 1] < filled)


@pytest.mark.parametrize("mode", ["split", "u_split"])
def test_no_conditional_returns_rows_of_pairs(mode):
    """The recomputation's form: each rung recomputes inside its own branch
    of the backward's ``cond``, so what a ``cond`` of the gradient returns
    is its result (the forward's) or the cotangents (the backward's), never
    ``n * k`` rows of a rung's buffers. A ``switch`` under one
    ``checkpoint`` would return the union of every rung's kept rows."""
    t = 24
    kw = {**KW, "experts_held": 1, "remat": True}
    plan = get_plan("afmoe", mode, jnp.float32, **kw)
    rs = np.random.RandomState(0)
    ids = rs.randint(0, KW["vocab"], (B, t + 1)).astype(np.int32)
    x, y = ids[:, :-1], ids[:, 1:]
    params = seeded(plan, x)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: cross_entropy(plan.apply(p, x), y)))(params).jaxpr
    found = _conds(jaxpr, [])
    pairs = B * t * KW["experts_per_token"]
    assert afmoe.pair_rungs(pairs, 1, 8) == (32, 64, pairs)
    assert len(found) == 2 * 4      # four routed layers, forward and backward
    for eqn in found:
        assert len(eqn.params["branches"]) == 3
        rows = [v.aval.shape for v in eqn.outvars
                if v.aval.shape and v.aval.shape[0] == pairs]
        assert rows == [], rows


def test_the_shares_add_up():
    """8 experts in 4 shares of 2: the routed parts that all the shares
    give, with the shared expert counted once, are the uncut layer."""
    kw = dict(num_heads=4, num_kv_heads=2, head_dim=16,
              layer_type="full_attention", window=8, dense_width=0,
              expert_width=32, experts_total=8, experts_per_token=2,
              shared_experts=1, route_scale=2.826)
    h = jax.random.normal(jax.random.PRNGKey(0), (B, T, 64), jnp.float32)
    whole = AfmoeLayer(**kw, experts_held=8, expert_offset=0)
    p = whole.init(jax.random.PRNGKey(1), h)["params"]
    p["experts"]["expert_bias"] = 0.1 * jax.random.normal(
        jax.random.PRNGKey(2), (8,))
    # norm_post_mlp is not linear: compare what goes into it
    p["norm_post_mlp"]["scale"] = jnp.ones((64,))
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
        part = inner(AfmoeLayer(**kw, experts_held=2, expert_offset=2 * share),
                     cut)
        np.testing.assert_array_equal(part["shared"]["__call__"][0], shared)
        parts = parts + part["experts"]["__call__"][0]
    np.testing.assert_allclose(parts, routed, atol=1e-5)
    # and the uncut reference gives the same layer
    kwr = dict(KW, experts_held=8, expert_offset=0, rms_norm_eps=1e-5,
               rope_theta=10000.0)
    mm = ref_common.matmul("f32")
    want = jax.vmap(lambda one: reference.layer(p, one, "full_attention",
                                                kwr, mm))(h)
    np.testing.assert_allclose(whole.apply({"params": p}, h), want, atol=2e-5)


@pytest.mark.parametrize("sizes", [
    [10, 0, 20, 5],      # an empty expert, a partly filled buffer
    [0, 35, 0, 0],       # every pair on one expert
    [12, 12, 12, 12],    # the buffer full
    [0, 0, 0, 0],        # nothing routed here
], ids=["empty-expert", "all-on-one", "buffer-full", "nothing"])
def test_grouped_products_match_a_loop(sizes):
    m, k, n = 48, 24, 40
    x = jax.random.normal(jax.random.PRNGKey(0), (m, k))
    w = jax.random.normal(jax.random.PRNGKey(1), (len(sizes), k, n))
    c = jax.random.normal(jax.random.PRNGKey(2), (m, n))
    gs = jnp.array(sizes, jnp.int32)
    f = lambda fn: (lambda x, w: jnp.sum(fn(x, w, gs) * c))
    want = jax.value_and_grad(f(grouped_matmul_reference), argnums=(0, 1))(x, w)
    got = jax.jit(jax.value_and_grad(f(grouped_matmul), argnums=(0, 1)))(x, w)
    out = grouped_matmul(x, w, gs)
    assert not np.asarray(out)[sum(sizes):].any()       # unfilled rows: zeros
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-5)
    for a, b in zip(got[1], want[1]):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def qkv(t, h, h_kv, b=2, d=16):
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    return (jax.random.normal(ks[0], (b, t, h, d)),
            jax.random.normal(ks[1], (b, t, h_kv, d)),
            jax.random.normal(ks[2], (b, t, h_kv, d)),
            jax.random.normal(ks[3], (b, t, h, d)))


# T 300 pads to three 128 blocks and is no multiple of the window; 640 is
# five blocks with a band of three; window 129 is one past a block edge
@pytest.mark.parametrize("onepass", [None, False], ids=["onepass", "split"])
@pytest.mark.parametrize("t,h,h_kv,window", [
    (72, 4, 2, 8), (300, 4, 1, 100), (640, 2, 2, 200), (384, 2, 1, 129),
    (640, 4, 2, None)])
def test_window_and_grouped_heads_match_the_dense_band(
        monkeypatch, onepass, t, h, h_kv, window):
    """Forward and gradients of the interpreted kernels, both backward
    forms, against ``full_attention``'s dense banded path."""
    import importlib
    fa = importlib.import_module("split_learning_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "ONEPASS", onepass)
    q, k, v, w = qkv(t, h, h_kv)
    f = lambda fn: (lambda a, b, c: jnp.sum(
        fn(a, b, c, causal=True, window=window) * w))
    want = jax.value_and_grad(f(full_attention), argnums=(0, 1, 2))(q, k, v)
    got = jax.jit(jax.value_and_grad(f(flash_attention),
                                     argnums=(0, 1, 2)))(q, k, v)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-4)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5)


# blocks of 128 in sub-tiles of 32 or 64, four query heads a key/value
# head: window 256 is Trinity's case (a multiple of the block: diagonal,
# one whole pair, a far edge cut above its diagonal), 200 cuts the last
# two pairs of the band, 512 at T 512 is clamped to the blocks there are
@pytest.mark.parametrize("onepass", [True, False], ids=["onepass", "split"])
@pytest.mark.parametrize("t,tile,window", [
    (512, 32, 256), (512, 64, 200), (640, 32, 129), (512, 32, 500)])
def test_cut_band_edges_match_the_dense_band(flash_tiled, onepass, t, tile,
                                             window):
    """Forward and all three gradients where the band's edge pairs run
    their live sub-tiles alone, over grouped heads, both backward forms."""
    q, k, v, w = qkv(t, 4, 1, b=1)
    f = lambda fn: jax.value_and_grad(
        lambda a, b, c: jnp.sum(fn(a, b, c) * w), argnums=(0, 1, 2))
    want = f(lambda a, b, c: full_attention(
        a, b, c, causal=True, window=window))(q, k, v)
    got = f(lambda a, b, c: flash_tiled(
        a, b, c, block=128, tile=tile, onepass=onepass, window=window))(
            q, k, v)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-4)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("onepass", [None, False], ids=["onepass", "split"])
@pytest.mark.parametrize("kw", [
    dict(causal=True), dict(causal=True, window=2048),
    dict(causal=True, window=512), dict(causal=True, strict=True),
    dict(causal=False)],
    ids=["causal", "window-2048", "window-512", "strict", "not-causal"])
def test_tiled_kernels_keep_their_calls_and_operands(monkeypatch, onepass,
                                                     kw):
    """What the trace readers tell the kernels apart by (``benchmarks/
    trace_reduce.py:short_name``): at blocks of 1024 and 512, where the
    cut pairs run in sub-tiles, an attention call's gradient holds the
    ``pallas_call``s it held before PR 31 with the operands they had
    (forward 3; one-pass backward 6; split backward 6 and 6), and a call
    with no causal mask traces to the letter as with one tile a block."""
    import importlib
    fa = importlib.import_module("split_learning_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "ONEPASS", onepass)
    q = jnp.zeros((1, 4096, 2, 16))
    kv = jnp.zeros((1, 4096, 1, 16))

    def traced(tile):
        monkeypatch.setattr(fa, "_TILE", tile)
        fa._make_flash.cache_clear()
        return jax.make_jaxpr(jax.grad(
            lambda a, b, c: jnp.sum(flash_attention_with_lse(
                a, b, c, **kw)[0]), argnums=(0, 1, 2)))(q, kv, kv)

    tiled, whole = traced(256), traced(4096)
    fa._make_flash.cache_clear()
    operands = lambda j: [len(e.invars) for e in _pallas_calls(j.jaxpr, [])]
    assert operands(tiled) == operands(whole) == (
        [3, 6] if onepass is None else [3, 6, 6])
    if kw["causal"]:
        assert len(str(tiled)) > len(str(whole))   # the cut pairs' bodies
    else:
        assert str(tiled) == str(whole)


def test_no_window_is_the_kernel_it_was():
    """``window=None`` with equal head counts traces the kernels the
    other families run: the same jaxpr as the call without the argument
    (PR 26 compared it, and its gradient's, with the parent commit's:
    equal text), bit-equal outputs, and a window that covers the
    sequence is no window."""
    q, k, v, _ = qkv(200, 2, 2)
    plain = jax.make_jaxpr(lambda a, b, c: flash_attention(a, b, c, True))
    named = jax.make_jaxpr(
        lambda a, b, c: flash_attention(a, b, c, causal=True, window=None))
    assert str(plain(q, k, v)) == str(named(q, k, v))
    base = flash_attention(q, k, v, causal=True)
    np.testing.assert_array_equal(
        base, flash_attention(q, k, v, causal=True, window=200))
    o, lse = flash_attention_with_lse(q, k, v, causal=True, window=None)
    np.testing.assert_array_equal(o, base)
    assert lse.shape == (2, 200, 2)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, window=8)
    with pytest.raises(ValueError, match="divide"):
        three = jnp.zeros((2, 200, 3, 16))   # 3 heads under 2
        flash_attention(q, three, three)
