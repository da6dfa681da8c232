"""The nemotron_h family (models/nemotron_h.py) against the benchmark's
plain reference (benchmarks/reference/nemotron_h.py): the loss and every
gradient, the fused first steps, the two-party path, the shares of the
experts, the chunked recurrence against the recurrence itself (ops/ssd.py),
the gate before the grouped norm, where ``dt_bias`` and ``A_log`` start, the
ungated routed layer, sixteen query heads a key/value head through the flash
kernels, the grouped products at a width of 1856, the plans and what they
refuse, the scopes and the step's counters, and ``remat``. CPU, small sizes;
the flash kernels (where forced) and the grouped products in interpret
mode."""

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
from split_learning_tpu.models import nemotron_h as family
from split_learning_tpu.models.afmoe import (
    AfmoeAttention, RoutedExperts, pair_rungs)
from split_learning_tpu.obs import spans
from split_learning_tpu.ops import grouped_matmul as gm
from split_learning_tpu.ops.flash_attention import flash_attention
from split_learning_tpu.ops.ring_attention import full_attention
from split_learning_tpu.ops.ssd import (
    ssd_chunked, ssd_product_flops, ssd_reference)
from split_learning_tpu.runtime import ServerRuntime, SplitClientTrainer
from split_learning_tpu.runtime.fused import FusedSplitTrainer
from split_learning_tpu.transport import LocalTransport
from split_learning_tpu.utils import Config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (os.path.join(ROOT, "benchmarks"), os.path.join(ROOT, "scripts")):
    if path not in sys.path:
        sys.path.insert(0, path)
from reference import nemotron_h as reference        # noqa: E402
from reference import common as ref_common           # noqa: E402

# the rehearsal's sizes: two turns of the published pattern's letters, of
# which the first seven layers are built (MEMEM*E); 8 Mamba heads of 8 over
# 2 groups with a state of 16, chunks of 8 tokens; 4 query heads over 2
# key/value heads of 16; 4 of 8 experts held, 2 a token, a shared one
PATTERN = "MEMEM*EMEMEM*E"
KW = dict(vocab=300, d_model=64, pattern=PATTERN,
          layers_kept=(0, 1, 2, 3, 4, 5, 6), client_depth=1, mamba_heads=8,
          mamba_head_dim=8, ssm_state=16, ssm_groups=2, conv_taps=4, chunk=8,
          time_step_min=0.001, time_step_max=0.1, num_heads=4, num_kv_heads=2,
          head_dim=16, expert_width=32, shared_width=64, experts_total=8,
          experts_held=4, expert_offset=0, experts_per_token=2,
          route_scale=2.5, norm_eps=1e-5, attn="auto", remat=True)
B, T, LR = 2, 20, 1e-3           # 20 tokens: the last chunk of 8 is padded
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
    around 1, the selection bias, ``dt_bias``, ``A_log`` and ``D`` around
    0), in float32."""
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
    return Config(mode="split", model="nemotron_h", optimizer="adamw", lr=LR,
                  batch_size=B)


def ssd_operands(t=37, heads=8, head_dim=4, groups=2, state=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(ks[0], (B, t, heads, head_dim)),
            jax.nn.softplus(jax.random.normal(ks[1], (B, t, heads))),
            -jnp.exp(jax.random.normal(ks[2], (heads,))),
            jax.random.normal(ks[3], (B, t, groups, state)),
            jax.random.normal(ks[4], (B, t, groups, state)),
            jax.random.normal(ks[5], (heads,)))


# float32 on the CPU: both sides are the same arithmetic in another order
# (the chunked recurrence against one token a step; whole arrays against
# blocks of heads, queries and tokens; the routed part by sorted rows against
# a scan over experts), so a leaf's gradient agrees to 2e-4 of its largest
# entry. bfloat16 products against the float32 reference: 8 mantissa bits
# through seven layers; the loss within 0.05, a leaf's gradient norm within
# 8 % of the reference's or of the median leaf's (benchmarks/check.py's
# measure).
@pytest.mark.parametrize("dtype,loss_tol,grad_tol", [
    ("float32", 2e-5, 2e-4), ("bfloat16", 0.05, 0.08)])
def test_loss_and_every_gradient_match_the_reference(dtype, loss_tol, grad_tol):
    plan = get_plan("nemotron_h", "split", jnp.dtype(dtype), **KW)
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
    for leaf in ("[0]['params']['layer0']['mamba']['A_log']",
                 "[0]['params']['layer0']['mamba']['dt_bias']",
                 "[0]['params']['layer0']['mamba']['conv_bias']",
                 "[1]['params']['layer2']['mamba']['norm']['scale']",
                 "[1]['params']['layer2']['mamba']['D']",
                 "[1]['params']['layer1']['shared']['up']['kernel']",
                 "[1]['params']['layer3']['experts']['up']",
                 "[1]['params']['layer5']['attn']['k']['kernel']"):
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
    plan = get_plan("nemotron_h", "split", jnp.float32, **KW)
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
    plan = get_plan("nemotron_h", "split", jnp.float32, **KW)
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
    give, with the shared expert that every chip computes alike counted
    once, are the uncut layer's, and the uncut reference gives the same
    layer."""
    h = jax.random.normal(jax.random.PRNGKey(0), (B, T, 64), jnp.float32)
    whole = family.NemotronLayer(sizes(experts_held=8), 1)
    p = whole.init(jax.random.PRNGKey(1), h)["params"]
    assert set(p) == {"norm", "shared", "experts"}
    p["experts"]["expert_bias"] = 0.1 * jax.random.normal(
        jax.random.PRNGKey(2), (8,))
    part = lambda layer, params, name: layer.apply(
        {"params": params}, h, capture_intermediates=lambda m, _: m.name == (
            name))[1]["intermediates"][name]["__call__"][0]
    routed = 0.0
    for share in range(4):
        cut = {**p, "experts": {**p["experts"], **{
            n: p["experts"][n][2 * share:2 * share + 2] for n in ("up", "down")}}}
        layer = family.NemotronLayer(sizes(
            experts_held=2, expert_offset=2 * share), 1)
        routed = routed + part(layer, cut, "experts")
        # the shared expert is the same on every chip
        np.testing.assert_array_equal(part(layer, cut, "shared"),
                                      part(whole, p, "shared"))
    np.testing.assert_allclose(routed, part(whole, p, "experts"), atol=1e-5)
    got = whole.apply({"params": p}, h)
    np.testing.assert_allclose(
        h + part(whole, p, "shared") + routed.reshape(h.shape), got, atol=1e-5)
    kwr = dict(KW, experts_held=8, expert_offset=0)
    mm = ref_common.matmul("f32")
    want = jax.vmap(lambda one: reference.layer(
        p, one, kwr, mm, reference.rounded("f32")))(h)
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("chunk", [8, 16])
def test_the_chunked_form_is_the_recurrence(chunk):
    """``ssd_chunked`` against one token a step, forward and every operand's
    gradient, at a length that is no multiple of the chunk (37: the last
    chunk is padded with steps that leave the state as it is)."""
    ops = ssd_operands()
    w = jax.random.normal(jax.random.PRNGKey(9), ops[0].shape)
    f = lambda fn: jax.value_and_grad(
        lambda *o: jnp.sum(fn(*o) * w), argnums=tuple(range(6)))
    want = f(ssd_reference)(*ops)
    got = jax.jit(f(lambda *o: ssd_chunked(*o, chunk)))(*ops)
    assert got[0] == pytest.approx(float(want[0]), rel=1e-5, abs=1e-4)
    for name, a, b in zip("x dt a b c d_skip".split(), got[1], want[1]):
        np.testing.assert_allclose(
            a, b, rtol=0, atol=1e-5 * max(1.0, float(jnp.abs(b).max())),
            err_msg=name)
    assert jnp.asarray(ssd_chunked(*ops, chunk)).dtype == jnp.float32


def test_the_chunked_form_is_causal_and_a_head_reads_its_own_group():
    """A change at token 13 moves nothing before it, within a chunk and
    across chunks; head n of 8 over 2 groups reads group n // 4: a change
    of group 1's ``b`` or ``c`` moves heads 4-7 and no other."""
    ops = ssd_operands()
    x, dt, a, b, c, d = ops
    base = np.asarray(ssd_chunked(*ops, 8))
    for moved in ((x.at[:, 13].add(1.0), dt, a, b, c, d),
                  (x, dt.at[:, 13].add(0.5), a, b, c, d),
                  (x, dt, a, b.at[:, 13].add(1.0), c, d)):
        got = np.asarray(ssd_chunked(*moved, 8))
        changed = np.abs(got - base).max(axis=(0, 2, 3)) > 1e-6
        assert not changed[:13].any() and changed[13:16].all()
        assert changed[16:].any()          # the carried state took it on
    for which in (3, 4):
        moved = list(ops)
        moved[which] = ops[which].at[:, :, 1].multiply(1.5)
        got = np.asarray(ssd_chunked(*moved, 8))
        by_head = np.abs(got - base).max(axis=(0, 1, 3)) > 1e-6
        assert by_head.tolist() == [False] * 4 + [True] * 4
    # the published sizes' count of the four products, a token and layer
    assert ssd_product_flops(1, 64, 64, 8, 128, 128) == 3407872


def test_the_gate_comes_before_the_grouped_norm():
    """``norm(y * silu(z))`` over groups, not ``norm(y) * silu(z)``, and
    the statistics are each group's own: written out by hand."""
    y = jax.random.normal(jax.random.PRNGKey(0), (B, T, 64))
    z = jax.random.normal(jax.random.PRNGKey(1), (B, T, 64))
    norm = family.GatedGroupNorm(2, 1e-5, jnp.float32)
    scale = 1.0 + jnp.arange(64.0) / 64
    got = norm.apply({"params": {"scale": scale}}, y, z)

    def grouped(v):
        parts = np.asarray(v, np.float64).reshape(B, T, 2, 32)
        parts = parts / np.sqrt((parts ** 2).mean(-1, keepdims=True) + 1e-5)
        return parts.reshape(B, T, 64)

    silu = np.asarray(jax.nn.silu(z), np.float64)
    first = grouped(np.asarray(y, np.float64) * silu) * np.asarray(scale)
    other = grouped(y) * silu * np.asarray(scale)
    np.testing.assert_allclose(got, first, atol=1e-5)
    assert np.abs(first - other).max() > 0.1
    # one group's statistics would be another number too
    whole = family.GatedGroupNorm(1, 1e-5, jnp.float32).apply(
        {"params": {"scale": scale}}, y, z)
    assert float(jnp.abs(whole - got).max()) > 1e-3


def test_the_mamba_layer_written_out_token_by_token():
    """The mixer against its equations by hand in float64: the order of
    ``[z | xBC | dt]``, the four taps with their bias and silu, where the
    taps, ``dt_bias`` and ``A_log`` start, the recurrence, the skip, the
    gate and the grouped norm."""
    s = sizes()
    mixer = family.Mamba2Mixer(s)
    t = 11
    u = jax.random.normal(jax.random.PRNGKey(0), (1, t, 64))
    p = seeded(mixer, u)["params"]
    assert {k: v.shape for k, v in p.items() if not isinstance(v, dict)} == {
        "conv_kernel": (4, 128), "conv_bias": (128,), "dt_bias": (8,),
        "A_log": (8,), "D": (8,)}
    assert p["in_proj"]["kernel"].shape == (64, 64 + 128 + 8)
    assert p["norm"]["scale"].shape == (64,)
    got = np.asarray(mixer.apply({"params": p}, u), np.float64)[0]
    f64 = lambda v: np.asarray(v, np.float64)
    zxd = f64(u[0]) @ f64(p["in_proj"]["kernel"])
    z, xbc, dt = zxd[:, :64], zxd[:, 64:192], zxd[:, 192:]
    w = f64(p["conv_kernel"]) + f64(family.tap_starts(4, 128))
    bias = f64(p["conv_bias"])
    conv = np.stack([bias + sum(w[k] * xbc[i - 3 + k] for k in range(4)
                                if i - 3 + k >= 0) for i in range(t)])
    xbc = conv / (1 + np.exp(-conv))
    x, b, c = xbc[:, :64].reshape(t, 8, 8), xbc[:, 64:96], xbc[:, 96:]
    dt_start, a_start = (f64(v) for v in family.mamba_starts(8, 0.001, 0.1))
    dt = np.log1p(np.exp(dt + f64(p["dt_bias"]) + dt_start))
    a = -np.exp(f64(p["A_log"]) + a_start)
    state, y = np.zeros((8, 8, 16)), np.zeros((t, 8, 8))
    for i in range(t):
        for n in range(8):
            g = n // 4
            state[n] = np.exp(dt[i, n] * a[n]) * state[n] + np.outer(
                dt[i, n] * x[i, n], b[i, 16 * g:16 * g + 16])
            y[i, n] = state[n] @ c[i, 16 * g:16 * g + 16] + (
                1 + f64(p["D"])[n]) * x[i, n]
    gated = (y.reshape(t, 64) * z / (1 + np.exp(-z))).reshape(t, 2, 32)
    gated = gated / np.sqrt((gated ** 2).mean(-1, keepdims=True) + 1e-5)
    want = (gated.reshape(t, 64) * f64(p["norm"]["scale"])) @ f64(
        p["out_proj"]["kernel"])
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_where_the_time_steps_and_decays_start():
    """The published initialiser's ranges, by quantile in head order: a
    leaf of zeros is a model whose first steps are log-uniform in
    [time_step_min, time_step_max] and whose decay rates span [1, 16]."""
    dt_bias, a_log = family.mamba_starts(64, 0.001, 0.1)
    dt = np.asarray(jax.nn.softplus(dt_bias), np.float64)
    assert dt.min() > 0.001 and dt.max() < 0.1 and (np.diff(dt) > 0).all()
    np.testing.assert_allclose(np.diff(np.log(dt)), np.log(100.0) / 64, rtol=1e-3)
    rates = np.exp(np.asarray(a_log, np.float64))
    assert rates.min() > 1.0 and rates.max() < 16.0
    np.testing.assert_allclose(np.diff(rates), 15.0 / 64, rtol=1e-3)
    ours, theirs = family.mamba_starts(8, 0.001, 0.1), reference.starts(8, 0.001, 0.1)
    for a, b in zip(ours, theirs):
        np.testing.assert_allclose(a, b, rtol=1e-6)


def test_where_the_taps_start():
    """The framework's default for a depthwise convolution of four taps,
    uniform in +-1/2, by quantile: every tap's channels fill the range
    evenly, no tap of a channel follows from another, and the reference
    writes the same numbers to the last bit."""
    taps = np.asarray(family.tap_starts(4, 6144), np.float64)
    assert taps.shape == (4, 6144) and np.abs(taps).max() < 0.5
    np.testing.assert_allclose(taps.mean(1), 0.0, atol=2e-3)
    np.testing.assert_allclose(taps.std(1), 0.5 / np.sqrt(3.0), rtol=2e-3)
    apart = np.corrcoef(taps) - np.eye(4)
    assert np.abs(apart).max() < 0.02
    for k in range(4):              # each quarter of the range holds a quarter
        held = np.histogram(taps[k], bins=4, range=(-0.5, 0.5))[0]
        assert np.abs(held - 1536).max() <= 2
    for shape in ((4, 6144), (4, 128), (3, 10)):
        np.testing.assert_array_equal(family.tap_starts(*shape),
                                      reference.tap_starts(*shape))


def test_the_recurrence_carries_a_share_of_a_seeded_layer():
    """At the published widths and the benchmark's weights (every leaf N(0,
    0.02), a scale 1 + that), what the carried state adds to ``y`` is no
    rounding beside the skip's part: a sixth of it in the root mean square.
    (With the taps at N(0, 0.02) it was 0.06 %, and the benchmark's
    comparison could not see the chunked form.)"""
    s = sizes(mamba_heads=64, mamba_head_dim=64, ssm_state=128, ssm_groups=8,
              chunk=128, remat=False)
    u = jax.random.normal(jax.random.PRNGKey(0), (1, 1024, 2688))
    u = u * jax.lax.rsqrt(jnp.mean(u * u, -1, keepdims=True))
    mixer = family.Mamba2Mixer(s)
    shapes, tree = jax.tree_util.tree_flatten_with_path(
        jax.eval_shape(mixer.init, jax.random.PRNGKey(0), u))
    keys = jax.random.split(jax.random.PRNGKey(7), len(shapes))
    params = jax.tree_util.tree_unflatten(tree, [
        0.02 * jax.random.normal(k, leaf.shape) + (
            "scale" in jax.tree_util.keystr(path))
        for (path, leaf), k in zip(shapes, keys)])
    seen = {}

    def spy(x, dt, a, b, c, d_skip, chunk):
        seen["skip"] = d_skip[:, None] * x.astype(jnp.float32)
        seen["y"] = ssd_chunked(x, dt, a, b, c, d_skip, chunk)
        return seen["y"]

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(family, "ssd_chunked", spy)
        mixer.apply(params, u)
    rms = lambda v: float(jnp.sqrt(jnp.mean(jnp.square(v))))
    carried = rms(seen["y"] - seen["skip"]) / rms(seen["skip"])
    assert 0.1 < carried < 0.3, carried


def test_a_fault_planted_in_the_chunked_form_shows_in_the_comparison():
    """benchmarks/check.py's measure on the first gradient's norms, the
    program against the reference: sound it reads rounding; with the
    carried state dropped (scripts/limit_readings.py's ``ssd_no_carry``)
    or the recurrence's operands in fp8 (``ssd_fp8``) it reads a gap that
    a limit can stand under. The Mamba-2 layers' first product is widened
    to what 2688 inputs of N(0, 0.02) give where this model has 64, so
    that ``x``, ``B`` and ``C`` are the size they are in the cell."""
    import check
    import limit_readings
    plan = get_plan("nemotron_h", "split", jnp.float32, **KW)
    (x, y), = batches(1)
    params = jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf * (2688 / 64) ** 0.5 if "['mamba']['in_proj']"
        in jax.tree_util.keystr(path) else leaf, seeded(plan, x))
    norms = lambda grads: {"all": {k: float(np.linalg.norm(g))
                                   for k, g in flat(grads).items()}}
    want = norms(jax.grad(reference.loss_fn(CONFIG, "f32"), argnums=(0, 1))(
        params[0], params[1], x, y))

    def gap():
        grads = jax.grad(lambda p: plan_loss(plan, p, x, y))(params)
        return check.worst_leaf_gap(norms(tuple(grads)), want)[0]

    assert gap() < 1e-5
    for fault, least in (("ssd_no_carry", 0.05), ("ssd_fp8", 0.03)):
        with limit_readings.planted(fault):
            assert gap() > least, fault


def test_the_controls_round_what_the_recurrence_contracts():
    """``rounded``: nothing at float32; at a control's precision the tensor
    going forward and its gradient coming back, as ``common.matmul`` rounds
    a product's operands; the fp8 control's loss is not the float32 one's
    in a model whose products are all exact (every other leaf zero)."""
    v = jax.random.normal(jax.random.PRNGKey(0), (5, 7))
    w = jax.random.normal(jax.random.PRNGKey(1), (5, 7))
    assert reference.rounded("f32")(v) is v
    for precision in ("bf16", "fp8"):
        q = ref_common._rounder(precision)
        got, grad = jax.value_and_grad(
            lambda a: jnp.sum(reference.rounded(precision)(a) * w))(v)
        np.testing.assert_array_equal(grad, q(w))
        assert float(got) == pytest.approx(float(jnp.sum(q(v) * w)), rel=1e-6)
        assert float(jnp.abs(q(v) - v).max()) > 0


@pytest.mark.parametrize("remat", [False, True])
def test_the_ungated_routed_layer_is_a_loop_over_dense_experts(remat):
    """``sum_e w_e W_down,e relu(W_up,e m)^2`` over the experts held,
    written as a loop, forward and every gradient; no ``gate`` leaf; the
    gated form still holds three leaves and is the SwiGLU it was."""
    m = jax.random.normal(jax.random.PRNGKey(0), (24, 64))
    kinds = {}
    for gated in (False, True):
        layer = RoutedExperts(32, 8, 4, 2, 2, 2.5, jnp.float32, remat,
                              gated=gated)
        p = layer.init(jax.random.PRNGKey(1), m)["params"]
        p["expert_bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(2), (8,))
        kinds[gated] = set(p)

        def by_hand(p, m):
            scores = jax.nn.sigmoid(m @ p["router"])
            _, chosen = jax.lax.top_k(scores + p["expert_bias"], 2)
            picked = jnp.take_along_axis(scores, chosen, -1)
            w = picked / picked.sum(-1, keepdims=True) * 2.5
            out = 0.0
            for e in range(4):
                up = m @ p["up"][e]
                act = jax.nn.silu(m @ p["gate"][e]) * up if gated \
                    else jnp.square(jax.nn.relu(up))
                out = out + jnp.where(chosen == e + 2, w, 0.0).sum(-1)[
                    :, None] * (act @ p["down"][e])
            return out

        c = jax.random.normal(jax.random.PRNGKey(3), m.shape)
        f = lambda fn: jax.value_and_grad(
            lambda p, m: jnp.sum(fn(p, m) * c), argnums=(0, 1))
        want = f(by_hand)(p, m)
        got = jax.jit(f(lambda p, m: layer.apply({"params": p}, m)))(p, m)
        assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5, abs=1e-6)
        for (name, a), b in zip(flat(got[1]).items(), flat(want[1]).values()):
            np.testing.assert_allclose(
                a, b, rtol=0, atol=2e-5 * max(np.abs(b).max(), 1e-3),
                err_msg=name)
    assert kinds[False] == {"router", "expert_bias", "up", "down"}
    assert kinds[True] == kinds[False] | {"gate"}


def test_the_plain_attention_has_no_gate_no_norms_and_no_positions():
    """Four leaves; the dense form by hand; and with no positions a
    permutation of the earlier tokens leaves the last token's output as it
    was (a rotary or a table would move it)."""
    attn = AfmoeAttention(4, 2, 16, None, attn="full", dtype=jnp.float32,
                          plain=True)
    u = jax.random.normal(jax.random.PRNGKey(0), (B, T, 64))
    p = attn.init(jax.random.PRNGKey(1), u)["params"]
    assert set(p) == {"q", "k", "v", "out"}
    q = (u @ p["q"]["kernel"]).reshape(B, T, 4, 16)
    k = (u @ p["k"]["kernel"]).reshape(B, T, 2, 16)
    v = (u @ p["v"]["kernel"]).reshape(B, T, 2, 16)
    want = full_attention(q, k, v, causal=True).reshape(B, T, 64) @ p["out"]["kernel"]
    got = attn.apply({"params": p}, u)
    np.testing.assert_allclose(got, want, atol=1e-5)
    turned = jnp.concatenate([u[:, :T - 1][:, ::-1], u[:, T - 1:]], axis=1)
    np.testing.assert_allclose(attn.apply({"params": p}, turned)[:, -1],
                               got[:, -1], atol=1e-5)
    # the family's own form is what it was: seven leaves
    full = AfmoeAttention(4, 2, 16, None, attn="full", dtype=jnp.float32)
    assert set(full.init(jax.random.PRNGKey(1), u)["params"]) == {
        "q", "k", "v", "gate", "q_norm", "k_norm", "out"}


@pytest.mark.parametrize("onepass", [None, False], ids=["onepass", "split"])
def test_sixteen_query_heads_a_key_value_head_through_the_flash_kernels(
        monkeypatch, onepass):
    """32 query heads over 2 key/value heads of 128 (the published heads,
    ``group`` 16 where trinity-mini has 8), through both backward forms,
    at a ragged length: the output and the gradient of q, k and v equal
    the dense path's."""
    import importlib
    fa = importlib.import_module("split_learning_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "ONEPASS", onepass)
    fa._make_flash.cache_clear()
    t = 300
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (1, t, 32, 128))
    k = jax.random.normal(ks[1], (1, t, 2, 128))
    v = jax.random.normal(ks[2], (1, t, 2, 128))
    w = jax.random.normal(ks[3], q.shape)
    f = lambda fn: jax.value_and_grad(
        lambda *ops: jnp.sum(fn(*ops, causal=True) * w), argnums=(0, 1, 2))
    want, got = f(full_attention)(q, k, v), f(flash_attention)(q, k, v)
    fa._make_flash.cache_clear()
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-3)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(a, b, atol=5e-5 * max(1.0, float(
            jnp.abs(b).max())), rtol=5e-5)


@pytest.mark.parametrize("wide", ["n", "k"])
def test_the_grouped_products_at_a_width_of_1856(wide):
    """1856 = 64 x 29 has no divisor that is a multiple of 128 and is over
    the cap: the products run megablox's irregular last tile of 1024 over
    an array dimension that is no multiple of a lane tile, as the columns
    (an expert's up) and as the contraction (its down). Forward, the rows'
    gradient and the weights' gradient against the plain loop."""
    k, n = (256, 1856) if wide == "n" else (1856, 256)
    sizes_, m = [9, 0, 17], 40
    ks = jax.random.split(jax.random.PRNGKey(1856), 3)
    x = jax.random.normal(ks[0], (m, k)) / k ** 0.5
    w = jax.random.normal(ks[1], (len(sizes_), k, n))
    c = jax.random.normal(ks[2], (m, n)) / n ** 0.5
    gs = jnp.array(sizes_, jnp.int32)
    f = lambda fn: (lambda x, w: jnp.sum(fn(x, w, gs) * c))
    want = jax.value_and_grad(f(gm.grouped_matmul_reference), argnums=(0, 1))(x, w)
    got = jax.jit(jax.value_and_grad(f(gm.grouped_matmul), argnums=(0, 1)))(x, w)
    assert not np.asarray(gm.grouped_matmul(x, w, gs))[sum(sizes_):].any()
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-4, abs=1e-4)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(a, b, rtol=0, atol=2e-5 * max(
            1.0, float(jnp.abs(b).max())))


def test_the_tiles_of_the_published_expert():
    """2688 = 21 x 128 gets 896, its largest divisor of that kind under the
    cap; 1856 the irregular 1024: 1856 of 2048 columns run are useful."""
    assert gm._tiles(6144, 2688, 1856) == (512, 896, 1024)
    assert gm._tiles(6144, 1856, 2688) == (512, 1024, 896)
    assert gm.tile_fill(6144, 2688, 1856) == pytest.approx(1856 / 2048)
    assert round(gm.tile_fill(49152, 2688, 1856), 3) == 0.906
    # 8192 tokens x 6 a token, 8 of 128 held: twice the even 3072, twice
    # that, and all
    assert pair_rungs(49152, 8, 128) == (6144, 12288, 49152)
    assert gm._tiles(12288, 2688, 1856) == gm._tiles(6144, 2688, 1856)


@pytest.mark.parametrize("mode,stages", [("split", 2), ("u_split", 3),
                                         ("federated", 2)])
def test_every_mode_builds_and_none_decodes(mode, stages):
    plan = get_plan("nemotron_h", mode, jnp.float32, **KW)
    assert plan.num_stages == stages
    assert plan.owners == ("client", "server", "client")[:stages]
    assert all(s.objective is None for s in plan.stages)
    (x, y), = batches(1)
    params = plan.init(jax.random.PRNGKey(0), x)
    assert plan.apply(params, x).shape == (B, T, KW["vocab"])
    # the client holds the embedding and published layer 0, a Mamba-2 layer
    assert set(params[0]["params"]) == {"tok", "layer0"}
    assert set(params[0]["params"]["layer0"]) == {"norm", "mamba"}
    server = params[1]["params"]
    assert {k for k in server if k.startswith("layer")} == {
        f"layer{i}" for i in range(1, 7)}
    for i, letter in enumerate(PATTERN[:7]):
        held = set((params[0] if i == 0 else params[1])["params"][f"layer{i}"])
        assert held == {"M": {"norm", "mamba"}, "*": {"norm", "attn"},
                        "E": {"norm", "shared", "experts"}}[letter], i
    assert set(server["layer5"]["attn"]) == {"q", "k", "v", "out"}
    assert np.isfinite(float(plan_loss(plan, params, x, y)))
    with pytest.raises(NotImplementedError, match="KV-cache"):
        plan.stages[0].apply(params[0], x, cache_len=T)
    with pytest.raises(NotImplementedError, match="state-space layer its state"):
        plan.stages[1].apply(params[1], jnp.zeros((B, T, 64)), decode_cache={})


@pytest.mark.parametrize("change,match", [
    (dict(layers_kept=(0, 1, 2, 3, 4, 6)), r"keep no \['\*'\] layer"),
    (dict(layers_kept=(1, 5)), r"keep no \['M'\] layer"),
    (dict(layers_kept=(0, 2, 4, 5)), r"keep no \['E'\] layer"),
    (dict(layers_kept=(0, 1, 1, 5)), "distinct rising"),
    (dict(layers_kept=(0, 1, 5, 14)), "14 published layers"),
    (dict(pattern="MEM-E*"), "Unknown layer letters"),
    (dict(client_depth=8), "client_depth"),
    (dict(experts_held=4, expert_offset=6), "router's 8"),
    (dict(num_kv_heads=3), "do not divide"),
    (dict(ssm_groups=3), "groups do not divide"),
    (dict(time_step_min=0.2), "time steps"),
    (dict(attn="ring"), "attn impl"),
])
def test_refused_plans(change, match):
    with pytest.raises(ValueError, match=match):
        get_plan("nemotron_h", "split", **{**KW, **change})


def test_the_scopes_name_the_new_parts_and_the_step_counts_three_layers():
    assert spans.SSM_SSD in spans.DEVICE_SCOPES
    plan = get_plan("nemotron_h", "split", jnp.float32, **KW)
    (x, y), = batches(1)
    shapes = jax.eval_shape(plan.init, jax.random.PRNGKey(0), x)
    text = jax.jit(lambda p: plan_loss(plan, p, x, y)).lower(shapes).as_text(
        debug_info=True)
    for scope in ("ssm_ssd", "ssm_conv", "attn_full", "moe_route",
                  "moe_experts", "moe_shared"):
        assert scope in text, scope
    assert "attn_window" not in text and "ssm_scan" not in text
    # a Mamba-2 layer's two products lie outside its scopes
    assert "ssm_ssd/in_proj" not in text and "ssm_conv/in_proj" not in text
    trainer = FusedSplitTrainer(plan, config(), jax.random.PRNGKey(0), x)
    tr = obs.enable()
    try:
        trainer.train_step(x, y)
    finally:
        obs.disable()
    read, = [r["attrs"] for r in tr.spans() if r["name"] == spans.COUNTERS_READ]
    assert read["layers"] == [f"trunk_head/layer{i}/experts" for i in (1, 3, 6)]
    # 2 x 20 tokens x 2 a token = 80 pairs; 4 of 8 experts held: one rung
    assert read["ladder"] == [[80]] * 3 and read["rows"] == [80] * 3
    assert all(len(p) == 4 and 0 < sum(p) <= 80 for p in read["pairs"])
    # at sizes that fill the recurrence's tiles (ops/ssd.py: chunks and
    # states of 128, 4 heads of 64 over 2 groups) the step holds its two
    # kernels once a Mamba-2 layer, under their scope, and at the
    # rehearsal's sizes above none
    assert "ssd_fwd" not in text and "ssd_bwd" not in text
    wide = get_plan("nemotron_h", "split", jnp.float32, **{
        **KW, "mamba_heads": 4, "mamba_head_dim": 64, "ssm_state": 128,
        "chunk": 128})
    tokens = jnp.zeros((1, 300), jnp.int32)
    text = str(jax.make_jaxpr(jax.grad(
        lambda p: plan_loss(wide, p, tokens, tokens)))(
            jax.eval_shape(wide.init, jax.random.PRNGKey(0), tokens)))
    assert text.count("name=ssd_fwd") == text.count("name=ssd_bwd") == 3


@pytest.mark.parametrize("attn", ["full", "flash"])
def test_remat_changes_no_number(attn):
    """The routed part, the chunked form's intra-chunk arrays and a
    Mamba-2 layer's elementwise passes recomputed or kept, under the dense
    attention and under the flash kernels: the same loss and the same
    gradients (float32: the recomputed forward is the forward)."""
    (x, y), = batches(1)
    out = []
    for remat in (True, False):
        plan = get_plan("nemotron_h", "split", jnp.float32,
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
