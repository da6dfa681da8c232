"""The phi4flash family (models/phi4flash.py) against the benchmark's
plain reference (benchmarks/reference/phi4flash.py): the fused first
steps, the rule of layer kinds, what the reading layers hold and read,
differential attention against a dense two-softmax form, the two-party
path, and the plans that are refused. CPU, small sizes; the scan kernel
and (where forced) the flash kernels in interpret mode."""

import math
import os
import sys
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from split_learning_tpu.core.losses import cross_entropy
from split_learning_tpu.models import get_plan
from split_learning_tpu.models import phi4flash
from split_learning_tpu.obs import spans
from split_learning_tpu.runtime import ServerRuntime, SplitClientTrainer
from split_learning_tpu.runtime.fused import FusedSplitTrainer
from split_learning_tpu.transport import LocalTransport
from split_learning_tpu.utils import Config

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
from reference import phi4flash as reference   # noqa: E402
from reference import common as ref_common     # noqa: E402

# the rehearsal's sizes: published layers 15-19 of 32, one of each kind,
# 8 query heads over 4 key/value heads of 8, window 8 of T 16
KW = dict(vocab=300, d_model=64, num_heads=8, num_kv_heads=4, head_dim=8,
          mlp_width=128, window=8, d_state=4, d_conv=4, expand=2, dt_rank=4,
          layers_published=32, mb_per_layer=2, layers_kept=[15, 16, 17, 18, 19],
          client_depth=1, eps=1e-5, attn="auto", remat=True)
B, T, LR = 2, 16, 1e-3


def batches(n, seed=0):
    rs = np.random.RandomState(seed)
    ids = rs.randint(0, KW["vocab"], (n, B, T + 1)).astype(np.int32)
    return [(a[:, :-1], a[:, 1:]) for a in ids]


def seeded(plan, x, seed=1):
    """``plan.init``'s weights moved off their constants (norm scales
    around 1, biases, A_log, D and the lambda vectors around their
    initial values), in float32."""
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
# (the kernel's scan against a scan over tokens, one softmax call for both
# maps against two, whole arrays against blocks), so a leaf's gradient
# agrees to 2e-4 of its largest entry. bfloat16 products against the
# float32 reference: 8 mantissa bits through five layers; the loss within
# 0.05, a leaf's gradient norm within 8 % of the reference's or of the
# median leaf's (the measure benchmarks/check.py takes: the lambda
# vectors' gradients are a small difference of two nearly equal maps at
# these sizes, and mostly rounding).
@pytest.mark.parametrize("dtype,loss_tol,grad_tol", [
    ("float32", 2e-5, 2e-4), ("bfloat16", 0.05, 0.08)])
def test_loss_and_every_gradient_match_the_reference(dtype, loss_tol, grad_tol):
    plan = get_plan("phi4flash", "split", jnp.dtype(dtype), **KW)
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
            if name.endswith("['k_bias']"):
                # a softmax does not see a shift of its keys: rounding
                assert np.abs(g).max() < 1e-7 > np.abs(prog[name]).max()
                continue
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
    losses = [trainer.train_step(x, y) for x, y in steps]
    return trainer, losses


def test_three_adamw_steps_match_the_reference():
    """FusedSplitTrainer's first three steps against the reference's
    training loop from the same weights: each loss, and every leaf's
    change (float32: 1e-4 and 2 % of the change's norm). The keys' bias
    takes no gradient but rounding on either side (a softmax does not
    see a shift of its keys), and Adam scales that up: left out."""
    plan = get_plan("phi4flash", "split", jnp.float32, **KW)
    steps = batches(3)
    cfg = Config(mode="split", model="phi4flash", optimizer="adamw", lr=LR,
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
            if not name.endswith("k_bias"):
                assert got[party][name] == pytest.approx(norm, rel=0.02), name


def test_fused_step_equals_the_two_party_step():
    """One program for the whole split step against a SplitClientTrainer
    and a ServerRuntime of the same plan over the local wire: the memory
    and the key/value set never cross it."""
    plan = get_plan("phi4flash", "split", jnp.float32, **KW)
    cfg = Config(mode="split", model="phi4flash", optimizer="adamw", lr=LR,
                 batch_size=B)
    steps = batches(3)
    _, fused = trained(lambda: FusedSplitTrainer(
        plan, cfg, jax.random.PRNGKey(3), steps[0][0]), steps)
    server = ServerRuntime(plan, cfg, jax.random.PRNGKey(3), steps[0][0])
    client = SplitClientTrainer(plan, cfg, jax.random.PRNGKey(3),
                                LocalTransport(server))
    party = [client.train_step(x, y, i) for i, (x, y) in enumerate(steps)]
    np.testing.assert_allclose(fused, party, rtol=1e-5, atol=1e-6)


def test_layer_kinds_at_the_published_depth():
    kinds = [phi4flash.layer_kind(i, 32, 2) for i in range(32)]
    assert Counter(kinds) == {"mamba": 9, "window": 8, "full": 1, "gmu": 7,
                              "cross": 7}
    assert kinds[15:20] == ["window", "mamba", "full", "gmu", "cross"]
    assert set(kinds[:16:2]) == {"mamba"} and set(kinds[1:16:2]) == {"window"}
    assert set(kinds[18::2]) == {"gmu"} and set(kinds[19::2]) == {"cross"}
    # the reference and the FLOP count carry the rule themselves
    from flops import phi4flash as flops
    for rule in (reference.layer_kind, flops.layer_kind):
        assert [rule(i, 32, 2) for i in range(32)] == kinds
    assert [phi4flash.layer_kind(i, 8, 2) for i in range(8)] == [
        "mamba", "window", "mamba", "window", "mamba", "full", "gmu", "cross"]


@pytest.mark.parametrize("mode,stages", [("split", 2), ("u_split", 3),
                                         ("federated", 2)])
def test_every_mode_builds_and_none_decodes(mode, stages):
    plan = get_plan("phi4flash", mode, jnp.float32, **KW)
    assert plan.num_stages == stages
    assert plan.owners == ("client", "server", "client")[:stages]
    (x, _), = batches(1)
    params = plan.init(jax.random.PRNGKey(0), x)
    assert plan.apply(params, x).shape == (B, T, KW["vocab"])
    with pytest.raises(NotImplementedError, match="recurrent state"):
        plan.stages[0].apply(params[0], x, cache_len=T)
    with pytest.raises(NotImplementedError, match="recurrent state"):
        plan.stages[1].apply(params[1], jnp.zeros((B, T, 64)), decode_cache={})


@pytest.mark.parametrize("change,match", [
    # the GMU and the cross layer on the server, what they read on the client
    (dict(layers_kept=[16, 17, 18, 19], client_depth=2), "layer 18 .gmu. reads"),
    (dict(layers_kept=[15, 16, 17, 18, 19], client_depth=2), "layer 18 .gmu. reads"),
    (dict(layers_kept=[15, 16, 17, 19], client_depth=3), "layer 19 .cross. reads"),
    # kept without the layer that exports
    (dict(layers_kept=[15, 17, 18, 19]), "layer 18 .gmu. reads what layer 16"),
    (dict(layers_kept=[15, 16, 18, 19]), "layer 19 .cross. reads what layer 17"),
    (dict(layers_kept=[17, 16]), "rising"),
    (dict(layers_kept=[15, 32]), "published"),
    (dict(client_depth=6), "client_depth"),
    (dict(num_kv_heads=3), "pair up"),
    (dict(attn="ring"), "attn impl"),
])
def test_refused_plans(change, match):
    with pytest.raises(ValueError, match=match):
        get_plan("phi4flash", "split", **{**KW, **change})


def test_plans_without_a_reading_layer_build():
    # a first half alone, and the fit rule's second branch
    for change in (dict(layers_kept=[13, 14, 15, 16, 17], client_depth=2),
                   dict(layers_kept=[16, 17, 18, 19], client_depth=0)):
        plan = get_plan("phi4flash", "split", **{**KW, **change})
        (x, _), = batches(1)
        assert plan.apply(plan.init(jax.random.PRNGKey(0), x), x).shape == (
            B, T, KW["vocab"])


def test_reading_layers_hold_no_scan_key_or_value_and_read_the_exports():
    """A GMU holds two products and a cross layer queries, lambdas, norm
    and output: nothing of a scan, no key or value weights. Their outputs
    move when the exporting layers' own weights do."""
    plan = get_plan("phi4flash", "split", jnp.float32, **KW)
    (x, _), = batches(1)
    params = seeded(plan, x)
    server = params[1]["params"]
    assert set(server["layer18"]["gmu"]) == {"in_proj", "out_proj"}
    assert set(server["layer19"]["attn"]) == {
        "q", "out", "subln", "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"}
    assert set(server["layer17"]["attn"]) == {
        "qkv", "q_bias", "k_bias", "v_bias", "out", "subln",
        "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"}
    # every norm's scale is named so (benchmarks/weights.py draws them
    # around 1 by that name)
    names = flat(params)
    assert sum(k.endswith("['scale']") for k in names) == 5 * 2 + 3 + 1
    h = plan.stages[0].apply(params[0], x)

    def mixers(server_params):
        """The outputs of the GMU and of the cross layer's attention."""
        _, state = plan.stages[1].apply(
            {"params": server_params}, h, capture_intermediates=lambda m, _: (
                isinstance(m, (phi4flash.GatedMemoryUnit,
                               phi4flash.DiffAttention))), mutable=["intermediates"])
        got = state["intermediates"]
        return (got["layer18"]["gmu"]["__call__"][0],
                got["layer19"]["attn"]["__call__"][0][0])

    def bumped(path, at, by=0.5):
        tree = jax.tree_util.tree_map(jnp.copy, server)
        leaf = tree
        for key in path[:-1]:
            leaf = leaf[key]
        leaf[path[-1]] = leaf[path[-1]].at[at].add(by)
        return tree

    # with the exporting layers' own output projections at zero their
    # mixers add nothing to h, so what they made reaches the layers after
    # through the memory and the key/value set alone
    server = jax.tree_util.tree_map(jnp.copy, server)
    server["layer16"]["mamba"]["out_proj"]["kernel"] *= 0
    server["layer17"]["attn"]["out"] = jax.tree_util.tree_map(
        jnp.zeros_like, server["layer17"]["attn"]["out"])
    gmu0, cross0 = mixers(server)
    # A = -exp(A_log): at +3 the state forgets twenty times faster (at
    # these sizes the state is 1e-4 of the memory, the D skip the rest)
    gmu1, _ = mixers(bumped(("layer16", "mamba", "A_log"), (slice(None),), 3.0))
    assert float(jnp.abs(gmu1 - gmu0).max()) > 1e-6 * float(jnp.abs(gmu0).max())
    # columns 64-95 of W_qkv are layer 17's key weights
    gmu2, cross2 = mixers(bumped(("layer17", "attn", "qkv", "kernel"),
                                 (slice(None), slice(64, 96))))
    assert float(jnp.abs(cross2 - cross0).max()) > 1e-4 * float(
        jnp.abs(cross0).max())
    # and the GMU reads nothing of layer 17: the very same floats
    np.testing.assert_array_equal(gmu2, gmu0)


def dense_two_softmax(q, k, v, window, lam, start, scale, eps=1e-5):
    """The differential attention of the equations, dense, one sequence:
    q [T, H, D], k, v [T, Hk, D]."""
    t, h, d = q.shape
    g = (h // 2) // (k.shape[1] // 2)
    q1, q2, k1, k2 = q[:, 0::2], q[:, 1::2], k[:, 0::2], k[:, 1::2]
    vv = np.concatenate([v[:, 0::2], v[:, 1::2]], -1)
    behind = np.arange(t)[:, None] - np.arange(t)[None, :]
    ok = behind >= 0
    if window is not None:
        ok &= behind < window
    out = np.zeros((t, h // 2, 2 * d), np.float64)
    for n in range(h // 2):
        maps = []
        for qq, kk in ((q1, k1), (q2, k2)):
            s = np.where(ok, qq[:, n] @ kk[:, n // g].T / math.sqrt(d), -np.inf)
            p = np.exp(s - s.max(-1, keepdims=True))
            maps.append(p / p.sum(-1, keepdims=True) @ vv[:, n // g])
        o = maps[0] - lam * maps[1]
        out[:, n] = o / np.sqrt((o * o).mean(-1, keepdims=True) + eps) * scale
    return out * (1 - start)


@pytest.mark.parametrize("kind,impl", [("window", "full"), ("window", "flash"),
                                       ("full", "full"), ("full", "flash")])
def test_differential_attention_against_a_dense_two_softmax_form(kind, impl):
    """lambda, 1 - lambda_init and the window's edge: the layer's
    attention output before W_o, by the dense path and (one call for both
    maps, zero-padded queries and keys, the kernel's scale undone) by the
    flash kernels."""
    index = {"window": 15, "full": 17}[kind]
    sizes = phi4flash.Sizes(
        d_model=64, num_heads=8, num_kv_heads=4, head_dim=8, mlp_width=128,
        window=5, d_state=4, d_conv=4, d_inner=128, dt_rank=4,
        layers_published=32, mb_per_layer=2, eps=1e-5, attn=impl,
        dtype=jnp.float32, remat=False)
    layer = phi4flash.DiffAttention(sizes, kind, index)
    t = 12
    u = jax.random.normal(jax.random.PRNGKey(0), (1, t, 64))
    params = seeded(layer, u)
    p = params["params"]
    # the output projection as the identity shows the normed difference
    p["out"]["kernel"] = jnp.eye(64)
    p["out"]["bias"] = jnp.zeros(64)
    (got, (k, v)) = layer.apply(params, u)
    assert k.shape == (1, t, 4, 8) and v.shape == (1, t, 2, 16)
    qkv = np.asarray(u[0] @ p["qkv"]["kernel"], np.float64)
    q = (qkv[:, :64] + np.asarray(p["q_bias"])).reshape(t, 8, 8)
    kk = (qkv[:, 64:96] + np.asarray(p["k_bias"])).reshape(t, 4, 8)
    vv = (qkv[:, 96:] + np.asarray(p["v_bias"])).reshape(t, 4, 8)
    start = 0.8 - 0.6 * math.exp(-0.3 * index)
    assert phi4flash.lambda_init(index) == start
    lam = (math.exp(float(p["lambda_q1"] @ p["lambda_k1"]))
           - math.exp(float(p["lambda_q2"] @ p["lambda_k2"])) + start)
    want = dense_two_softmax(q, kk, vv, 5 if kind == "window" else None, lam,
                             start, np.asarray(p["subln"]["scale"]))
    np.testing.assert_allclose(got[0], want.reshape(t, 64), atol=2e-5)
    if kind == "window":
        # the edge: a key 5 behind is out, so the full form differs
        full = dense_two_softmax(q, kk, vv, None, lam, start,
                                 np.asarray(p["subln"]["scale"]))
        assert np.abs(full[5:] - want[5:]).max() > 1e-3
        np.testing.assert_allclose(full[:5], want[:5])


# blocks of 128 in sub-tiles of 32: the window is under the block, as the
# cell's 512 is under 1024, so every row block is a diagonal pair cut on
# both sides and a far pair of which a corner is live; two query heads a
# key/value head, queries and keys zero-padded to twice their width and
# the queries scaled by sqrt 2, as ``DiffAttention`` hands them over
@pytest.mark.parametrize("onepass", [True, False], ids=["onepass", "split"])
@pytest.mark.parametrize("t,window", [(384, 64), (300, 100)],
                         ids=["window-under-block", "ragged"])
def test_flash_kernels_cut_a_window_under_the_block(flash_tiled, onepass, t,
                                                    window):
    from split_learning_tpu.ops.ring_attention import full_attention
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    pad = lambda x: jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, 8)))
    q = pad(jax.random.normal(ks[0], (1, t, 4, 8))) * math.sqrt(2)
    k = pad(jax.random.normal(ks[1], (1, t, 2, 8)))
    v = jax.random.normal(ks[2], (1, t, 2, 16))
    w = jax.random.normal(ks[3], (1, t, 4, 16))
    f = lambda fn: jax.value_and_grad(
        lambda a, b, c: jnp.sum(fn(a, b, c) * w), argnums=(0, 1, 2))
    want = f(lambda a, b, c: full_attention(
        a, b, c, causal=True, window=window))(q, k, v)
    got = f(lambda a, b, c: flash_tiled(
        a, b, c, block=128, tile=32, onepass=onepass, window=window))(q, k, v)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-4)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5)


def test_scopes_name_the_new_parts():
    assert {spans.SSM_CONV, spans.SSM_SCAN, spans.GMU, spans.ATTN_CROSS} <= set(
        spans.DEVICE_SCOPES)
    plan = get_plan("phi4flash", "split", jnp.float32, **KW)
    (x, _), = batches(1)
    shapes = jax.eval_shape(plan.init, jax.random.PRNGKey(0), x)
    text = jax.jit(plan.apply).lower(shapes, x).as_text(debug_info=True)
    for scope in ("ssm_conv", "ssm_scan", "gmu", "attn_window", "attn_full",
                  "attn_cross"):
        assert scope in text, scope


def test_remat_changes_no_number():
    """Each layer's MLP under ``nn.remat`` that keeps its wide product, or
    under none: the same loss and the same gradients (float32: the kept
    product is the forward's own, and the recomputed passes around it
    are the forward's)."""
    (x, y), = batches(1)
    out = []
    for remat in (True, False):
        plan = get_plan("phi4flash", "split", jnp.float32,
                        **{**KW, "remat": remat})
        params = seeded(plan, x)
        out.append(jax.jit(jax.value_and_grad(
            lambda p, plan=plan: cross_entropy(plan.apply(p, x), y)))(params))
    (l1, g1), (l0, g0) = out
    assert float(l1) == pytest.approx(float(l0), abs=1e-6)
    for (name, a), b in zip(flat(g1).items(), flat(g0).values()):
        # the keys' bias takes rounding for a gradient: an absolute floor
        np.testing.assert_allclose(a, b, rtol=0,
                                   atol=1e-5 * max(np.abs(b).max(), 1e-3),
                                   err_msg=name)


def _equations(jaxpr, inside=()):
    """``(names of the equations it lies inside, equation)`` for every
    equation of a jaxpr and of those inside it."""
    for eqn in jaxpr.eqns:
        yield inside, eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub, inside + (eqn.primitive.name,))


@pytest.mark.parametrize("named,again", [(True, 0), (False, 5)],
                         ids=["product-kept", "name-lost"])
def test_remat_keeps_the_wide_product_and_recomputes_around_it(
        monkeypatch, named, again):
    """In the gradient's jaxpr the ``[B, T, 2 * mlp_width]`` product is
    there once a layer, in the forward, and the five recomputed bodies
    (one ``checkpoint`` equation a layer: LayerNorm and the gate) hold
    none. Without the product's name the policy keeps nothing and every
    body makes it again, as the whole-MLP wrap did: the count sees it."""
    if not named:
        monkeypatch.setattr(phi4flash, "checkpoint_name", lambda x, name: x)
    # 2 * 96 = 192: no other product of these sizes is that wide (the
    # Mamba's in_proj is 2 * 128)
    width = 96
    plan = get_plan("phi4flash", "split", jnp.float32,
                    **{**KW, "mlp_width": width})
    (x, y), = batches(1)
    shapes = jax.eval_shape(plan.init, jax.random.PRNGKey(0), x)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: cross_entropy(plan.apply(p, x), y)))(shapes).jaxpr
    bodies = [e for _, e in _equations(jaxpr)
              if e.primitive.name == "remat2"]    # jax.checkpoint's
    assert len(bodies) == len(KW["layers_kept"])
    wide = Counter(
        bool(inside) for inside, e in _equations(jaxpr)
        if e.primitive.name == "dot_general"
        and e.outvars[0].aval.shape == (B, T, 2 * width))
    assert wide[False] == len(KW["layers_kept"]) and wide[True] == again
    # what a body does make again: the LayerNorm's statistics and the gate
    for body in bodies:
        made = Counter(e.primitive.name
                       for _, e in _equations(body.params["jaxpr"]))
        assert made["rsqrt"] == 1 and made["logistic"] >= 1
