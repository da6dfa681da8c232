"""ops/causal_conv.py's kernels in interpret mode, at a shape of three token
tiles and two channel tiles (``[2, 3072, 1024]``: the carried rows and the
tap sums cross two edges a channel tile), against the plain form, and what
the module header says of both: causal, zeros before a sequence, a sequence
alone in its batch, the choice by shape, residuals without a float32 ``[T,
C]`` array, one body for a step's three layers, and a body kept by
``ops/common.py:traced_once`` that follows the grid it runs under."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from split_learning_tpu.ops import causal_conv
from split_learning_tpu.ops.causal_conv import TOKENS, conv_silu

T, C = 3 * TOKENS, 1024
NAMES = ("x", "taps", "bias")


def operands(dtype=jnp.float32, batch=2, t=T, c=C, taps=4, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (batch, t, c)).astype(dtype),
            jax.random.uniform(ks[1], (taps, c), minval=-0.5, maxval=0.5),
            0.1 * jax.random.normal(ks[2], (c,)))


@pytest.fixture
def form(request, monkeypatch):
    """``conv_silu`` through the kernels or, with the shapes' test answering
    no, through the plain form."""
    if request.param == "plain":
        monkeypatch.setattr(causal_conv, "fills_tiles", lambda *a: False)
    return conv_silu


def both_forms(fn):
    return pytest.mark.parametrize("form", ["kernels", "plain"],
                                   indirect=True)(fn)


def output_and_gradients(fn, ops, dtype):
    w = jax.random.normal(jax.random.PRNGKey(9), ops[0].shape)
    (_, y), grads = jax.jit(jax.value_and_grad(
        lambda *o: (lambda y: (jnp.sum(y.astype(jnp.float32) * w), y))(
            fn(*o, dtype)), argnums=(0, 1, 2), has_aux=True))(*ops)
    return {"y": y, **dict(zip(NAMES, grads))}


@pytest.mark.parametrize("x_dtype,y_dtype,taps", [
    (jnp.float32, jnp.float32, 4),      # a CPU test's types
    (jnp.bfloat16, jnp.bfloat16, 4),    # nemotron_h's on the chip
    (jnp.bfloat16, jnp.float32, 4),     # phi4flash's
    (jnp.bfloat16, jnp.bfloat16, 3),
])
def test_the_kernels_are_the_plain_form(monkeypatch, x_dtype, y_dtype, taps):
    """Forward to the last bit: the same float32 products and sums in the
    same order, one rounding. The gradients within float32 rounding: ``dx``
    to 1e-6 of its largest entry before its one rounding to ``x``'s type
    (in bfloat16 a rounding may fall the other way: 2^-7 of the entry), the
    taps' and the bias's sums of 6144 terms to 2e-6 of the largest."""
    ops = operands(x_dtype, taps=taps)
    got = output_and_gradients(conv_silu, ops, y_dtype)
    monkeypatch.setattr(causal_conv, "fills_tiles", lambda *a: False)
    want = output_and_gradients(conv_silu, ops, y_dtype)
    assert got["y"].dtype == y_dtype and got["y"].shape == ops[0].shape
    np.testing.assert_array_equal(np.asarray(got["y"], np.float32),
                                  np.asarray(want["y"], np.float32))
    for name in NAMES:
        u, v = got[name], want[name]
        assert u.shape == v.shape and u.dtype == v.dtype, name
        u, v = np.asarray(u, np.float32), np.asarray(v, np.float32)
        one_rounding = 2.0 ** -7 * np.abs(v) * (
            name == "x" and x_dtype == jnp.bfloat16)
        np.testing.assert_array_less(
            np.abs(u - v), 2e-6 * np.abs(v).max() + one_rounding + 1e-30,
            err_msg=name)


# each order under taps of its own, so that neither finds a body the other
# (or another test of this process) has traced at these types
@pytest.mark.parametrize("tiles,taps", [((3, 8), 5), ((8, 3), 6)],
                         ids=["three-then-eight", "eight-then-three"])
def test_the_kernels_follow_the_number_of_token_tiles(monkeypatch, tiles,
                                                      taps):
    """One process, two sequence lengths of the same blocks: the backward's
    body reads the grid's last step (``pl.num_programs``), which is a
    constant of the trace, so a body kept from the first length would zero
    the rows before the wrong tile of the second (PR 50)."""
    for n in tiles:
        ops = operands(batch=1, t=n * TOKENS, c=128, taps=taps, seed=n)
        with monkeypatch.context() as m:
            got = output_and_gradients(conv_silu, ops, jnp.float32)
            m.setattr(causal_conv, "fills_tiles", lambda *a: False)
            want = output_and_gradients(conv_silu, ops, jnp.float32)
        np.testing.assert_array_equal(got["y"], want["y"])
        for name in NAMES:
            np.testing.assert_allclose(
                got[name], want[name], rtol=0,
                atol=2e-6 * np.abs(want[name]).max(), err_msg=f"{name} {n}")


@pytest.mark.parametrize("wrappers", ["one", "one-a-grid"])
def test_a_kept_body_follows_the_grid(wrappers):
    """``traced_once`` keeps a body's jaxpr by the grid as well as by the
    refs' types: the same body under grids of three and of five steps, with
    blocks of one type, through one wrapper or one a grid (jax's own cache
    of traces knows the function and the types alone)."""
    from split_learning_tpu.ops.common import traced_once

    def body(o_ref):
        o_ref[...] = jnp.full(o_ref.shape, pl.num_programs(0), jnp.int32)

    kept = traced_once(body)
    for steps in (3, 5, 3):
        run = kept if wrappers == "one" else traced_once(body)
        out = pl.pallas_call(
            run, grid=(steps,), interpret=True,
            out_shape=jax.ShapeDtypeStruct((8 * steps, 128), jnp.int32),
            out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)))()
        np.testing.assert_array_equal(out, steps)


def test_the_plain_form_is_the_shifted_sum_in_float64():
    """``y_t = silu(bias + sum_k taps[k] x_{t - 3 + k})`` with zeros before
    the sequence, by its definition, at a shape the kernels leave alone."""
    x, taps, bias = operands(batch=1, t=20, c=24)
    assert not causal_conv.fills_tiles(x, taps)
    xs, w, b = (np.asarray(v, np.float64) for v in (x[0], taps, bias))
    past = np.concatenate([np.zeros((3, 24)), xs])
    pre = b + sum(w[k] * past[k:k + 20] for k in range(4))
    np.testing.assert_allclose(conv_silu(x, taps, bias, jnp.float32)[0],
                               pre / (1 + np.exp(-pre)), rtol=0, atol=2e-6)


@both_forms
def test_the_form_is_causal_and_a_sequence_starts_from_zeros(form):
    """A change at token ``t`` moves tokens ``t .. t + 3`` of its own
    sequence and nothing else: within a strip, over a strip's edge (127 ->
    128) and over a tile's (1023 -> 1024); the first tokens of a sequence see
    zeros and the second sequence not the first's tail."""
    x, taps, bias = operands()
    base = np.asarray(form(x, taps, bias, jnp.float32))
    for at in (5, 127, TOKENS - 1, 2 * TOKENS - 2, T - 1):
        moved = np.abs(np.asarray(form(x.at[0, at].add(1.0), taps, bias,
                                       jnp.float32)) - base).max(axis=2) > 1e-6
        assert moved[0, at:at + 4].all() and moved.sum() == min(4, T - at)
    # sequence 1 alone in a batch of one is sequence 1 of the batch of two
    np.testing.assert_array_equal(form(x[1:], taps, bias, jnp.float32)[0],
                                  base[1])
    # and zeros stand before it: token 0 is silu(bias + taps[3] x_0)
    pre = bias + taps[3] * x[1, 0]
    np.testing.assert_allclose(base[1, 0], pre * jax.nn.sigmoid(pre),
                               rtol=0, atol=1e-6)


def test_the_gradient_of_x_reaches_back_over_the_edges():
    """The cotangent at token ``t`` reaches ``x_{t - 3 .. t}`` alone: over a
    strip's edge and a tile's, in the backward's own order (last tile
    first)."""
    x, taps, bias = operands(batch=1)
    for at in (2, 128, TOKENS, TOKENS + 1, T - 1):
        dx = jax.grad(lambda v: conv_silu(v, taps, bias, jnp.float32
                                          )[0, at].sum())(x)
        reached = np.abs(np.asarray(dx)).max(axis=(0, 2)) > 0
        assert reached[max(at - 3, 0):at + 1].all()
        assert reached.sum() == min(4, at + 1)


def step_text(shape, taps, dtype=jnp.bfloat16):
    s = jax.ShapeDtypeStruct
    return str(jax.make_jaxpr(jax.grad(
        lambda *o: jnp.sum(conv_silu(*o, jnp.float32)), argnums=(0, 1, 2)))(
            s(shape, dtype), s((taps, shape[-1]), jnp.float32),
            s(shape[-1:], jnp.float32)))


@pytest.mark.parametrize("shape,taps,dtype,kernels", [
    ((1, 8192, 6144), 4, jnp.bfloat16, True),     # nemotron_h's layer
    ((1, 8192, 5120), 4, jnp.bfloat16, True),     # phi4flash's
    ((2, T, C), 3, jnp.float32, True),            # this file's
    ((1, 8192, 6144), 9, jnp.bfloat16, False),    # more taps than sublanes
    ((1, 8192, 6100), 4, jnp.bfloat16, False),    # no multiple of 128 lanes
    ((1, 8000, 6144), 4, jnp.bfloat16, False),    # nor of the token tile
    ((1, 8192, 6144), 4, jnp.float16, False),     # a type the kernels leave
    ((1, 20, 24), 4, jnp.float32, False),         # a rehearsal's
])
def test_the_shapes_choose_the_form(shape, taps, dtype, kernels):
    """From the arguments alone: a jaxpr of forward and backward holds one
    ``conv_silu_bwd`` call where the sizes fill the tiles (its forward's
    result is not asked for), and no Pallas call where they do not."""
    s = jax.ShapeDtypeStruct
    text = step_text(shape, taps, dtype)
    assert text.count("name=conv_silu_bwd") == kernels
    assert ("pallas_call" in text) == kernels
    assert causal_conv.fills_tiles(s(shape, dtype),
                                   s((taps, shape[-1]), jnp.float32)) == kernels
    forward = str(jax.make_jaxpr(lambda *o: conv_silu(*o, dtype))(
        s(shape, dtype), s((taps, shape[-1]), jnp.float32),
        s(shape[-1:], jnp.float32)))
    assert forward.count("name=conv_silu_fwd") == kernels


def test_the_residuals_are_the_inputs_alone():
    """What the forward keeps for the backward at nemotron_h's sizes: ``x``
    as it is stored, the taps and the bias; no float32 array of ``[T, C]``
    elements (201 MB a layer for the pre-activation)."""
    s = jax.ShapeDtypeStruct
    t, c = 8192, 6144
    _, vjp = jax.eval_shape(
        lambda *o: jax.vjp(lambda *q: conv_silu(*q, jnp.bfloat16), *o),
        s((1, t, c), jnp.bfloat16), s((4, c), jnp.float32),
        s((c,), jnp.float32))
    kept = jax.tree_util.tree_leaves(vjp)
    assert sorted((v.size, str(v.dtype)) for v in kept) == [
        (c, "float32"), (4 * c, "float32"), (t * c, "bfloat16")]


def test_one_body_is_traced_for_every_layer_of_a_shape():
    """Three call sites at one shape (a step's three Mamba-2 layers) run
    each kernel body's Python once."""
    calls = []
    real = causal_conv._pre
    s = jax.ShapeDtypeStruct
    ops = (s((1, TOKENS, 256), jnp.float32), s((4, 256), jnp.float32),
           s((256,), jnp.float32))

    def counted(*a):
        calls.append(1)
        return real(*a)

    causal_conv._make_conv_silu.cache_clear()
    try:
        causal_conv._pre = counted
        text = str(jax.make_jaxpr(jax.value_and_grad(lambda *o: sum(
            jnp.sum(conv_silu(*o, jnp.float32)) for _ in range(3))))(*ops))
    finally:
        causal_conv._pre = real
        causal_conv._make_conv_silu.cache_clear()
    assert text.count("name=conv_silu_fwd") == 3
    assert text.count("name=conv_silu_bwd") == 3
    # two lane tiles a body, the forward's and the backward's
    assert len(calls) == 4


# tests/test_nemotron_h.py's and tests/test_phi4flash.py's rehearsal sizes:
# a convolution 128 channels wide in both, three Mamba-2 layers of seven and
# one Mamba layer of five
FAMILIES = {
    "nemotron_h": (3, dict(
        vocab=300, d_model=64, pattern="MEMEM*EMEMEM*E",
        layers_kept=(0, 1, 2, 3, 4, 5, 6), client_depth=1, mamba_heads=8,
        mamba_head_dim=8, ssm_state=16, ssm_groups=2, conv_taps=4, chunk=8,
        time_step_min=0.001, time_step_max=0.1, num_heads=4, num_kv_heads=2,
        head_dim=16, expert_width=32, shared_width=64, experts_total=8,
        experts_held=4, expert_offset=0, experts_per_token=2,
        route_scale=2.5, norm_eps=1e-5, attn="full", remat=True)),
    "phi4flash": (1, dict(
        vocab=300, d_model=64, num_heads=8, num_kv_heads=4, head_dim=8,
        mlp_width=128, window=8, d_state=4, d_conv=4, expand=2, dt_rank=4,
        layers_published=32, mb_per_layer=2,
        layers_kept=[15, 16, 17, 18, 19], client_depth=1, eps=1e-5,
        attn="full", remat=True)),
}


@pytest.mark.parametrize("family", sorted(FAMILIES))
@pytest.mark.parametrize("tokens,kernels", [(TOKENS, True), (24, False)])
def test_a_step_calls_each_kernel_once_a_layer(family, tokens, kernels):
    """The engagement count a trace shows, from the step's jaxpr: under
    ``remat`` one ``conv_silu_fwd`` and one ``conv_silu_bwd`` a Mamba layer
    (no second forward: nemotron_h wraps the plain form alone in
    ``jax.checkpoint``) where a row's tokens fill the tile, none where they
    do not."""
    from split_learning_tpu.core.losses import plan_loss
    from split_learning_tpu.models.factory import get_plan
    layers, kw = FAMILIES[family]
    plan = get_plan(family, "split", jnp.float32, **kw)
    x = jnp.zeros((1, tokens), jnp.int32)
    shapes = jax.eval_shape(plan.init, jax.random.PRNGKey(0), x)
    text = str(jax.make_jaxpr(jax.grad(
        lambda p: plan_loss(plan, p, x, x)))(shapes))
    assert text.count("name=conv_silu_fwd") == kernels * layers
    assert text.count("name=conv_silu_bwd") == kernels * layers
