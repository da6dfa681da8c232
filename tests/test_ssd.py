"""The Mamba-2 recurrence's kernels (ops/ssd.py) in interpret mode, at shapes
that fill their tiles: chunks and states of 128, two groups of two heads of
64, 300 tokens (three chunks, the last padded: the carried state and its
cotangent cross two edges). Against the recurrence itself in float32,
against the plain form in bfloat16, and what the module header says of
both forms: causal, a head reads its own group, the choice by shape, and
residuals without a ``[chunks, H, L, L]`` array."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from split_learning_tpu.ops import ssd
from split_learning_tpu.ops.ssd import ssd_chunked, ssd_reference

CHUNK, T, HEADS, HEAD_DIM, GROUPS, STATE = 128, 300, 4, 64, 2, 128
NAMES = ("x", "dt", "a", "b", "c", "d_skip")


def operands(dtype=jnp.float32, batch=2, t=T, seed=0):
    """``dt`` around 0.1 to 0.3 and ``-a`` around 1, so that a chunk's first
    token still reaches its last (``exp(l_end)`` about 1e-9 at worst)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(ks[0], (batch, t, HEADS, HEAD_DIM)).astype(dtype),
            jax.nn.softplus(jax.random.normal(ks[1], (batch, t, HEADS)) - 2.0),
            -jnp.exp(0.5 * jax.random.normal(ks[2], (HEADS,))),
            (0.3 * jax.random.normal(ks[3], (batch, t, GROUPS, STATE))
             ).astype(dtype),
            (0.3 * jax.random.normal(ks[4], (batch, t, GROUPS, STATE))
             ).astype(dtype),
            jax.random.normal(ks[5], (HEADS,)))


@pytest.fixture
def form(request, monkeypatch):
    """``ssd_chunked`` at ``CHUNK`` through the kernels or, with the shapes'
    test answering no, through the plain form."""
    if request.param == "plain":
        monkeypatch.setattr(ssd, "fills_tiles", lambda *sizes: False)
    return lambda *ops: ssd_chunked(*ops, CHUNK)


def both_forms(fn):
    return pytest.mark.parametrize("form", ["kernels", "plain"],
                                   indirect=True)(fn)


def output_and_gradients(fn, ops):
    w = jax.random.normal(jax.random.PRNGKey(9), ops[0].shape)
    (_, y), grads = jax.jit(jax.value_and_grad(
        lambda *o: (lambda y: (jnp.sum(y * w), y))(fn(*o)),
        argnums=tuple(range(6)), has_aux=True))(*ops)
    return {"y": y, **dict(zip(NAMES, grads))}


@both_forms
def test_the_chunked_form_is_the_recurrence_in_float32(form):
    """Forward and all six operands' gradients against one token a step: the
    same arithmetic in another order, so a leaf agrees to 2e-5 of its
    largest entry."""
    ops = operands()
    want = output_and_gradients(ssd_reference, ops)
    got = output_and_gradients(form, ops)
    assert got["y"].dtype == jnp.float32 and got["y"].shape == ops[0].shape
    for name, u in got.items():
        v = want[name]
        assert u.shape == v.shape and u.dtype == v.dtype, name
        np.testing.assert_allclose(
            u, v, rtol=0, atol=2e-5 * max(1.0, float(jnp.abs(v).max())),
            err_msg=name)


def test_the_kernels_round_as_the_plain_form_does_in_bfloat16(monkeypatch):
    """bfloat16 operands: the kernels lie as far from the float32 recurrence
    as the plain form does (norm over norm), and from the plain form no
    further than both lie from the recurrence; the output, whose products
    round the same operands in the same order, agrees to an ulp of its
    accumulation."""
    ops = operands(jnp.bfloat16)
    want = output_and_gradients(ssd_reference, ops)
    got = output_and_gradients(lambda *o: ssd_chunked(*o, CHUNK), ops)
    monkeypatch.setattr(ssd, "fills_tiles", lambda *sizes: False)
    plain = output_and_gradients(lambda *o: ssd_chunked(*o, CHUNK), ops)
    far = lambda u, v: float(
        jnp.linalg.norm((u - v).astype(jnp.float32).ravel())
        / jnp.linalg.norm(v.astype(jnp.float32).ravel()))
    for name in ("y", *NAMES):
        assert got[name].dtype == plain[name].dtype, name
        to_plain, ours, its = (far(got[name], plain[name]),
                               far(got[name], want[name]),
                               far(plain[name], want[name]))
        assert ours <= 1.5 * its + 1e-3, (name, ours, its)
        assert to_plain <= 8e-3, (name, to_plain)
    assert far(got["y"], plain["y"]) <= 1e-5


@both_forms
def test_the_form_is_causal_and_a_head_reads_its_own_group(form):
    """tests/test_nemotron_h.py's test of the plain form at chunks of 8, at
    the kernels' sizes: a change at token 100 moves nothing before it,
    within its chunk and across the chunk's edge at 128; head n of 4 over 2
    groups reads group n // 2."""
    ops = operands(batch=1)
    x, dt, a, b, c, d = ops
    base = np.asarray(form(*ops))
    for moved in ((x.at[:, 100].add(1.0), dt, a, b, c, d),
                  (x, dt.at[:, 100].add(0.5), a, b, c, d),
                  (x, dt, a, b.at[:, 100].add(1.0), c, d)):
        changed = np.abs(np.asarray(form(*moved)) - base
                         ).max(axis=(0, 2, 3)) > 1e-6
        assert not changed[:100].any() and changed[100:CHUNK].all()
        assert changed[CHUNK:].any()       # the carried state took it on
    for which in (3, 4):
        moved = list(ops)
        moved[which] = ops[which].at[:, :, 1].multiply(1.5)
        by_head = np.abs(np.asarray(form(*moved)) - base
                         ).max(axis=(0, 1, 3)) > 1e-6
        assert by_head.tolist() == [False] * 2 + [True] * 2


@both_forms
def test_a_length_of_whole_chunks_and_one_chunk_alone(form):
    """256 tokens (no padding) and 128 (the grid's chunk axis has one step:
    what scripts/limit_readings.py's ``ssd_no_carry`` runs, a chunk a
    row)."""
    for t in (256, 128):
        ops = operands(batch=1, t=t, seed=3)
        np.testing.assert_allclose(form(*ops), ssd_reference(*ops), rtol=0,
                                   atol=2e-5 * 16)


def shapes(t, heads, head_dim, groups, state, dtype=jnp.bfloat16):
    s = jax.ShapeDtypeStruct
    return (s((1, t, heads, head_dim), dtype), s((1, t, heads), jnp.float32),
            s((heads,), jnp.float32), s((1, t, groups, state), dtype),
            s((1, t, groups, state), dtype), s((heads,), jnp.float32))


def step_text(chunk, *sizes, **kw):
    return str(jax.make_jaxpr(jax.grad(
        lambda *o: jnp.sum(ssd_chunked(*o, chunk)),
        argnums=tuple(range(6))))(*shapes(*sizes, **kw)))


@pytest.mark.parametrize("chunk,sizes,kernels", [
    (128, (8192, 64, 64, 8, 128), True),      # the published sizes
    (128, (300, 4, 64, 2, 128), True),        # this file's
    (8, (8192, 64, 64, 8, 128), False),       # a chunk under a lane tile
    (128, (8192, 64, 64, 8, 64), False),      # a state under one
    (128, (8192, 8, 64, 8, 128), False),      # a group of one head of 64
    (128, (8192, 64, 48, 8, 128), False),     # heads that split no tile
    (8, (20, 8, 4, 2, 16), False),            # the rehearsal's
])
def test_the_shapes_choose_the_form(chunk, sizes, kernels):
    """From the arguments alone: a jaxpr of forward and backward holds one
    ``ssd_fwd`` and one ``ssd_bwd`` call where the sizes fill the tiles,
    and no Pallas call where they do not."""
    text = step_text(chunk, *sizes)
    assert text.count("name=ssd_fwd") == text.count("name=ssd_bwd") == kernels
    assert ("pallas_call" in text) == kernels
    assert ssd.fills_tiles(chunk, sizes[4], sizes[1] // sizes[3],
                           sizes[2]) == kernels


def test_operands_of_two_types_take_the_plain_form():
    s = shapes(300, 4, 64, 2, 128)
    mixed = (s[0], *s[1:3], jax.ShapeDtypeStruct(s[3].shape, jnp.float32),
             *s[4:])
    assert "pallas_call" not in str(jax.make_jaxpr(
        lambda *o: ssd_chunked(*o, CHUNK))(*mixed))


def test_the_residuals_hold_no_array_of_a_chunks_pairs():
    """What the forward keeps for the backward at the published sizes: the
    operands, ``dt`` and ``l`` in their two layouts and the states the
    chunks start from in the products' type; nothing of ``[chunks, H, L,
    L]`` elements (268 MB a layer in float32 for the plain form's decays),
    nothing in float32 beyond ``[B, T, H]``."""
    t, heads, head_dim, groups, state = 8192, 64, 64, 8, 128
    _, vjp = jax.eval_shape(
        lambda *o: jax.vjp(lambda *q: ssd_chunked(*q, CHUNK), *o),
        *shapes(t, heads, head_dim, groups, state))
    kept = jax.tree_util.tree_leaves(vjp)
    pairs = t // CHUNK * heads * CHUNK * CHUNK
    assert kept and all(v.size < pairs for v in kept)
    assert all(v.size <= t * heads for v in kept if v.dtype == jnp.float32)
    states = t // CHUNK * heads * head_dim * state
    assert sorted(v.size for v in kept if v.dtype == jnp.bfloat16) == sorted(
        [t * heads * head_dim, states, t * groups * state, t * groups * state])


def test_one_body_is_traced_for_every_layer_of_a_shape():
    """Three call sites at one shape (a step's three Mamba-2 layers) run
    each kernel body's Python once."""
    calls = []
    real = ssd._chunk_parts
    ops = shapes(256, 4, 64, 2, 128, jnp.float32)

    def counted(*a):
        calls.append(1)
        return real(*a)

    ssd._make_ssd.cache_clear()
    try:
        ssd._chunk_parts = counted
        jax.make_jaxpr(jax.grad(lambda *o: sum(
            jnp.sum(ssd_chunked(*o, CHUNK)) for _ in range(3))))(*ops)
    finally:
        ssd._chunk_parts = real
        ssd._make_ssd.cache_clear()
    assert len(calls) == 2          # the forward's body and the backward's
