"""Sequence-parallel attention (ops/ring_attention.py) vs dense reference.

The property both parallel forms must satisfy — on the 8-virtual-device
mesh (SURVEY.md §4 item 4) — is exact math: sharding the sequence axis
over ``seq`` must not change the attention output *or its gradients*
beyond float32 reassociation noise.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from split_learning_tpu.ops.ring_attention import (
    full_attention, ring_attention, ulysses_attention)

B, T, H, D = 4, 32, 4, 8


@pytest.fixture(scope="module")
def qkv():
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    shape = (B, T, H, D)
    return tuple(jax.random.normal(k, shape, jnp.float32) for k in ks)


def seq_mesh(devices, data=2, seq=4):
    grid = np.asarray(devices[: data * seq]).reshape(data, seq)
    return Mesh(grid, ("data", "seq"))


@pytest.mark.parametrize("attn", [ring_attention, ulysses_attention])
@pytest.mark.parametrize("causal", [False, True])
def test_matches_dense_forward(devices, qkv, attn, causal):
    q, k, v = qkv
    mesh = seq_mesh(devices)
    want = full_attention(q, k, v, causal=causal)
    got = jax.jit(lambda a, b, c: attn(a, b, c, mesh=mesh, causal=causal))(
        q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("attn", [ring_attention, ulysses_attention])
@pytest.mark.parametrize("causal", [False, True])
def test_matches_dense_gradients(devices, qkv, attn, causal):
    q, k, v = qkv
    mesh = seq_mesh(devices)
    w = jax.random.normal(jax.random.PRNGKey(3), q.shape, jnp.float32)

    def loss(fn):
        def f(a, b, c):
            return jnp.sum(fn(a, b, c) * w)
        return f

    want = jax.grad(loss(lambda a, b, c: full_attention(
        a, b, c, causal=causal)), argnums=(0, 1, 2))(q, k, v)
    got = jax.jit(jax.grad(loss(lambda a, b, c: attn(
        a, b, c, mesh=mesh, causal=causal)), argnums=(0, 1, 2)))(q, k, v)
    for g, wgrad in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(wgrad),
                                   atol=5e-5, rtol=5e-5)


def test_no_seq_axis_falls_back_to_dense(devices, qkv):
    """Model code calls ring_attention unconditionally; without a seq
    mesh axis it must be exactly the dense path."""
    q, k, v = qkv
    grid = np.asarray(devices[:4]).reshape(2, 2)
    mesh = Mesh(grid, ("data", "pipe"))
    want = full_attention(q, k, v)
    np.testing.assert_array_equal(
        np.asarray(ring_attention(q, k, v, mesh=mesh)), np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(ring_attention(q, k, v, mesh=None)), np.asarray(want))


def test_causal_first_token_ignores_future(devices, qkv):
    """Causal masking across shard boundaries: token 0's output depends
    only on token 0, even though later tokens live on other ranks."""
    q, k, v = qkv
    mesh = seq_mesh(devices)
    out = jax.jit(lambda a, b, c: ring_attention(
        a, b, c, mesh=mesh, causal=True))(q, k, v)
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(v[:, 0]),
                               atol=1e-6)


def test_ulysses_rejects_indivisible_heads(devices):
    mesh = seq_mesh(devices, data=2, seq=4)
    shape = (B, T, 6, D)  # 6 heads % 4 seq shards != 0
    q = jnp.zeros(shape)
    with pytest.raises(ValueError, match="divisible"):
        jax.jit(lambda a: ulysses_attention(a, a, a, mesh=mesh))(q)


@pytest.mark.slow
@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_matches_dense(devices, qkv, causal):
    """Ring with the Pallas flash kernel as block compute (interpret
    mode on CPU): exact vs dense, forward and gradients — the composed
    path that keeps per-rank attention memory O(T_local * D)."""
    q, k, v = qkv
    mesh = seq_mesh(devices)
    ring_flash = lambda a, b, c: ring_attention(
        a, b, c, mesh=mesh, causal=causal, block_impl="flash")
    want = full_attention(q, k, v, causal=causal)
    got = jax.jit(ring_flash)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)

    w = jax.random.normal(jax.random.PRNGKey(11), q.shape, jnp.float32)

    def loss(fn):
        return lambda a, b, c: jnp.sum(fn(a, b, c) * w)

    gw = jax.grad(loss(lambda a, b, c: full_attention(
        a, b, c, causal=causal)), argnums=(0, 1, 2))(q, k, v)
    gg = jax.jit(jax.grad(loss(ring_flash), argnums=(0, 1, 2)))(q, k, v)
    for g, want_g in zip(gg, gw):
        np.testing.assert_allclose(np.asarray(g), np.asarray(want_g),
                                   atol=2e-4, rtol=2e-4)


def test_ring_flash_no_seq_axis_falls_back_to_flash(devices, qkv):
    """Without a seq axis, block_impl='flash' degrades to the
    single-device flash kernel (not dense): same math either way."""
    q, k, v = qkv
    want = full_attention(q, k, v, causal=True)
    got = jax.jit(lambda a, b, c: ring_attention(
        a, b, c, mesh=None, causal=True, block_impl="flash"))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_ring_block_impl_validated():
    with pytest.raises(ValueError, match="block_impl"):
        ring_attention(jnp.zeros((1, 8, 1, 8)), jnp.zeros((1, 8, 1, 8)),
                       jnp.zeros((1, 8, 1, 8)), block_impl="bogus")


def test_ulysses_flash_matches_dense(devices, qkv):
    """Ulysses with the flash kernel as the per-head full-sequence math:
    exact vs dense (the long-context ulysses path)."""
    q, k, v = qkv
    mesh = seq_mesh(devices)
    got = jax.jit(lambda a, b, c: ulysses_attention(
        a, b, c, mesh=mesh, causal=True, block_impl="flash"))(q, k, v)
    want = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_parallel_auto_block_impl_resolution(monkeypatch):
    """block_impl='auto' maps the same HBM rule onto the shapes a rank
    actually materializes: tiny test shards stay dense; a long-context
    shard selects flash, and so does one at the speed crossover where
    the kernels compile."""
    from split_learning_tpu.ops.ring_attention import _resolve_block_impl

    assert _resolve_block_impl("dense", 4, 1 << 20, 1 << 20, 4, 4) == "dense"
    assert _resolve_block_impl("flash", 4, 8, 8, 4, 4) == "flash"
    assert _resolve_block_impl("auto", 4, 32, 32, 4, 4) == "dense"
    big = 1 << 20  # 3*4*4*T_q*T_kv*4 bytes >> any HBM
    assert _resolve_block_impl("auto", 4, big, big, 4, 4) == "flash"
    # the ring backward retains residuals over ALL hops: T_kv is global,
    # so a modest per-rank T still trips the wall when T_global is huge
    assert _resolve_block_impl("auto", 16, 4096, 1 << 22, 2, 4) == "flash"
    import importlib
    fa = importlib.import_module("split_learning_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "use_interpret", lambda: False)
    assert _resolve_block_impl("auto", 4, 1024, 1024, 4, 4) == "flash"
    assert _resolve_block_impl("auto", 4, 512, 512, 4, 4) == "dense"


@pytest.mark.parametrize("block_impl", [
    "dense",
    # the flash-block variant re-checks the same stripe semantics
    # through the interpreted Pallas kernel — 10 s of compile on this
    # image's single core, so it rides the slow tier (the kernel-level
    # flash equivalences stay in the default tier in
    # test_flash_attention.py)
    pytest.param("flash", marks=pytest.mark.slow),
])
def test_striped_causal_ring_matches_dense(devices, qkv, block_impl):
    """The load-balanced (striped) causal ring layout is exact vs dense,
    forward and gradients, with BOTH block computes — the stripe
    permutation and the per-hop causal/strict-causal local masks must
    compose to the identity semantics. (layout='auto' stripes the flash
    path, so the default long-context causal ring IS striped+flash.)"""
    q, k, v = qkv
    mesh = seq_mesh(devices)
    striped = lambda a, b, c: ring_attention(
        a, b, c, mesh=mesh, causal=True, layout="striped",
        block_impl=block_impl)
    want = full_attention(q, k, v, causal=True)
    got = jax.jit(striped)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)

    w = jax.random.normal(jax.random.PRNGKey(13), q.shape, jnp.float32)

    def loss(fn):
        return lambda a, b, c: jnp.sum(fn(a, b, c) * w)

    gw = jax.grad(loss(lambda a, b, c: full_attention(
        a, b, c, causal=True)), argnums=(0, 1, 2))(q, k, v)
    gg = jax.jit(jax.grad(loss(striped), argnums=(0, 1, 2)))(q, k, v)
    for g, want_g in zip(gg, gw):
        np.testing.assert_allclose(np.asarray(g), np.asarray(want_g),
                                   atol=2e-4, rtol=2e-4)


def test_explicit_contiguous_layout_and_permutation(devices, qkv):
    """The explicit contiguous layout stays pinned to dense semantics,
    and the stripe permutation round-trips."""
    from split_learning_tpu.ops.ring_attention import stripe_permutation

    q, k, v = qkv
    mesh = seq_mesh(devices)
    contiguous = jax.jit(lambda a, b, c: ring_attention(
        a, b, c, mesh=mesh, causal=True, layout="contiguous"))(q, k, v)
    np.testing.assert_allclose(
        np.asarray(contiguous),
        np.asarray(full_attention(q, k, v, causal=True)),
        atol=2e-5, rtol=2e-5)
    perm = stripe_permutation(T, 4)
    assert sorted(perm.tolist()) == list(range(T))
    np.testing.assert_array_equal(perm[np.argsort(perm)], np.arange(T))


def test_striped_layout_balances_causal_work():
    """The point of the stripes: per-(rank, hop) live-key counts — the
    work a mask-SKIPPING block compute (the flash kernels' causal block
    skip, which is why layout='auto' stripes exactly the flash path)
    actually executes. In the contiguous layout the busiest rank does n
    blocks of work while the idlest does 1 (ratio n); striped, every
    rank's total is within one token-row of equal — and the lockstep
    ring runs at the per-hop maximum, so the *critical path* (sum over
    hops of the busiest rank's live keys) drops nearly 2x at n=4."""
    t, n = 64, 4
    t_local = t // n

    def live_keys(q_pos, k_pos):
        return int((q_pos[:, None] >= k_pos[None, :]).sum())

    def totals(pos_of_rank):
        per_rank = []
        critical = 0
        for hop in range(n):
            hop_work = []
            for rank in range(n):
                src = (rank - hop) % n
                hop_work.append(live_keys(pos_of_rank(rank),
                                          pos_of_rank(src)))
            critical += max(hop_work)
            per_rank.append(hop_work)
        rank_totals = [sum(col) for col in zip(*per_rank)]
        return rank_totals, critical

    contiguous, crit_c = totals(
        lambda r: np.arange(t_local) + r * t_local)
    striped, crit_s = totals(
        lambda r: np.arange(t_local) * n + r)
    # same total causal work either way
    assert sum(contiguous) == sum(striped) == t * (t + 1) // 2
    # contiguous: rank 0 does ~1/n the work of rank n-1
    assert max(contiguous) / min(contiguous) > 2.5
    # striped: near-perfect balance
    assert max(striped) / min(striped) < 1.1
    # and the lockstep critical path shrinks accordingly
    assert crit_s < 0.65 * crit_c
