"""The selective-scan kernel (ops/selective_scan.py), in interpret mode,
against the plain ``lax.scan`` form beside it: values and all six
gradients, with T over several chunks and not a multiple of one, a
``d_inner`` that is no whole lane tile, and the blocks the shapes pick."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from split_learning_tpu.ops.selective_scan import (
    _pick_blocks, selective_scan, selective_scan_reference)

NAMES = ("x", "delta", "a", "b", "c", "d_skip")


def operands(batch, t, d_inner, d_state, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(ks[0], (batch, t, d_inner)),
            jax.nn.softplus(jax.random.normal(ks[1], (batch, t, d_inner))),
            -jnp.exp(0.5 * jax.random.normal(ks[2], (d_inner, d_state))),
            jax.random.normal(ks[3], (batch, t, d_state)),
            jax.random.normal(ks[4], (batch, t, d_state)),
            jax.random.normal(ks[5], (d_inner,)))


# T 150 is three chunks of 64 with the last padded, d_inner 160 two lane
# tiles with the second padded; T 20 is one short chunk; T 128 whole chunks
# and d_inner 768 two channel blocks of 384, so a state is carried across
# chunks in each and the sums over channels cross a block
SHAPES = [(2, 150, 160, 4), (1, 20, 32, 16), (1, 128, 768, 8)]


@pytest.fixture(scope="module", params=SHAPES, ids=lambda s: "x".join(map(str, s)))
def both(request):
    """(output, six gradients) of the kernel and of the plain form."""
    args = operands(*request.param)
    w = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)

    def run(fn):
        y = fn(*args)
        grads = jax.grad(lambda *a: jnp.sum(fn(*a) * w),
                         argnums=tuple(range(6)))(*args)
        return y, grads

    return run(selective_scan), run(selective_scan_reference)


def test_output_matches_the_plain_scan(both):
    (y, _), (want, _) = both
    # float32 both: the same sums in another order
    np.testing.assert_allclose(y, want, rtol=0,
                               atol=1e-5 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("which", range(6), ids=NAMES)
def test_gradient_matches_the_plain_scan(both, which):
    (_, got), (_, want) = both
    g, w = got[which], want[which]
    assert g.shape == w.shape and g.dtype == w.dtype
    np.testing.assert_allclose(g, w, rtol=0,
                               atol=1e-5 * float(jnp.abs(w).max()))


def test_blocks_follow_the_shapes():
    # (chunk, padded T, channel block, padded d_inner)
    assert _pick_blocks(8192, 5120) == (64, 8192, 640, 5120)
    assert _pick_blocks(150, 160) == (64, 192, 256, 256)
    assert _pick_blocks(20, 32) == (24, 24, 128, 128)
    assert _pick_blocks(128, 768) == (64, 128, 384, 768)


def test_output_takes_the_type_of_x_and_shapes_are_checked():
    x, delta, a, b, c, d = operands(1, 16, 32, 4)
    y = selective_scan(x.astype(jnp.bfloat16), delta, a, b, c, d)
    assert y.dtype == jnp.bfloat16 and y.shape == x.shape
    want = selective_scan_reference(x.astype(jnp.bfloat16), delta, a, b, c, d)
    np.testing.assert_allclose(y.astype(np.float32), want.astype(np.float32),
                               rtol=0.02, atol=0.02)
    with pytest.raises(ValueError, match="no selective scan"):
        selective_scan(x, delta, a.T, b, c, d)
    with pytest.raises(ValueError, match="no selective scan"):
        selective_scan(x, delta, a, b[:, :8], c, d)


def test_the_state_crosses_chunks():
    """A token of the first chunk moves the last chunk's output, through
    the state carried in VMEM, and by what the plain form says."""
    args = operands(1, 150, 32, 4, seed=3)
    slow = (args[0], 0.05 * args[1], *args[2:])     # a long memory
    bumped = (slow[0].at[0, 5].add(1.0), *slow[1:])
    moved = selective_scan(*bumped) - selective_scan(*slow)
    want = selective_scan_reference(*bumped) - selective_scan_reference(*slow)
    assert float(jnp.abs(want[0, 140:]).max()) > 1e-4
    np.testing.assert_allclose(moved[0, 140:], want[0, 140:], atol=1e-5)
