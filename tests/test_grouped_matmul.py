"""The grouped products' tile rule (ops/grouped_matmul.py): tiles that
divide the dimension they tile, the products under them against the
plain loop, and what the rule leaves as it was."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from split_learning_tpu.models.afmoe import pair_rungs
from split_learning_tpu.ops import grouped_matmul as gm

D_MODEL = 2048
# cell: expert width, (pairs a step, experts held, experts in all)
CELLS = {"trinity-mini": (1024, (65536, 8, 128)),
         "joyai-llm-flash": (768, (65536, 8, 256)),
         "lfm2-24b-a2b": (1536, (32768, 8, 64))}


def old_rule(k: int, n: int) -> tuple:
    """``_tiles`` before PR 38, at 512 rows or more."""
    return 512, min(k, 1024), min(n, 1024)


def layer_calls(rows: int, width: int) -> list:
    """(kernel, k, n, tiling) of the six distinct calls of a routed layer
    (gate and up share theirs; the forward runs twice): ``k`` the
    dimension the kernel's ``tk`` tiles and ``n`` the one ``tn`` does."""
    calls = []
    for k, n in ((D_MODEL, width), (width, D_MODEL)):    # gate / up, down
        tiling = gm._tiles(rows, k, n)
        d_rows, d_weights = gm._bwd_tiles(tiling)
        calls += [("gmm", k, n, tiling), ("gmm_t", n, k, d_rows),
                  ("tgmm", k, n, d_weights)]
    return calls


@pytest.mark.parametrize("width", [768, 1024, 1536, 1408, 1664])
@pytest.mark.parametrize("wide", ["n", "k"])
def test_products_and_gradients_match_a_loop_at_the_cells_widths(width, wide):
    """Forward, the rows' gradient and the weights' gradient at a
    model-sized ``k`` and ``n``: every width to 1536 whole, 1664 (over
    the cap, no divisor) through megablox's irregular last tile of 1024;
    the groups leave rows of ``lhs`` unfilled."""
    k, n = (D_MODEL, width) if wide == "n" else (width, D_MODEL)
    sizes = [9, 0, 17]
    m = 40
    ks = jax.random.split(jax.random.PRNGKey(width), 3)
    x = jax.random.normal(ks[0], (m, k)) / k ** 0.5
    w = jax.random.normal(ks[1], (len(sizes), k, n))
    c = jax.random.normal(ks[2], (m, n)) / n ** 0.5
    gs = jnp.array(sizes, jnp.int32)
    f = lambda fn: (lambda x, w: jnp.sum(fn(x, w, gs) * c))
    want = jax.value_and_grad(f(gm.grouped_matmul_reference),
                              argnums=(0, 1))(x, w)
    got = jax.jit(jax.value_and_grad(f(gm.grouped_matmul),
                                     argnums=(0, 1)))(x, w)
    out = gm.grouped_matmul(x, w, gs)
    assert not np.asarray(out)[sum(sizes):].any()
    np.testing.assert_allclose(
        out, gm.grouped_matmul_reference(x, w, gs), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4, atol=1e-4)
    for a, b in zip(got[1], want[1]):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("rows", [4096, 8192, 65536])
@pytest.mark.parametrize("config,tilings", [
    ("trinity-mini", [(512, 1024, 1024), (512, 1024, 1024)]),
    ("joyai-llm-flash", [(512, 1024, 768), (512, 768, 1024)]),
])
def test_the_bypassing_cells_keep_their_tiles(rows, config, tilings):
    width = CELLS[config][0]
    for (k, n), tiling in zip(((D_MODEL, width), (width, D_MODEL)), tilings):
        assert gm._tiles(rows, k, n) == tiling
        assert tiling == old_rule(k, n)
        assert gm.tile_fill(rows, k, n) == 1.0


@pytest.mark.parametrize("rows", pair_rungs(*CELLS["lfm2-24b-a2b"][1]))
def test_every_call_of_an_lfm2_layer_gets_tiles_that_divide(rows):
    calls = layer_calls(rows, 1536)
    assert len(calls) == 6      # x 2 for gate and up, forward twice: twelve
    for kernel, k, n, (tm, tk, tn) in calls:
        assert (tm, k % tk, n % tn) == (512, 0, 0), (kernel, k, n, tk, tn)
        assert tk % 128 == 0 and tn % 128 == 0
    assert [t for _, _, _, t in calls] == [
        (512, 1024, 1536), (512, 1536, 1024), (512, 512, 1536),
        (512, 1536, 1024), (512, 1024, 1536), (512, 512, 1024)]


@pytest.mark.parametrize("k,n,tiling", [
    (2048, 1536, (512, 1024, 1536)),    # 14 MiB by the count: the wide cap
    (2048, 3072, (512, 1024, 1536)),
    (1536, 1536, (512, 768, 768)),      # 18 MiB whole: back under 1024
    (3072, 3072, (512, 1024, 1024)),
    (2048, 1664, (512, 1024, 1024)),    # no divisor: the irregular tile
])
def test_the_wide_cap_holds_only_where_every_calls_tiles_fit(k, n, tiling):
    assert gm._tiles(8192, k, n) == tiling
    rows, weights = gm._bwd_tiles(tiling)
    assert max(gm._gmm_bytes(*tiling), gm._gmm_bytes(*rows),
               gm._tgmm_bytes(*weights)) <= 14 * 2 ** 20


@pytest.mark.parametrize("dim,under_1024,under_1536", [
    (24, 24, 24), (768, 768, 768), (1024, 1024, 1024), (1536, 768, 1536),
    (2048, 1024, 1024), (2560, 640, 1280), (3072, 1024, 1536),
    (4608, 768, 1536), (1408, 1024, 1408),
    (1664, 1024, 1024), (2176, 1024, 1024),     # 13 and 17 lane tiles: 1024
])
def test_a_tile_divides_its_dimension_or_is_1024(dim, under_1024, under_1536):
    assert gm._dividing(dim, 1024) == under_1024
    assert gm._dividing(dim, 1536) == under_1536


@pytest.mark.parametrize("k,n,old,new", [
    (2048, 1536, 0.75, 1.0), (1536, 2048, 0.75, 1.0),     # LFM2
    (2048, 1024, 1.0, 1.0), (1024, 2048, 1.0, 1.0),       # Trinity-Mini
    (2048, 768, 1.0, 1.0), (768, 2048, 1.0, 1.0),         # JoyAI
    (2048, 1408, 0.6875, 1.0),                            # whole now
    (2048, 1664, 0.8125, 0.8125),                         # no divisor
])
def test_tile_fill_is_useful_over_run_work(k, n, old, new):
    assert gm.tile_fill(8192, k, n, old_rule(k, n)) == old
    assert gm.tile_fill(8192, k, n) == new


@pytest.mark.parametrize("config,rungs", [
    ("trinity-mini", (8192, 16384, 65536)),
    ("joyai-llm-flash", (4096, 8192, 65536)),
    ("lfm2-24b-a2b", (8192, 16384, 32768))])
def test_the_row_tile_and_the_rungs_did_not_move(config, rungs):
    assert pair_rungs(*CELLS[config][1]) == rungs
    assert all(gm._tiles(rows, 1, 1)[0] == 512 for rows in rungs)
