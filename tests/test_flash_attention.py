"""Flash attention kernels (ops/flash_attention.py) vs the dense
reference — interpret mode on CPU, compiled on TPU (same code path)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from split_learning_tpu.ops.flash_attention import flash_attention
from split_learning_tpu.ops.ring_attention import full_attention


def qkv(b=2, t=40, h=3, d=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, t, h, d), jnp.float32)
                 for k in ks)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("t", [40, 128, 200])
def test_forward_matches_dense(causal, t):
    """Ragged (40, 200) and exact (128) T against the 128-block grid."""
    q, k, v = qkv(t=t)
    want = full_attention(q, k, v, causal=causal)
    got = jax.jit(lambda a, b, c: flash_attention(
        a, b, c, causal=causal))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_gradients_match_dense(causal):
    q, k, v = qkv(t=72)  # ragged: 72 pads to one 128 block
    w = jax.random.normal(jax.random.PRNGKey(5), q.shape, jnp.float32)

    def loss(fn):
        return lambda a, b, c: jnp.sum(fn(a, b, c) * w)

    want = jax.grad(loss(lambda a, b, c: full_attention(
        a, b, c, causal=causal)), argnums=(0, 1, 2))(q, k, v)
    got = jax.jit(jax.grad(loss(lambda a, b, c: flash_attention(
        a, b, c, causal=causal)), argnums=(0, 1, 2)))(q, k, v)
    for g, wg in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(wg),
                                   atol=5e-5, rtol=5e-5)


def _mask(tp, causal, strict, window):
    """The position mask over a padded sequence, as a boolean matrix."""
    behind = np.arange(tp)[:, None] - np.arange(tp)[None, :]
    ok = np.ones((tp, tp), bool)
    if causal:
        ok &= behind > 0 if strict else behind >= 0
    if window is not None:
        ok &= behind < window
    return ok


# blocks of 128: window under / equal to / one over / between / twice the
# block; T 300 is ragged (three blocks, 84 rows of padding)
@pytest.mark.parametrize("tile", [32, 64])
@pytest.mark.parametrize("t,causal,strict,window", [
    (128, True, False, None), (640, True, False, None),
    (384, True, True, None), (384, True, False, 64),
    (640, True, False, 128), (640, True, False, 129),
    (640, True, False, 200), (640, True, False, 256),
    (300, True, False, None), (300, True, False, 100),
    (384, False, False, None)],
    ids=["one-block", "causal", "strict", "window-under", "window-equal",
         "window-one-over", "window-between", "window-twice", "ragged",
         "ragged-window", "not-causal"])
def test_live_tiles_are_the_masks_own(t, causal, strict, window, tile):
    """``_live_cols`` / ``_live_rows`` / ``live_tile_share`` against a
    count over the boolean mask: in every block pair the kernels visit, a
    sub-tile is run if and only if the position mask leaves one of its
    entries live (rows and columns past a ragged T are the elementwise
    mask's: every tile they leave live is among those run)."""
    import importlib
    fa = importlib.import_module("split_learning_tpu.ops.flash_attention")
    block, n = 128, 128 // tile
    n_blk = -(-t // block)
    ok = _mask(n_blk * block, causal, strict, window)
    within = np.arange(n_blk * block) < t
    real = ok & within[:, None] & within[None, :]
    n_off = n_blk if window is None else fa._band_blocks(window, block, n_blk)
    whole = fa._whole_pairs(n_off, t, block, causal, strict, window)
    ran = visited = pairs = unmasked = 0
    for qb in range(n_blk):
        for kb in range(n_blk):
            if causal and not 0 <= qb - kb < n_off:
                continue   # a pair the grid skips whole
            pair = ok[qb * block:, kb * block:][:block, :block]
            # a pair's body drops its mask only where nothing is masked
            # (a ragged T keeps every pair's, the whole ones' too)
            bare = (qb - kb) in whole if causal else bool(whole)
            assert bare == bool(pair.all() and t % block == 0)
            pairs, unmasked = pairs + 1, unmasked + bare
            tiles = pair.reshape(n, tile, n, tile).any(axis=(1, 3))
            cols = fa._live_cols((qb - kb) * block, block, tile, causal,
                                 strict, window)
            got = np.zeros((n, n), bool)
            for r, (lo, hi) in enumerate(cols):
                got[r, lo:hi] = True
            np.testing.assert_array_equal(got, tiles, err_msg=f"{qb},{kb}")
            back = np.zeros((n, n), bool)
            for c, (lo, hi) in enumerate(fa._live_rows(cols)):
                back[lo:hi, c] = True
            np.testing.assert_array_equal(back, tiles)
            seen = real[qb * block:, kb * block:][:block, :block]
            assert not (seen.reshape(n, tile, n, tile).any(axis=(1, 3))
                        & ~got).any()
            ran += got.sum()
            visited += n * n
    assert fa.live_tile_share(t, block, causal, window, strict,
                              tile) == pytest.approx(ran / visited)
    assert fa.unmasked_pair_share(t, block, causal, window,
                                  strict) == pytest.approx(unmasked / pairs)
    if not causal:
        assert ran == visited and fa._cut_pairs(
            n_off, block, tile, causal, strict, window) == {}


@pytest.mark.parametrize("t,window,at_512,at_256", [
    (1024, None, 0.750, 0.625), (8192, None, 0.944, 0.917),
    (8192, 2048, 0.833, 0.750), (8192, 512, 0.517, 0.388)],
    ids=["gpt2", "full-t8192", "trinity-window", "phi4flash-window"])
def test_live_tile_share_at_the_cells_shapes(t, window, at_512, at_256):
    """ISSUE 31's table: the share of today's sub-tiles that stay live at
    blocks of 1024, by the mask of each benchmark cell's calls."""
    from split_learning_tpu.ops.flash_attention import live_tile_share
    assert live_tile_share(t, 1024, True, window, tile=512) == pytest.approx(
        at_512, abs=1e-3)
    assert live_tile_share(t, 1024, True, window, tile=256) == pytest.approx(
        at_256, abs=1e-3)
    assert live_tile_share(t, 1024, True, window) == pytest.approx(
        at_256, abs=1e-3)   # the module's constant
    assert live_tile_share(t, 1024, False) == 1.0


@pytest.mark.parametrize("t,window,fetched,unmasked", [
    (1024, None, 1.0, 0.0), (8192, None, 36 / 64, 28 / 36),
    (8192, 2048, 21 / 24, 7 / 21), (8192, 512, 15 / 16, 0.0),
    (8000, None, 36 / 64, 0.0)],
    ids=["gpt2", "full-t8192", "trinity-window", "phi4flash-window",
         "ragged"])
def test_pair_shares_at_the_cells_shapes(t, window, fetched, unmasked):
    """ISSUE 33's counters at blocks of 1024, by the mask of each cell's
    calls: the share of the forward's grid steps that fetch a key block
    (all of them before: the dead pairs' blocks were fetched too) and the
    share of the live pairs whose body masks nothing."""
    from split_learning_tpu.ops.flash_attention import (
        fetched_pair_share, unmasked_pair_share)
    assert fetched_pair_share(t, 1024, True, window) == pytest.approx(fetched)
    assert unmasked_pair_share(t, 1024, True, window) == pytest.approx(
        unmasked)
    assert fetched_pair_share(t, 1024, False) == 1.0
    assert unmasked_pair_share(t, 1024, False) == float(t % 1024 == 0)


def test_index_maps_name_no_dead_block():
    """The inner index maps as plain functions: in a causal grid of 8
    blocks, step ``k`` of row block ``i`` names key block ``min(k, i)``
    (36 fetches a head where the grid has 64 steps: a step that names the
    block of the step before copies nothing) and, from the key side,
    query block ``max(k, i)``; under a window the band's clamps; a grid
    of one block, or no causal mask, names block ``k`` as it did."""
    import importlib
    fa = importlib.import_module("split_learning_tpu.ops.flash_attention")
    kv_inner, q_inner = fa._inner_maps(8, 8, True, None, fa._kv_index(4))
    named = [[int(kv_inner(5, i, k)[1]) for k in range(8)] for i in range(8)]
    assert named == [[min(k, i) for k in range(8)] for i in range(8)]
    assert sum(len(set(row)) for row in named) == 36
    assert all(a <= b for row in named for a, b in zip(row, row[1:]))
    assert kv_inner(5, 3, 7)[0] == 1 and q_inner(5, 3, 7)[0] == 5
    assert [[int(q_inner(0, i, k)[1]) for k in range(8)]
            for i in range(8)] == [[max(k, i) for k in range(8)]
                                   for i in range(8)]
    kv_inner, q_inner = fa._inner_maps(8, 3, True, 2048, fa._kv_index(1))
    assert [int(kv_inner(0, i, k)[1]) for i in (0, 1, 7)
            for k in range(3)] == [0, 0, 0, 0, 0, 1, 5, 6, 7]
    assert [int(q_inner(0, i, k)[1]) for i in (0, 6, 7)
            for k in range(3)] == [0, 1, 2, 6, 7, 7, 7, 7, 7]
    for n_blk, causal in ((1, True), (8, False)):
        kv_inner, q_inner = fa._inner_maps(n_blk, n_blk, causal, None,
                                           fa._kv_index(1))
        assert kv_inner(2, 0, 5) == (2, 5, 0) == q_inner(2, 0, 5)


def _dense(q, k, v, strict, window):
    """Causal attention with its logsumexp, the plain way; a row that
    sees no key (the first, under ``strict``) gives zeros and NEG_BIG."""
    from split_learning_tpu.ops.common import NEG_BIG
    t, g = q.shape[1], q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    ok = jnp.asarray(_mask(t, True, strict, window))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    s = jnp.where(ok, s, NEG_BIG)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(ok, jnp.exp(s - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    seen = l > 0
    o = jnp.einsum("bhqk,bkhd->bqhd", p / jnp.where(seen, l, 1.0), v)
    lse = jnp.where(seen, m + jnp.log(jnp.where(seen, l, 1.0)), NEG_BIG)
    return o, jnp.transpose(lse[..., 0], (0, 2, 1))


# blocks of 128 in sub-tiles of 32 or 64: one block is GPT-2's case (the
# diagonal pair and nothing else), 384 adds whole pairs below it (their
# bodies mask nothing) and dead ones past it (their blocks are not
# fetched), strict is a ring hop's mask, 300 pads 84 rows and columns
# (every pair keeps its mask); window 256 has one whole pair between two
# cut ones, 200 cuts the band's last two; then one key/value head under
# two query heads, and keys of 24 over values of 16
@pytest.mark.parametrize("onepass", [True, False], ids=["onepass", "split"])
@pytest.mark.parametrize("t,tile,strict,window,kv_heads,d_k", [
    (128, 32, False, None, 2, 16), (384, 32, False, None, 2, 16),
    (256, 64, True, None, 2, 16), (300, 32, False, None, 2, 16),
    (512, 32, False, 256, 2, 16), (512, 64, False, 200, 2, 16),
    (384, 32, False, None, 1, 16), (384, 64, False, None, 2, 24)],
    ids=["one-block", "many-blocks", "strict", "ragged", "window-blocks",
         "window-between", "grouped", "keys-wider"])
def test_cut_pairs_match_dense(flash_tiled, onepass, t, tile, strict, window,
                               kv_heads, d_k):
    """Forward, logsumexp and all three gradients of the kernels whose
    block pairs each run the body of their kind, both backward forms."""
    q, k, v = qkv(t=t, b=1, h=2)
    k, v = k[:, :, :kv_heads], v[:, :, :kv_heads]
    if d_k != v.shape[-1]:   # queries and keys of d_k over values of 16
        q, k = (jnp.tile(x, (1, 1, 1, 2))[..., :d_k] for x in (q, k))
    ks = jax.random.split(jax.random.PRNGKey(9), 2)
    w = jax.random.normal(ks[0], q.shape[:3] + v.shape[3:])
    w_lse = jax.random.normal(ks[1], q.shape[:3])

    def loss(fn):
        def f(a, b, c):
            o, lse = fn(a, b, c)
            lse = jnp.where(lse < -1e20, 0.0, lse)   # rows that see no key
            return jnp.sum(o * w) + jnp.sum(lse * w_lse)
        return jax.value_and_grad(f, argnums=(0, 1, 2))

    got = loss(lambda a, b, c: flash_tiled(
        a, b, c, block=128, tile=tile, onepass=onepass, strict=strict,
        window=window, with_lse=True))(q, k, v)
    want = loss(lambda a, b, c: _dense(a, b, c, strict, window))(q, k, v)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-3)
    for g, wg in zip(got[1], want[1]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(wg),
                                   atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("t,block", [(640, 128), (2048, 1024)])
def test_multi_block_gradients(t, block):
    """Multi-block grids under the adaptive block picker: T=640 tiles as
    5x128 (ragged T keeps the small edge), T=2048 as 2x1024 (the large
    edge the round-5 on-chip sweep adopted as default). Exercises the
    inner block loops of all three kernels, causal (block-skew)
    masking on."""
    from split_learning_tpu.ops.flash_attention import _pick_block
    assert _pick_block(t) == block
    q, k, v = qkv(t=t, b=1, h=2)
    w = jax.random.normal(jax.random.PRNGKey(6), q.shape, jnp.float32)
    f = lambda a, b, c: jnp.sum(flash_attention(a, b, c, causal=True) * w)
    r = lambda a, b, c: jnp.sum(full_attention(a, b, c, causal=True) * w)
    got = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(r, argnums=(0, 1, 2))(q, k, v)
    for g, wg in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(wg),
                                   atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_onepass_backward_matches_two_kernel(monkeypatch, causal):
    """The mid-T one-pass backward (grid (bh, k), VMEM-resident dQ) and
    the long-T two-kernel split must produce the same gradients — the
    form is a perf choice, never a numerics choice. T=256 tiles as
    2x128 so the one-pass q loop and the causal start offset are both
    multi-block."""
    import importlib
    fa = importlib.import_module("split_learning_tpu.ops.flash_attention")
    _make_flash = fa._make_flash
    q, k, v = qkv(t=256, b=1, h=2, d=16)
    w = jax.random.normal(jax.random.PRNGKey(7), q.shape, jnp.float32)

    grads = {}
    for name, flag in (("onepass", True), ("twokernel", False)):
        monkeypatch.setattr(fa, "ONEPASS", flag)
        _make_flash.cache_clear()  # onepass is part of the build key
        f = lambda a, b, c: jnp.sum(
            flash_attention(a, b, c, causal=causal) * w)
        grads[name] = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    _make_flash.cache_clear()
    for g1, g2 in zip(grads["onepass"], grads["twokernel"]):
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   atol=1e-5, rtol=1e-5)


def test_onepass_backward_bf16_storage():
    """The on-chip path runs bf16 storage with f32 accumulation; pin the
    same property in interpret mode: bf16 one-pass grads track the f32
    dense reference within bf16 resolution."""
    q, k, v = qkv(t=128, b=1, h=2, d=16)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    w = jax.random.normal(jax.random.PRNGKey(8), q.shape, jnp.float32)

    f = lambda a, b, c: jnp.sum(
        flash_attention(a, b, c, causal=True).astype(jnp.float32) * w)
    r = lambda a, b, c: jnp.sum(full_attention(a, b, c, causal=True) * w)
    got = jax.grad(f, argnums=(0, 1, 2))(qb, kb, vb)
    want = jax.grad(r, argnums=(0, 1, 2))(q, k, v)
    for g, wg in zip(got, want):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(wg), atol=0.04, rtol=0.04)


def test_onepass_selection_rule(monkeypatch):
    """_use_onepass: VMEM-residency-bounded, env-overridable."""
    import importlib
    # ops/__init__ re-exports the flash_attention *function*, which
    # shadows the submodule attribute `import ... as` would resolve
    fa = importlib.import_module(
        "split_learning_tpu.ops.flash_attention")
    _use_onepass = fa._use_onepass

    # pin the v4/v5 VMEM figure so the assertions are host-independent,
    # and pin interpret mode so this tests the *static* rule only — on
    # a TPU host the raised-limit shapes would otherwise consult real
    # preflight compiles (and cache their verdicts process-wide under
    # the monkeypatched limit)
    monkeypatch.setattr(fa, "_vmem_limit_bytes", lambda: 96 * 1024 * 1024)
    monkeypatch.setattr(fa, "use_interpret", lambda: True)
    # bf16 d=128: _onepass_resident_bytes = 4 KiB/row (double-buffered,
    # lane-padded rows) -> 64 MiB budget caps at tp 16384
    assert _use_onepass(4096, 512, 128, jnp.bfloat16)
    assert _use_onepass(8192, 512, 128, jnp.bfloat16)
    assert _use_onepass(16384, 512, 128, jnp.bfloat16)
    assert not _use_onepass(32768, 512, 128, jnp.bfloat16)
    # f32 rows are 5 KiB: cap drops below tp 16384
    assert _use_onepass(8192, 512, 128, jnp.float32)
    assert not _use_onepass(16384, 512, 128, jnp.float32)


def test_onepass_preflight_fallback(monkeypatch):
    """On a compiled-TPU path (use_interpret() False), a shape needing
    the raised scoped-VMEM limit consults the cached preflight compile
    and falls back to the two-kernel split when the device rejects it —
    the round-4 T=4096 hard compile error can never recur as a
    user-path failure."""
    import importlib
    fa = importlib.import_module(
        "split_learning_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "_vmem_limit_bytes", lambda: 96 * 1024 * 1024)
    monkeypatch.setattr(fa, "use_interpret", lambda: False)

    # T=4096 bf16 d=128 needs ~16.5 MiB resident: past the 12 MiB
    # default-limit-safe line, so the preflight verdict decides
    monkeypatch.setattr(fa, "_onepass_compile_ok",
                        lambda *a: False)
    assert not fa._use_onepass(4096, 512, 128, jnp.bfloat16)
    monkeypatch.setattr(fa, "_onepass_compile_ok",
                        lambda *a: True)
    assert fa._use_onepass(4096, 512, 128, jnp.bfloat16)
    # T=1024 bf16 fits the 16 MiB default (~4.1 MiB resident): one-pass
    # without any probe even where the probe would say no
    monkeypatch.setattr(fa, "_onepass_compile_ok",
                        lambda *a: False)
    assert fa._use_onepass(1024, 512, 128, jnp.bfloat16)
    # a form named from outside short-circuits everything, the probe too
    monkeypatch.setattr(fa, "ONEPASS", False)
    assert not fa._use_onepass(1024, 512, 128, jnp.bfloat16)


def test_large_block_always_preflights(monkeypatch):
    """Edges past _SPLIT_BLOCK_MAX must consult the compiler even at
    tiny residency: the _DEFAULT_LIMIT_SAFE skip margin was derived
    for <=512 blocks (~1 MiB of block buffers), and a 1024 edge's f32
    score temporaries (4 MiB per pair) void it."""
    import importlib
    fa = importlib.import_module(
        "split_learning_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "_vmem_limit_bytes", lambda: 96 * 1024 * 1024)
    monkeypatch.setattr(fa, "use_interpret", lambda: False)
    probed = []

    def probe(*a):
        probed.append(a)
        return False

    monkeypatch.setattr(fa, "_onepass_compile_ok", probe)
    # T=1024 bf16 d=128: ~4.1 MiB resident — inside the skip margin,
    # but block=1024 still must preflight (and honor its verdict)
    assert not fa._use_onepass(1024, 1024, 128, jnp.bfloat16)
    assert probed
    # same shape at the derived-for 512 edge: no probe, static yes
    probed.clear()
    assert fa._use_onepass(1024, 512, 128, jnp.bfloat16)
    assert not probed


def test_preflight_probes_the_mask_the_call_uses(monkeypatch):
    """A pair the mask cuts has a body of its own, so the one-pass
    preflight compiles the call's own (causal, strict, window): the mask
    reaches the probe, and a verdict is per mask."""
    import importlib
    fa = importlib.import_module(
        "split_learning_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "_vmem_limit_bytes", lambda: 96 * 1024 * 1024)
    monkeypatch.setattr(fa, "use_interpret", lambda: False)
    probed = []
    monkeypatch.setattr(fa, "_onepass_compile_ok",
                        lambda *a: probed.append(a) or a[-1][2] != 512)
    q = jnp.zeros((1, 8192, 4, 128), jnp.bfloat16)
    kv = jnp.zeros((1, 8192, 2, 128), jnp.bfloat16)
    made = []
    monkeypatch.setattr(fa, "_make_flash", lambda *a, **kw: made.append(
        (a[5], kw["onepass"])) or (lambda q, k, v: (
            (q, q[..., 0]) if kw["with_lse"] else q)))
    fa.flash_attention(q, kv, kv, causal=True, window=512)
    fa.flash_attention(q, kv, kv, causal=True)
    fa.flash_attention_with_lse(q, kv, kv, causal=True, strict=True)
    assert [p[-1] for p in probed] == [
        (True, False, 512), (True, False, 512),   # refused at 1024 and 512
        (True, False, None), (True, True, None)]
    assert {p[:6] for p in probed[2:]} == {(8192, 128, 1024, "bfloat16", 2, 2)}
    assert made == [(512, False), (1024, True), (1024, True)]


def test_resolve_block_caps_split_form(monkeypatch):
    """When the two-kernel split carries the gradient, the whole
    program drops to the proven _SPLIT_BLOCK_MAX edge (the blk-1024
    sweep legs all ran the one-pass backward, so 1024 evidence does
    not cover _dq_kernel/_dkv_kernel)."""
    import importlib
    fa = importlib.import_module(
        "split_learning_tpu.ops.flash_attention")
    # default path, one-pass selected (interpret mode skips the probe):
    # the swept 1024 edge stands
    monkeypatch.setattr(fa, "use_interpret", lambda: True)
    assert fa._resolve_block(2048, 128, jnp.bfloat16) == (1024, True)
    # force the split form: the edge must drop to the proven 512
    monkeypatch.setattr(fa, "ONEPASS", False)
    assert fa._resolve_block(2048, 128, jnp.bfloat16) == (512, False)
    # ragged T already below the cap: unchanged
    assert fa._resolve_block(640, 128, jnp.bfloat16) == (128, False)


@pytest.mark.slow
def test_onepass_vmem_limit_reaches_mosaic():
    """The raised scoped-VMEM limit must actually reach the compiler:
    lower the one-pass backward for the TPU platform (jax.export needs
    no TPU device) and assert the Mosaic custom call's backend config
    carries ``scoped_memory_configs`` with the requested byte size —
    the serialization contract verified against jax's tpu_custom_call
    (jax/_src/tpu_custom_call.py, scoped_memory_configs). Round 4's
    on-chip failure showed the 16 MiB *default* enforced; this pins
    the request side of the fix off-chip."""
    import importlib
    fa = importlib.import_module(
        "split_learning_tpu.ops.flash_attention")
    tp, dp, block = 1024, 128, 512
    seq = jax.ShapeDtypeStruct((1, tp, dp), jnp.bfloat16)
    row = jax.ShapeDtypeStruct((1, tp, fa._ROWW), jnp.float32)
    # interpret-mode pallas_call (the CPU default) never emits the
    # custom call; build the compiled form explicitly
    import split_learning_tpu.ops.common as common
    orig = common.use_interpret
    try:
        common.use_interpret = lambda: False
        fa.use_interpret = common.use_interpret
        call = fa._onepass_call(1, tp, tp, dp, block, 1.0, False, False,
                                jnp.bfloat16)
        exp = jax.export.export(jax.jit(call), platforms=["tpu"])(
            seq, seq, seq, seq, row, row)
    finally:
        common.use_interpret = orig
        fa.use_interpret = orig
    txt = exp.mlir_module()
    assert "scoped_memory_configs" in txt
    assert str(fa._vmem_limit_bytes()) in txt


def test_auto_attention_selection():
    """attn='auto' resolves per shape by two rules: flash at/past the
    measured round-4 speed crossover (_FLASH_SPEED_T, regardless of
    HBM headroom), and flash wherever dense's quadratic backward
    buffers threaten HBM; dense otherwise."""
    from split_learning_tpu.ops.flash_attention import select_attention

    hbm = 16 * 1024 ** 3
    # the measured facts (bench_tpu_transformer_2026-08-01 +
    # tpu_window_runs.jsonl): flash wins on compiled-Mosaic speed at
    # every both-sides-measured T >= 1024, so on the chip
    # (interpret=False) the pin sits at 1024 even when dense fits
    tpu = dict(hbm_bytes=hbm, interpret=False)
    assert select_attention(16, 1024, 2, 2, **tpu) == "flash"
    assert select_attention(16, 4096, 2, 2, **tpu) == "flash"
    assert select_attention(16, 16384, 2, 2, **tpu) == "flash"
    assert select_attention(1, 8192, 1, 2, hbm_bytes=100 * hbm,
                            interpret=False) == "flash"
    # below the speed crossover with huge HBM: dense (T=256 measured
    # dense-ahead, 353 vs 204)
    assert select_attention(1, 512, 1, 2, hbm_bytes=100 * hbm,
                            interpret=False) == "full"
    # the speed rule is compiled-Mosaic-only: on interpreter backends
    # (this CPU test process resolves interpret=True by default) auto
    # keeps XLA dense at speed-rule shapes...
    assert select_attention(16, 4096, 2, 2, hbm_bytes=hbm) == "full"
    assert select_attention(16, 4096, 2, 2, hbm_bytes=hbm,
                            interpret=True) == "full"
    # ...while the HBM rule stays universal — dense's quadratic
    # backward buffers threatening memory force flash on any backend
    assert select_attention(512, 512, 8, 4, hbm_bytes=hbm,
                            interpret=True) == "flash"
    assert select_attention(16, 16384, 2, 2, hbm_bytes=hbm,
                            interpret=True) == "flash"


@pytest.mark.slow
def test_transformer_auto_matches_dense_at_small_t():
    """attn='auto' at T=32 resolves to dense: the trainer's loss series
    is bit-identical to attn='full'."""
    from split_learning_tpu.models.transformer import transformer_plan
    from split_learning_tpu.runtime.fused import FusedSplitTrainer
    from split_learning_tpu.utils import Config

    rs = np.random.RandomState(1)
    xs = rs.randint(0, 256, (2, 8, 32)).astype(np.int32)
    ys = rs.randint(0, 10, (2, 8)).astype(np.int32)
    cfg = Config(mode="split", model="transformer", batch_size=8,
                 attn="auto")
    dense = FusedSplitTrainer(transformer_plan(), cfg,
                              jax.random.PRNGKey(0), xs[0])
    auto = FusedSplitTrainer(transformer_plan(attn="auto"), cfg,
                             jax.random.PRNGKey(0), xs[0])
    for i in range(2):
        assert auto.train_step(xs[i], ys[i]) == dense.train_step(xs[i], ys[i])


@pytest.mark.slow
def test_transformer_trains_with_flash_attn():
    """attn='flash' is a drop-in for the model family: same init, loss
    matches the dense-attention trainer step for step."""
    from split_learning_tpu.models.transformer import transformer_plan
    from split_learning_tpu.runtime.fused import FusedSplitTrainer
    from split_learning_tpu.utils import Config

    rs = np.random.RandomState(0)
    xs = rs.randint(0, 256, (3, 8, 32)).astype(np.int32)
    ys = rs.randint(0, 10, (3, 8)).astype(np.int32)
    cfg = Config(mode="split", model="transformer", batch_size=8,
                 attn="flash")
    dense = FusedSplitTrainer(transformer_plan(), cfg,
                              jax.random.PRNGKey(0), xs[0])
    flash = FusedSplitTrainer(transformer_plan(attn="flash"), cfg,
                              jax.random.PRNGKey(0), xs[0])
    for i in range(3):
        ld = dense.train_step(xs[i], ys[i])
        lf = flash.train_step(xs[i], ys[i])
        np.testing.assert_allclose(lf, ld, atol=5e-5, rtol=5e-5)


def test_with_lse_strict_requires_causal():
    """strict refines the causal mask; without causal it must be a loud
    error, never silently-unmasked attention."""
    from split_learning_tpu.ops.flash_attention import (
        flash_attention_with_lse)

    q, k, v = qkv(t=8)
    with pytest.raises(ValueError, match="causal"):
        flash_attention_with_lse(q, k, v, causal=False, strict=True)
