"""Grouped matrix products — the routed expert layer's hot op.

``grouped_matmul(lhs, rhs, group_sizes)`` multiplies consecutive row
groups of ``lhs [m, k]`` by their own matrix of ``rhs [g, k, n]``: rows
``[sum(sizes[:i]), sum(sizes[:i+1]))`` by ``rhs[i]``. The groups need
not fill ``lhs``: a routed layer picks, on the device, the smallest of
up to three static buffer sizes that holds what the routing sends to
the experts held here (twice the even share, twice that, and the worst
case of tokens x experts per token rows: models/afmoe.pair_rungs, whose
header says why three). Rows past ``sum(group_sizes)`` come back as
zeros and take no gradient, and the work follows the rows that are
filled, not ``m``.

The kernels are ``jax.experimental.pallas.ops.tpu.megablox``'s ``gmm``
(forward, and the gradient of ``lhs`` with ``rhs`` transposed) and
``tgmm`` (the gradient of ``rhs``): their grid's row-tile extent is the
number of *active* tiles, a runtime value computed from ``group_sizes``,
so one compiled program serves every routing. What this module adds is
the contract around them: the unfilled rows' zeros (the kernels leave
them unwritten: NaN is what the interpreter shows there), the tile
choice, ``rhs`` in float32 with its gradient accumulated and returned in
float32, and interpret mode off-TPU like every kernel of this package.
:func:`grouped_matmul_reference` is the plain loop the tests compare
with.

The tiles (:func:`_tiles`, one rule from the shape). The row tile is
``_TM``, and it is also the unit ``models/afmoe.pair_rungs`` counts a
routed layer's rows in, so it does not follow the width. Each of the
``k`` and ``n`` tiles is the dimension itself where that is no more than
a cap (768, 1024 and 1536 stand whole), else the largest multiple of
128, from 256 up, that is no more than the cap and divides the dimension
(2048 in tiles of 1024), else 1024. The cap is 1536 where the tiles of
the product and of its two gradients fit ``_VMEM`` (two buffers of every
operand's and the output's tile and the float32 accumulator), else 1024,
under which 1536 goes in tiles of 768. A tile that does not divide costs
a whole tile's product for the part that is left: megablox rounds the
tile count up (``_calculate_irregular_num_tiles``), so 1536 columns in
tiles of 1024 run a second ``[tm, tk] x [tk, 1024]`` product for 512 of
them, and 1536 contracted in tiles of 1024 runs its last ``k`` step
whole with half of both operands zeroed first (``mask_k_rem``: two more
passes over the loaded tiles), a third more MXU work than the model asks
for either way. :func:`tile_fill` is that ratio, useful over run, from
the shapes alone; only a width over the cap with no such divisor (1664)
still pays it. Whole beats halves where it fits: one grid step where
two, and ``lhs`` is read once a column tile. The gradients' tilings
follow from the product's own (:func:`_bwd_tiles`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox.gmm import (
    gmm as _mb_gmm, tgmm as _mb_tgmm)

from split_learning_tpu.ops.common import pad_axis, round_up, use_interpret

# Row-tile edge. A routed layer's groups average a few hundred rows
# (8192 tokens x 8 / 128 experts = 512), and every group boundary inside
# a tile costs one more visit of that tile, so the edge stays at the
# mean group: 512 rows against [1024, 1024] weight tiles is 512 FLOP a
# weight byte, over the v5e's 240 at the roofline's knee.
_TM = 512
# Caps on a ``k`` or ``n`` tile's edge (multiples of ``_LANES``), the
# widest first: ``_tiles`` takes the first under which every call's
# tiles fit ``_VMEM``.
_CAPS = (1536, 1024)
_LANES, _MIN_TILE = 128, 256    # the divisors ``_dividing`` looks among
# ``tgmm`` writes float32 [tk, tn] tiles and holds a float32 accumulator
# of the same size, so its ``k`` tile is held to this edge.
_TGMM_TK = 512
# What a call's tiles may take of Mosaic's 16 MiB scoped default, by
# ``_gmm_bytes`` / ``_tgmm_bytes``: ``gmm`` at (512, 1024, 1536), 14 MiB
# by that count, compiles and runs (PR 38); ``tgmm`` at 1024 x 1024
# tiles, 16 MiB, does not (PR 26).
_VMEM = 14 * 2 ** 20


def _dividing(dim: int, cap: int) -> int:
    """The tile of a ``k`` or ``n`` dimension (the module docstring): the
    dimension whole where the cap allows, else the largest multiple of a
    lane tile under the cap that divides it, else 1024."""
    if dim <= cap:
        return dim
    whole = (t for t in range(cap, _MIN_TILE - 1, -_LANES) if dim % t == 0)
    return next(whole, _CAPS[-1])


def _gmm_bytes(tm: int, tk: int, tn: int) -> int:
    """VMEM of a ``gmm`` call's tiles: both operands' and the output's
    bfloat16 tiles twice (the pipeline's two buffers), the float32
    accumulator once."""
    return 4 * (tm * tk + tk * tn + tm * tn) + 4 * tm * tn


def _tgmm_bytes(tm: int, tk: int, tn: int) -> int:
    """The same for ``tgmm``: its ``[tk, tn]`` output is float32."""
    return 4 * (tm * tk + tm * tn) + 12 * tk * tn


def _tiles(m: int, k: int, n: int) -> tuple[int, int, int]:
    tm = _TM if m >= _TM else round_up(m, 16)
    for cap in _CAPS:
        tiling = tm, _dividing(k, cap), _dividing(n, cap)
        rows, weights = _bwd_tiles(tiling)
        if max(_gmm_bytes(*tiling), _gmm_bytes(*rows),
               _tgmm_bytes(*weights)) <= _VMEM:
            break
    return tiling


def _bwd_tiles(tiling: tuple) -> tuple[tuple, tuple]:
    """The tilings of a product's two gradients from its own: the rows'
    (``gmm`` with ``rhs`` transposed contracts over ``n`` and writes
    ``k`` columns, so the two tiles swap) and the weights' (``tgmm``
    writes ``[tk, tn]`` float32 tiles: ``_TGMM_TK``)."""
    tm, tk, tn = tiling
    return (tm, tn, tk), (tm, min(tk, _TGMM_TK), tn)


def tile_fill(m: int, k: int, n: int, tiling=None) -> float:
    """Useful over run MXU work of ``[m, k] x [k, n]`` under ``tiling``
    (:func:`_tiles`' where none is given): ``k n`` over the extents that
    whole ``k`` and ``n`` tiles cover. 1.0 where both tiles divide, 0.75
    for 1536 in tiles of 1024; the rows' part is the groups' and no
    function of the shape."""
    _, tk, tn = tiling or _tiles(m, k, n)
    return k * n / (round_up(k, tk) * round_up(n, tn))


def _zero_unfilled(out: jax.Array, group_sizes: jax.Array) -> jax.Array:
    rows = jax.lax.broadcasted_iota(jnp.int32, (out.shape[0], 1), 0)
    return jnp.where(rows < jnp.sum(group_sizes), out, 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm(lhs, rhs, group_sizes, tiling):
    return _gmm_fwd(lhs, rhs, group_sizes, tiling)[0]


def _gmm_fwd(lhs, rhs, group_sizes, tiling):
    rhs_c = rhs.astype(lhs.dtype)
    out = _mb_gmm(lhs, rhs_c, group_sizes, lhs.dtype, tiling,
                  interpret=use_interpret())
    return _zero_unfilled(out, group_sizes), (lhs, rhs_c, group_sizes)


def _gmm_bwd(tiling, res, g):
    lhs, rhs_c, group_sizes = res
    rows_tiling, weights_tiling = _bwd_tiles(tiling)
    g = g.astype(lhs.dtype)
    d_lhs = _mb_gmm(g, rhs_c, group_sizes, lhs.dtype, rows_tiling,
                    transpose_rhs=True, interpret=use_interpret())
    d_rhs = _mb_tgmm(lhs.swapaxes(0, 1), g, group_sizes, jnp.float32,
                     weights_tiling, num_actual_groups=rhs_c.shape[0],
                     interpret=use_interpret())
    return _zero_unfilled(d_lhs, group_sizes), d_rhs, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(lhs: jax.Array, rhs: jax.Array,
                   group_sizes: jax.Array) -> jax.Array:
    """``[m, k] x [g, k, n] -> [m, n]`` by row groups (module docstring).

    ``lhs`` carries the compute type (bfloat16 on the chip); ``rhs`` may
    be float32 weights: they are rounded to ``lhs.dtype`` for the
    products and their gradient comes back in float32.
    ``group_sizes`` is ``[g]`` int32 with ``sum <= m``."""
    m, k = lhs.shape
    tiling = _tiles(m, k, rhs.shape[2])
    padded = pad_axis(lhs, 0, round_up(m, tiling[0]))
    rhs = rhs.astype(jnp.float32)   # one cotangent type whatever came in
    return _gmm(padded, rhs, group_sizes.astype(jnp.int32), tiling)[:m]


def grouped_matmul_reference(lhs: jax.Array, rhs: jax.Array,
                             group_sizes: jax.Array) -> jax.Array:
    """The same contract as a plain loop over the groups: every group's
    dense product, masked to the group's rows."""
    ends = jnp.cumsum(group_sizes)
    rows = jnp.arange(lhs.shape[0])[:, None]
    out = jnp.zeros((lhs.shape[0], rhs.shape[2]), lhs.dtype)
    for i in range(rhs.shape[0]):
        inside = (rows >= ends[i] - group_sizes[i]) & (rows < ends[i])
        out = out + jnp.where(inside, lhs @ rhs[i].astype(lhs.dtype), 0)
    return out
