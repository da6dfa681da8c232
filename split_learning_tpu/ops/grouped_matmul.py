"""Grouped matrix products — the routed expert layer's hot op.

``grouped_matmul(lhs, rhs, group_sizes)`` multiplies consecutive row
groups of ``lhs [m, k]`` by their own matrix of ``rhs [g, k, n]``: rows
``[sum(sizes[:i]), sum(sizes[:i+1]))`` by ``rhs[i]``. The groups need
not fill ``lhs``: a routed layer picks, on the device, the smaller of
two static buffer sizes if it holds what the routing sends to the
experts held here, else the worst case of tokens x experts per token
rows (models/afmoe.py). Rows past ``sum(group_sizes)`` come back as
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
_TM, _TK, _TN = 512, 1024, 1024
# ``tgmm`` writes float32 [tk, tn] tiles and holds a float32 accumulator
# of the same size: 1024 x 1024 would pass Mosaic's 16 MiB scoped
# default, 512 x 1024 stays under it.
_TGMM_TK = 512


def _tiles(m: int, k: int, n: int) -> tuple[int, int, int]:
    tm = _TM if m >= _TM else round_up(m, 16)
    return tm, min(k, _TK), min(n, _TN)


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
    tm, tk, tn = tiling
    g = g.astype(lhs.dtype)
    d_lhs = _mb_gmm(g, rhs_c, group_sizes, lhs.dtype, (tm, tn, tk),
                    transpose_rhs=True, interpret=use_interpret())
    d_rhs = _mb_tgmm(lhs.swapaxes(0, 1), g, group_sizes, jnp.float32,
                     (tm, min(tk, _TGMM_TK), tn),
                     num_actual_groups=rhs_c.shape[0],
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
