"""State-space duality — the recurrence of a Mamba-2 layer in its chunked
form: four matrix products a chunk and the chunks' states carried from one
to the next. Pallas TPU kernels where the shapes fill their tiles, forward
and backward under one ``custom_vjp``; plain ``jax.numpy``, differentiated
by ``jax`` itself, at any other shape.

For every sequence and head ``h`` of ``H`` (head ``h`` reads group ``h //
(H / G)`` of ``b`` and ``c``; ``a[h] < 0`` is one scalar a head, the state
``S`` is ``[P, N]`` a head)::

    S_t = exp(dt_t[h] * a[h]) * S_{t-1} + dt_t[h] * x_t[h] b_t^T,   S_{-1} = 0
    y_t[h] = S_t c_t + d_skip[h] * x_t[h]

:func:`ssd_reference` is that recurrence token by token (``lax.scan``):
what the tests hold the chunked form against, and what nothing calls on
the chip. ops/selective_scan.py computes Mamba-1's recurrence (a decay a
channel *and* state, a state of 16 a channel) on the vector unit at about
29 operations a channel, state and token; a state of ``128 x 4096`` a token
is 6.4 times PF's work there, with not one matrix product in it. One
scalar decay a head is what makes the products possible.

**The chunked form** (:func:`ssd_chunked`), over chunks of ``L`` tokens
with ``l_i = sum_{j <= i} dt_j a`` inside a chunk (so every ``exp`` below
has an argument ``<= 0``):

1. inside a chunk, ``Y_diag = ((C B^T) * exp(l_i - l_j)[i >= j]) (dt x)``:
   the scores ``C B^T`` once a group (``[L, N] x [N, L]``), the decays once
   a head, their product against the chunk's ``[L, P]`` inputs;
2. the state a chunk adds, ``sum_j exp(l_end - l_j) (dt x)_j b_j^T`` (``[P,
   L] x [L, N]`` a head);
3. the states carried from chunk to chunk by ``exp(l_end)``, elementwise
   over ``[H, P, N]``;
4. what the carried state gives a chunk's tokens, ``Y_off = exp(l_i) C_i
   S_prev`` (``[L, N] x [N, P]`` a head).

``dt``, ``a``, the cumulative sums, every ``exp`` and the carried states are
float32; the four products' operands are in ``x``'s type (bfloat16 on the
chip) and accumulate in float32. ``T`` pads to whole chunks with ``dt = 0``
(a token that leaves the state as it is and adds nothing).
:func:`ssd_product_flops` counts the four products from the shapes.

**The kernels** (``ssd_fwd``, ``ssd_bwd``: the names of their calls in a
trace, under the scope ``ssm_ssd``) run where ``L`` and ``N`` are multiples
of 128 lanes, a head's ``P`` divides 128 and a group's ``(H / G) x P``
channels are whole lane tiles (the published 128, 128, 8 x 64), and the
operands share one type. One grid step is one (sequence, group, chunk), the
chunk innermost and sequential:

- it reads the chunk's ``c`` and ``b`` ``[L, N]`` and the group's ``x [L,
  (H / G) P]`` once, ``dt`` and ``l`` (the cumulative sum, made outside by
  XLA: ``[B, T, H]`` float32, tiny; a product with a triangle of ones at
  full float32 precision, whose transpose is as cheap, where XLA's own
  cumulative sum is a reduce-window that costs 1.0 ms a layer forward and
  reversed) with the tokens both on sublanes and in lanes; forms the scores
  once, and a head at a time the decays, their masked product with the
  scores and ``Y_diag``, all in VMEM; the heads' columns are taken two to a
  128-lane tile (``P`` 64), each product at the tile's width and the head's
  lanes selected after it;
- the state ``[N, (H / G) P]`` float32 (256 KB) is a VMEM scratch carried
  over the chunks: a step reads it for ``Y_off`` and leaves ``exp(l_end) S +
  B^T (w dt x)`` (one product for the group's heads). The other form, a
  ``lax.scan`` between two kernels, writes every chunk's added state in
  float32 and reads it back around 64 elementwise steps: PERF.md, Findings
  PR 42, has both by the kernels alone;
- it writes ``y`` in float32 and, for the backward, the state the chunk
  starts from in the products' type (67 MB a layer at the benchmark's
  sizes). **Nothing of shape ``[chunks, H, L, L]`` reaches HBM**: not the
  decays, the scores or their product.

The backward visits the chunks last to first with the cotangent of the
state carried the same way, makes the scores, the decays and their product
again from the residuals (the operands, ``l`` and the chunks' first
states) and gives all six gradients: ``x``, ``b`` and ``c`` whole (a
group's heads summed by the products' contraction over its channels),
``dt`` and ``l`` a token and head (their sums over a head's channels are the
kernel's: row sums in lanes, column sums on sublanes), ``d_skip`` and the
part of ``l_end``'s a channel, which XLA sums over the 64 channels of a
head. ``a``'s follows from ``l``'s through the cumulative sum outside. A
cotangent enters a product in the operands' type, as the MXU takes a
float32 operand at XLA's default precision; every sum and elementwise step
is float32.

Any other shape (the CPU tests' chunks of 8 and 16, a rehearsal's 20
tokens in chunks of 8) takes :func:`_ssd_plain`: the same four steps as
batched ``einsum``s, which writes the decays ``[T / L, H, L, L]`` in
float32 and their product with the scores in the compute type, a layer.
It is also what tests/test_ssd.py holds the kernels against.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from split_learning_tpu.obs import spans
from split_learning_tpu.ops.common import (
    LANE, pad_axis, round_up, traced_once, use_interpret)

_F32 = jnp.float32


def ssd_product_flops(t: int, heads: int, head_dim: int, groups: int,
                      state: int, chunk: int) -> int:
    """Operations of the chunked form's four products over ``t`` tokens,
    forward: the scores once a group, their product with the chunk's
    inputs, the chunk states and the carried state's part, once a head."""
    return 2 * t * (groups * chunk * state + heads * chunk * head_dim
                    + 2 * heads * head_dim * state)


def fills_tiles(chunk: int, state: int, per_group: int, head_dim: int) -> bool:
    """Whether the kernels' tiling holds these sizes (the module header)."""
    return (chunk % LANE == 0 and state % LANE == 0 and LANE % head_dim == 0
            and (per_group * head_dim) % LANE == 0)


def ssd_chunked(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                c: jax.Array, d_skip: jax.Array, chunk: int) -> jax.Array:
    """``x [B, T, H, P]``, ``dt [B, T, H]`` (after its softplus), ``a [H]``,
    ``b`` and ``c`` ``[B, T, G, N]``, ``d_skip [H]`` -> ``y [B, T, H, P]``
    in float32 (the module header)."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2:]
    if h % g:
        raise ValueError(f"{g} groups do not divide {h} heads")
    chunks = round_up(t, chunk) // chunk
    # [B, chunks, L, ...], the heads by group
    fold = lambda v, *rest: pad_axis(v, 1, chunks * chunk).reshape(
        bsz, chunks, chunk, *rest)
    form = _ssd_plain
    if fills_tiles(chunk, n, h // g, p) and x.dtype == b.dtype == c.dtype:
        form = _ssd_kernels
    with jax.named_scope(spans.SSM_SSD):
        y = form(fold(x, g, h // g, p), fold(dt.astype(_F32), g, h // g),
                 a.astype(_F32).reshape(g, h // g), fold(b, g, n),
                 fold(c, g, n), d_skip.astype(_F32).reshape(g, h // g))
    return y.reshape(bsz, chunks * chunk, h, p)[:, :t]


def _ssd_plain(x, dt, a, b, c, d_skip):
    """The four steps as batched products over folded operands: ``x [B,
    chunks, L, G, R, P]``, ``dt [B, chunks, L, G, R]``, ``a`` and ``d_skip``
    ``[G, R]``, ``b`` and ``c`` ``[B, chunks, L, G, N]`` -> ``y`` of ``x``'s
    shape, float32."""
    chunk, dtype = x.shape[2], x.dtype
    # l [B, chunks, G, R, L]: the log of the decay since the chunk began
    l = jnp.cumsum(dt * a, axis=2).transpose(0, 1, 3, 4, 2)
    dx = x.astype(_F32) * dt[..., None]
    # 1. inside a chunk
    scores = jnp.einsum("zclgn,zcsgn->zcgls", c, b,
                        preferred_element_type=_F32)
    since = l[..., :, None] - l[..., None, :]
    live = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(live, since, -jnp.inf))
    y = jnp.einsum("zcgrls,zcsgrp->zclgrp",
                   (scores[:, :, :, None] * decay).astype(dtype),
                   dx.astype(dtype), preferred_element_type=_F32)
    # 2. the state each chunk adds, [B, chunks, G, R, P, N]
    to_end = jnp.exp(l[..., -1:] - l).transpose(0, 1, 4, 2, 3)
    added = jnp.einsum("zcsgn,zcsgrp->zcgrpn", b,
                       (dx * to_end[..., None]).astype(dtype),
                       preferred_element_type=_F32)

    # 3. the state each chunk starts from
    def carry(state, chunk_):
        kept, new = chunk_
        return state * kept[..., None, None] + new, state

    _, before = jax.lax.scan(
        carry, jnp.zeros_like(added[:, 0]),
        (jnp.exp(l[..., -1]).swapaxes(0, 1), added.swapaxes(0, 1)))
    # 4. what it gives the chunk's tokens
    y = y + jnp.einsum(
        "zclgn,zcgrpn->zclgrp", c, before.swapaxes(0, 1).astype(dtype),
        preferred_element_type=_F32
    ) * jnp.exp(l).transpose(0, 1, 4, 2, 3)[..., None]
    return y + d_skip[..., None] * x.astype(_F32)


# ------------------------------------------------------------------ #
# the kernels: a block is one chunk of one group. ``x``, ``y`` and their
# cotangents are ``[L, R P]`` (a head's P channels side by side), ``b`` and
# ``c`` ``[L, N]``, the state ``[N, R P]``; a ``cols`` array is ``[L, R]``
# (tokens on sublanes, a head a lane), a ``rows`` array ``[R, L]``.

def _dot(u, v, cu: int, cv: int):
    """``u`` and ``v`` contracted over their axes ``cu`` and ``cv``,
    accumulated in float32."""
    return jax.lax.dot_general(u, v, (((cu,), (cv,)), ((), ())),
                               preferred_element_type=_F32)


def _lanes(rows: int):
    return jax.lax.broadcasted_iota(jnp.int32, (rows, LANE), 1)


def _tile_of_heads(parts, p: int):
    """One ``[L, 128]`` tile whose lanes ``[j p, (j + 1) p)`` are
    ``parts[j]``'s (each a ``[L, 128]`` or ``[L, 1]`` value)."""
    rows = max(v.shape[0] for v in parts)
    tile = jnp.broadcast_to(parts[0], (rows, LANE))
    for j, part in enumerate(parts[1:], 1):
        tile = jnp.where(_lanes(rows) >= j * p, part, tile)
    return tile


def _wide(cols, p: int):
    """``[L, R] -> [L, R P]``: head ``h``'s column over its ``P`` lanes."""
    per_tile = LANE // p
    return jnp.concatenate([
        _tile_of_heads([cols[:, h:h + 1] for h in range(k, k + per_tile)], p)
        for k in range(0, cols.shape[1], per_tile)], axis=1)


def _own_lanes(tile, j: int, p: int):
    """``tile [L, 128]`` with the lanes of every head but its ``j``-th
    zeroed."""
    if p == LANE:
        return tile
    lanes = _lanes(tile.shape[0])
    return jnp.where((lanes >= j * p) & (lanes < (j + 1) * p), tile, 0.0)


class _Chunk(NamedTuple):
    """What both passes make first of a chunk's blocks: ``x`` and its
    float32, ``b``, ``c``, ``l`` and ``dt`` a channel ``[L, R P]``, the
    chunk's last ``l`` ``[1, R P]``, ``dx = dt x`` in float32, the pairs ``i
    >= j`` and the scores ``c b^T``."""
    x: jax.Array
    x32: jax.Array
    b: jax.Array
    c: jax.Array
    l_w: jax.Array
    dt_w: jax.Array
    l_end: jax.Array
    dx: jax.Array
    live: jax.Array
    scores: jax.Array


def _chunk_parts(x_ref, dtc_ref, lc_ref, b_ref, c_ref, p: int) -> _Chunk:
    x, bb, cc = x_ref[0], b_ref[0], c_ref[0]
    chunk, x32 = x.shape[0], x.astype(_F32)
    l_w = _wide(lc_ref[0, 0, 0], p)
    dt_w = _wide(dtc_ref[0, 0, 0], p)
    live = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))
    return _Chunk(x, x32, bb, cc, l_w, dt_w, l_w[chunk - 1:chunk], x32 * dt_w,
                  live, _dot(cc, bb, 1, 1))


def _decay(live, lc, lr, h: int):
    """Head ``h``'s ``exp(l_i - l_j)`` over the pairs ``i >= j``, ``[L, L]``."""
    return jnp.exp(jnp.where(live, lc[:, h:h + 1] - lr[h:h + 1, :], -jnp.inf))


def _chunk_y(p: int, at: _Chunk, lc, lr, before, skip):
    """``y [L, R P]`` of one chunk: ``l`` as cols and as rows, the state
    ``before`` the chunk in the products' type and the skip ``[1, R P]``;
    steps 1 and 4 and the skip, in the plain form's order."""
    dtype, per_tile = at.x.dtype, LANE // p
    dxb = at.dx.astype(dtype)
    tiles = []
    for k in range(0, lc.shape[1], per_tile):
        lanes = slice(k * p, k * p + LANE)
        tiles.append(_tile_of_heads([_dot(
            (at.scores * _decay(at.live, lc, lr, h)).astype(dtype),
            dxb[:, lanes], 1, 0) for h in range(k, k + per_tile)], p))
    y = jnp.concatenate(tiles, axis=1)
    y = y + _dot(at.c, before, 1, 0) * jnp.exp(at.l_w)
    return y + skip * at.x32


def _added(at: _Chunk):
    """The state ``[N, R P]`` a chunk adds (step 2)."""
    return _dot(at.b, (at.dx * jnp.exp(at.l_end - at.l_w)).astype(at.x.dtype),
                0, 0)


def _fwd_kernel(p: int, x_ref, dtc_ref, lc_ref, lr_ref, b_ref, c_ref,
                skip_ref, y_ref, before_ref, s_ref):
    """One chunk of one group: ``s_ref`` holds the state the chunk starts
    from, which is also what the backward needs of it."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        s_ref[:] = jnp.zeros_like(s_ref)

    at = _chunk_parts(x_ref, dtc_ref, lc_ref, b_ref, c_ref, p)
    before = s_ref[:].astype(x_ref.dtype)
    before_ref[0, 0, 0] = before
    y_ref[0] = _chunk_y(p, at, lc_ref[0, 0, 0], lr_ref[0, 0, 0], before,
                        skip_ref[0])
    s_ref[:] = s_ref[:] * jnp.exp(at.l_end) + _added(at)


def _bwd_kernel(p: int, x_ref, dy_ref, dtc_ref, lc_ref, lr_ref, b_ref, c_ref,
                skip_ref, before_ref,
                dx_ref, ddt_ref, dlc_ref, dlr_ref, db_ref, dc_ref, dskip_ref,
                dlend_ref, ds_ref):
    """One chunk, visited after the chunks that follow it: ``ds_ref`` is the
    cotangent of the state the chunk leaves. ``dlc_ref`` takes what ``l_i``
    receives as a row's index and ``dlr_ref`` as a column's (a token and
    head), ``dlend_ref`` what the chunk's last ``l`` receives, a channel."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        ds_ref[:] = jnp.zeros_like(ds_ref)
        dskip_ref[0, 0] = jnp.zeros(dskip_ref.shape[2:], _F32)

    at = _chunk_parts(x_ref, dtc_ref, lc_ref, b_ref, c_ref, p)
    x32, bb, cc, dx, scores = at.x32, at.b, at.c, at.dx, at.scores
    dtype, per_tile, chunk = at.x.dtype, LANE // p, at.x.shape[0]
    lc, lr = lc_ref[0, 0, 0], lr_ref[0, 0, 0]
    dy, before, ds = dy_ref[0], before_ref[0, 0, 0], ds_ref[:]
    since, to_end, kept = (jnp.exp(at.l_w), jnp.exp(at.l_end - at.l_w),
                           jnp.exp(at.l_end))
    dxb, dyb, dsb = dx.astype(dtype), dy.astype(dtype), ds.astype(dtype)
    # 4. y_off = exp(l) (c before)
    dz = (dy * since).astype(dtype)
    y_off = _dot(cc, before, 1, 0) * since
    dc = _dot(dz, before, 1, 1)
    # 2 and 3. the state the chunk leaves, exp(l_end) before + b^T (w dx)
    ddxw = _dot(bb, dsb, 1, 0) * to_end     # what dx receives through it
    db = _dot((dx * to_end).astype(dtype), dsb, 1, 1)
    ds_ref[:] = ds * kept + _dot(cc, dz, 0, 0)
    to_w = ddxw * dx                        # what w = exp(l_end - l_j) does
    dlend_ref[0, 0, 0] = (
        jnp.sum(ds * before.astype(_F32), axis=0, keepdims=True) * kept
        + jnp.sum(to_w, axis=0, keepdims=True))
    to_l = dy * y_off - to_w                # l_i's part outside the decays
    # 1. inside the chunk, a head at a time
    dscores = jnp.zeros_like(scores)
    dlc, dlr, ddt = jnp.zeros_like(lc), jnp.zeros_like(lr), jnp.zeros_like(lc)
    head_lane = jax.lax.broadcasted_iota(jnp.int32, lc.shape, 1)
    head_row = jax.lax.broadcasted_iota(jnp.int32, lr.shape, 0)
    over_lanes = lambda v: jnp.sum(v, axis=1, keepdims=True)
    tiles = []
    for k in range(0, lc.shape[1], per_tile):
        lanes = slice(k * p, k * p + LANE)
        parts = []
        for j, h in enumerate(range(k, k + per_tile)):
            decay = _decay(at.live, lc, lr, h)
            masked = scores * decay
            dm = _dot(_own_lanes(dy[:, lanes], j, p).astype(dtype),
                      dxb[:, lanes], 1, 1)
            dscores = dscores + dm * decay
            moved = dm * masked             # what exp(l_i - l_j) receives
            parts.append(_dot(masked.astype(dtype), dyb[:, lanes], 0, 0))
            outside = _own_lanes(to_l[:, lanes], j, p)
            # one reduction over the lanes where the two are of one shape
            dlc = jnp.where(head_lane == h, (
                over_lanes(moved + outside) if chunk == LANE
                else over_lanes(moved) + over_lanes(outside)), dlc)
            dlr = jnp.where(head_row == h,
                            -jnp.sum(moved, axis=0, keepdims=True), dlr)
            ddt = jnp.where(head_lane == h, over_lanes(_own_lanes(
                (parts[-1] + ddxw[:, lanes]) * x32[:, lanes], j, p)), ddt)
        tiles.append(_tile_of_heads(parts, p))
    ddx = jnp.concatenate(tiles, axis=1) + ddxw
    dx_ref[0] = (ddx * at.dt_w + skip_ref[0] * dy).astype(dx_ref.dtype)
    ddt_ref[0, 0, 0], dlc_ref[0, 0, 0], dlr_ref[0, 0, 0] = ddt, dlc, dlr
    dscores = dscores.astype(dtype)
    dc_ref[0] = (dc + _dot(dscores, bb, 1, 0)).astype(dc_ref.dtype)
    db_ref[0] = (db + _dot(dscores, cc, 0, 0)).astype(db_ref.dtype)
    dskip_ref[0, 0] += jnp.sum(dy * x32, axis=0, keepdims=True)


_VMEM_LIMIT = 64 * 1024 * 1024   # the backward holds a dozen [L, R P] float32


@functools.lru_cache(maxsize=None)
def _make_ssd(bsz: int, chunks: int, chunk: int, g: int, r: int, p: int,
              n: int, dtype):
    """The custom-VJP recurrence for one static shape: ``(x [B, T, G R P],
    dt and l as cols [B, G, chunks, L, R], l as rows [B, G, chunks, R, L],
    b, c [B, T, G N], skip [G, 1, R P]) -> y [B, T, G R P]`` float32."""
    t, rp = chunks * chunk, r * p
    vmem = lambda shape, index: pl.BlockSpec(shape, index,
                                             memory_space=pltpu.VMEM)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, _F32)
    typed = lambda *shape: jax.ShapeDtypeStruct(shape, dtype)
    grid = (bsz, g, chunks)
    skip = vmem((1, 1, rp), lambda z, i, k: (i, 0, 0))
    fwd_kernel = traced_once(functools.partial(_fwd_kernel, p))
    bwd_kernel = traced_once(functools.partial(_bwd_kernel, p))

    def specs(time):
        """Blocks at grid point ``(z, i, k)``, chunk ``time(k)``: of a
        ``[B, T, G x width]`` array, of cols, of rows, of the states."""
        five = lambda *block: vmem(
            (1, 1, 1, *block), lambda z, i, k: (z, i, time(k), 0, 0))
        return (lambda width: vmem((1, chunk, width),
                                   lambda z, i, k: (z, time(k), i)),
                five(chunk, r), five(r, chunk), five(n, rp))

    def forward(x, dtc, lc, lr, b, c, skip_w):
        seq, cols, rows, states = specs(lambda k: k)
        return pl.pallas_call(
            fwd_kernel,
            out_shape=(f32(bsz, t, g * rp), typed(bsz, g, chunks, n, rp)),
            grid=grid,
            in_specs=[seq(rp), cols, cols, rows, seq(n), seq(n), skip],
            out_specs=(seq(rp), states),
            scratch_shapes=[pltpu.VMEM((n, rp), _F32)],
            compiler_params=params, interpret=use_interpret(), name="ssd_fwd",
        )(x, dtc, lc, lr, b, c, skip_w)

    def backward(x, dtc, lc, lr, b, c, skip_w, before, dy):
        seq, cols, rows, states = specs(lambda k: chunks - 1 - k)
        last = lambda z, i, k: (z, i, chunks - 1 - k, 0, 0)
        return pl.pallas_call(
            bwd_kernel,
            out_shape=(typed(bsz, t, g * rp), f32(bsz, g, chunks, chunk, r),
                       f32(bsz, g, chunks, chunk, r),
                       f32(bsz, g, chunks, r, chunk), typed(bsz, t, g * n),
                       typed(bsz, t, g * n), f32(bsz, g, 1, rp),
                       f32(bsz, g, chunks, 1, rp)),
            grid=grid,
            in_specs=[seq(rp), seq(rp), cols, cols, rows, seq(n), seq(n),
                      skip, states],
            out_specs=(seq(rp), cols, cols, rows, seq(n), seq(n),
                       vmem((1, 1, 1, rp), lambda z, i, k: (z, i, 0, 0)),
                       vmem((1, 1, 1, 1, rp), last)),
            scratch_shapes=[pltpu.VMEM((n, rp), _F32)],
            compiler_params=params, interpret=use_interpret(), name="ssd_bwd",
        )(x, dy, dtc, lc, lr, b, c, skip_w, before)

    @jax.custom_vjp
    def ssd(x, dtc, lc, lr, b, c, skip_w):
        return forward(x, dtc, lc, lr, b, c, skip_w)[0]

    def ssd_fwd(*operands):
        y, before = forward(*operands)
        return y, (*operands, before)

    def ssd_bwd(res, dy):
        dx, ddt, dlc, dlr, db, dc, dskip, dlend = backward(*res, dy)
        # the chunk's last l, a head: its channels summed
        dlc = dlc.at[:, :, :, chunk - 1].add(
            dlend.reshape(bsz, g, chunks, r, p).sum(-1))
        return dx, ddt, dlc, dlr, db, dc, dskip.sum(0)

    ssd.defvjp(ssd_fwd, ssd_bwd)
    return ssd


def _kernel_operands(x, dt, a, b, c, d_skip) -> tuple:
    """:func:`_ssd_plain`'s folded operands as :func:`_make_ssd`'s seven."""
    bsz, chunks, chunk, g, r, p = x.shape
    n, t = b.shape[-1], chunks * chunk
    # l as rows [B, G, chunks, R, L], summed along the lanes by a product
    # with ones on and above the diagonal at full float32 precision (a
    # product with 1.0 is exact, the sum is the accumulator's): XLA's own
    # cumulative sum is a reduce-window, 0.19 ms a layer here and 0.81 ms
    # reversed in the backward, 1.9 ms over [.., L, G, R]
    dt = dt.transpose(0, 3, 1, 4, 2)
    l = jnp.einsum("zgcrj,ji->zgcri", dt * a[:, None, :, None],
                   jnp.triu(jnp.ones((chunk, chunk), _F32)),
                   precision=jax.lax.Precision.HIGHEST)
    return (x.reshape(bsz, t, g * r * p), dt.swapaxes(-1, -2),
            l.swapaxes(-1, -2), l, b.reshape(bsz, t, g * n),
            c.reshape(bsz, t, g * n), jnp.repeat(d_skip, p, axis=1)[:, None])


def _ssd_kernels(x, dt, a, b, c, d_skip):
    """:func:`_ssd_plain`'s operands and result, through the kernels."""
    bsz, chunks, chunk, g, r, p = x.shape
    ssd = _make_ssd(bsz, chunks, chunk, g, r, p, b.shape[-1], x.dtype)
    return ssd(*_kernel_operands(x, dt, a, b, c, d_skip)).reshape(x.shape)


def ssd_reference(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                  c: jax.Array, d_skip: jax.Array) -> jax.Array:
    """The recurrence itself, one token a step, float32 (the module
    header); the shapes are :func:`ssd_chunked`'s."""
    h, g = x.shape[2], b.shape[2]
    f32 = lambda v: v.astype(_F32)
    by_head = lambda v: jnp.repeat(f32(v), h // g, axis=2).swapaxes(0, 1)
    x, dt, a, d_skip = f32(x), f32(dt), f32(a), f32(d_skip)

    def step(state, token):
        x_t, dt_t, b_t, c_t = token      # [B, H, P], [B, H], [B, H, N] twice
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None])
        y_t = jnp.einsum("zhpn,zhn->zhp", state, c_t,
                         precision=jax.lax.Precision.HIGHEST)
        return state, y_t + d_skip[:, None] * x_t

    start = jnp.zeros(x.shape[:1] + x.shape[2:] + b.shape[3:], _F32)
    _, y = jax.lax.scan(step, start, (x.swapaxes(0, 1), dt.swapaxes(0, 1),
                                      by_head(b), by_head(c)))
    return y.swapaxes(0, 1)
