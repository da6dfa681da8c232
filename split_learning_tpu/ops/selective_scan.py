"""Selective scan — the state-space recurrence of a Mamba layer as a
Pallas TPU kernel, forward and backward under one ``custom_vjp``.

For every sequence, channel ``d`` of ``d_inner`` and state ``n`` of
``d_state`` (float32 throughout)::

    s_t[d, n] = exp(delta_t[d] * a[d, n]) * s_{t-1}[d, n]
                + delta_t[d] * x_t[d] * b_t[n],        s_{-1} = 0
    y_t[d]    = sum_n s_t[d, n] * c_t[n] + d_skip[d] * x_t[d]

XLA has no form of it that fits a long sequence: an associative scan
writes ``[T, d_inner, d_state]`` float32 arrays (2.7 GB each at T 8192,
``d_inner`` 5120), a ``lax.scan`` over tokens is T sequential steps of
tiny operations, forward and again backward. Here the state never
leaves VMEM:

- grid ``(batch, channel block, time chunk)``, the chunk fastest; the
  state ``[d_state, channels]`` (states on sublanes, channels on lanes)
  is a VMEM scratch carried across the chunks of one channel block;
- a chunk's steps run in groups of eight rows: one aligned ``[8,
  channels]`` load of ``delta`` and ``x``, eight unrolled steps, one
  aligned store of ``y``; ``b_t`` and ``c_t`` arrive broadcast over 128
  lanes (``[T, d_state, 128]``), so that a step multiplies whole
  registers and broadcasts nothing across lanes;
- the forward saves the state at chunk edges only (``[T / chunk,
  d_state, d_inner]``); the backward visits the chunks last to first,
  makes a chunk's states again from its edge into a VMEM scratch and
  then walks the chunk backwards with the state's cotangent carried
  the same way. ``db`` and ``dc`` sum over channels: over the lanes in
  the kernel, over the channel blocks outside it.

The chunk (64 steps) and the channel block (the largest of 640, 512,
384, 256, 128 lanes that divides the padded ``d_inner``) follow from
the shapes; T pads to whole chunks with ``delta = 0`` (a step that
leaves the state as it is) and ``d_inner`` to whole lane tiles with
``a = 0``. The binding unit is the vector unit, not the MXU or HBM: a
step is ``d_state * d_inner`` exponentials and a dozen multiplies and
adds on as many elements, and nothing in it is a matrix product.

:func:`selective_scan_reference` is the same recurrence as a plain
``lax.scan`` over tokens: what the kernel is tested against
(tests/test_selective_scan.py, interpret mode off the chip), and what
nothing calls on the chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from split_learning_tpu.ops.common import (
    LANE, SUBLANE, pad_axis, round_up, use_interpret)

_CHUNK = 64                      # steps between saved states
_CHANNEL_BLOCKS = (640, 512, 384, 256, 128)
_VMEM_LIMIT = 64 * 1024 * 1024   # the backward's chunk of states is 2.7 MB


def selective_scan_reference(x, delta, a, b, c, d_skip):
    """The recurrence as a ``lax.scan`` over tokens, float32; shapes as
    :func:`selective_scan`."""
    f32 = lambda v: v.astype(jnp.float32)
    x32, delta, a, b, c, d_skip = map(f32, (x, delta, a, b, c, d_skip))

    def step(s, row):
        x_t, dt, b_t, c_t = row
        s = jnp.exp(dt[..., None] * a) * s + (dt * x_t)[..., None] * b_t[:, None, :]
        return s, jnp.einsum("bdn,bn->bd", s, c_t) + d_skip * x_t

    rows = tuple(jnp.swapaxes(v, 0, 1) for v in (x32, delta, b, c))
    s0 = jnp.zeros((x.shape[0],) + a.shape, jnp.float32)
    _, y = jax.lax.scan(step, s0, rows)
    return jnp.swapaxes(y, 0, 1).astype(x.dtype)


def _pick_blocks(t: int, d_inner: int) -> tuple[int, int, int, int]:
    """(chunk, padded T, channel block, padded d_inner)."""
    chunk = min(_CHUNK, round_up(t, SUBLANE))
    dp = round_up(d_inner, LANE)
    lanes = next(n for n in _CHANNEL_BLOCKS if dp % n == 0)
    return chunk, round_up(t, chunk), lanes, dp


def _over_lanes(tile, lanes: int):
    """A ``[d_state, 128]`` tile repeated to ``[d_state, lanes]``."""
    return tile if lanes == LANE else jnp.concatenate(
        [tile] * (lanes // LANE), axis=1)


def _lane_tiles_sum(v):
    """``[d_state, lanes] -> [d_state, 128]``: the lane tiles added."""
    out = v[:, :LANE]
    for i in range(1, v.shape[1] // LANE):
        out = out + v[:, i * LANE:(i + 1) * LANE]
    return out


def _set_row(rows, j: int, row):
    """``rows [8, lanes]`` with row ``j`` replaced by ``row [1, lanes]``."""
    at = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 0) == j
    return jnp.where(at, row, rows)


def _fwd_kernel(chunk: int, delta_ref, x_ref, a_ref, b_ref, c_ref, skip_ref,
                y_ref, edge_ref, s_ref):
    """One chunk of one channel block: ``s_ref`` holds the state the
    chunk starts from, which is also what the backward needs of it."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        s_ref[:] = jnp.zeros_like(s_ref)

    edge_ref[0, 0] = s_ref[:]
    a, skip = a_ref[:], skip_ref[:]
    lanes = a.shape[1]

    def group(i, s):
        r0 = pl.multiple_of(i * SUBLANE, SUBLANE)
        dt8 = delta_ref[0, pl.ds(r0, SUBLANE), :]
        x8 = x_ref[0, pl.ds(r0, SUBLANE), :]
        du8 = dt8 * x8
        y8 = skip * x8
        for j in range(SUBLANE):
            s = (jnp.exp(dt8[j:j + 1] * a) * s
                 + du8[j:j + 1] * _over_lanes(b_ref[0, r0 + j], lanes))
            y_t = jnp.sum(s * _over_lanes(c_ref[0, r0 + j], lanes),
                          axis=0, keepdims=True)
            y8 = _set_row(y8, j, y8[j:j + 1] + y_t)
        y_ref[0, pl.ds(r0, SUBLANE), :] = y8
        return s

    s_ref[:] = jax.lax.fori_loop(0, chunk // SUBLANE, group, s_ref[:])


def _bwd_kernel(chunk: int, delta_ref, x_ref, dy_ref, a_ref, b_ref, c_ref,
                skip_ref, edge_ref,
                ddelta_ref, dx_ref, da_ref, db_ref, dc_ref,
                states_ref, h_ref):
    """One chunk, visited after the chunks that follow it. ``h_ref`` is
    the cotangent of the chunk's last state as the later steps left it
    (``exp(delta a)`` of the next step times that step's cotangent);
    ``states_ref[t]`` is the state before step ``t`` of the chunk."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        h_ref[:] = jnp.zeros_like(h_ref)
        da_ref[0] = jnp.zeros_like(a_ref)

    a, skip = a_ref[:], skip_ref[:]
    lanes = a.shape[1]
    groups = chunk // SUBLANE

    def again(i, s):
        r0 = pl.multiple_of(i * SUBLANE, SUBLANE)
        dt8 = delta_ref[0, pl.ds(r0, SUBLANE), :]
        du8 = dt8 * x_ref[0, pl.ds(r0, SUBLANE), :]
        for j in range(SUBLANE):
            states_ref[r0 + j] = s
            s = (jnp.exp(dt8[j:j + 1] * a) * s
                 + du8[j:j + 1] * _over_lanes(b_ref[0, r0 + j], lanes))
        return s

    states_ref[chunk] = jax.lax.fori_loop(0, groups, again, edge_ref[0, 0])
    lane = jax.lax.broadcasted_iota(jnp.int32, (a.shape[0], LANE), 1)

    def back(i, carry):
        h, da, db, dc = carry
        r0 = pl.multiple_of((groups - 1 - i) * SUBLANE, SUBLANE)
        dt8 = delta_ref[0, pl.ds(r0, SUBLANE), :]
        x8 = x_ref[0, pl.ds(r0, SUBLANE), :]
        dy8 = dy_ref[0, pl.ds(r0, SUBLANE), :]
        du8 = dt8 * x8
        ddu8 = jnp.zeros_like(dt8)
        ddt8 = jnp.zeros_like(dt8)
        for j in reversed(range(SUBLANE)):
            t = r0 + j
            dt, du, dy = dt8[j:j + 1], du8[j:j + 1], dy8[j:j + 1]
            b_t = _over_lanes(b_ref[0, t], lanes)
            decay = jnp.exp(dt * a)
            g = h + dy * _over_lanes(c_ref[0, t], lanes)
            # over the channels of this block: the lanes here, the
            # blocks outside; step t of the chunk is lane t of the tile
            dc_t = jnp.sum(_lane_tiles_sum(dy * states_ref[t + 1]),
                           axis=1, keepdims=True)
            db_t = jnp.sum(_lane_tiles_sum(g * du), axis=1, keepdims=True)
            dc = jnp.where(lane == t, dc_t, dc)
            db = jnp.where(lane == t, db_t, db)
            gs = g * states_ref[t] * decay
            ddu8 = _set_row(ddu8, j, jnp.sum(g * b_t, axis=0, keepdims=True))
            ddt8 = _set_row(ddt8, j, jnp.sum(gs * a, axis=0, keepdims=True))
            da = da + gs * dt
            h = decay * g
        ddelta_ref[0, pl.ds(r0, SUBLANE), :] = ddu8 * x8 + ddt8
        dx_ref[0, pl.ds(r0, SUBLANE), :] = ddu8 * dt8 + skip * dy8
        return h, da, db, dc

    tile = jnp.zeros((a.shape[0], LANE), jnp.float32)
    h, da, db, dc = jax.lax.fori_loop(
        0, groups, back, (h_ref[:], jnp.zeros_like(a), tile, tile))
    h_ref[:] = h
    da_ref[0] += da
    db_ref[0, 0, 0] = db
    dc_ref[0, 0, 0] = dc


@functools.lru_cache(maxsize=None)
def _make_scan(batch: int, t: int, d_inner: int, d_state: int):
    """The custom-VJP scan over padded float32 operands for one static
    shape: ``(x, delta [B, Tp, Dp], aT [N, Dp], b, c [B, Tp, N, 128],
    skip [1, Dp]) -> y [B, Tp, Dp]``."""
    chunk, tp, lanes, dp = _pick_blocks(t, d_inner)
    n_chunks, n_blocks = tp // chunk, dp // lanes
    vmem = lambda shape, index: pl.BlockSpec(shape, index,
                                             memory_space=pltpu.VMEM)
    params = pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT)
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)

    def specs(time):
        """Blocks of (a [B, Tp, Dp] array, aT, b or c, skip, the edges)
        at grid point ``(b, j, k)``, chunk ``time(k)``."""
        return (vmem((1, chunk, lanes), lambda b, j, k: (b, time(k), j)),
                vmem((d_state, lanes), lambda b, j, k: (0, j)),
                vmem((1, chunk, d_state, LANE),
                     lambda b, j, k: (b, time(k), 0, 0)),
                vmem((1, lanes), lambda b, j, k: (0, j)),
                vmem((1, 1, d_state, lanes),
                     lambda b, j, k: (b, time(k), 0, j)))

    def forward(x, delta, a_t, b, c, skip):
        seq, a_s, bc, sk, edge = specs(lambda k: k)
        return pl.pallas_call(
            functools.partial(_fwd_kernel, chunk),
            out_shape=(f32(batch, tp, dp), f32(batch, n_chunks, d_state, dp)),
            grid=(batch, n_blocks, n_chunks),
            in_specs=[seq, seq, a_s, bc, bc, sk],
            out_specs=(seq, edge),
            scratch_shapes=[pltpu.VMEM((d_state, lanes), jnp.float32)],
            compiler_params=params, interpret=use_interpret(),
        )(delta, x, a_t, b, c, skip)

    def backward(x, delta, a_t, b, c, skip, edges, dy):
        seq, a_s, bc, sk, edge = specs(lambda k: n_chunks - 1 - k)
        per_block = vmem((1, 1, 1, d_state, LANE),
                         lambda b, j, k: (b, j, n_chunks - 1 - k, 0, 0))
        partial = f32(batch, n_blocks, n_chunks, d_state, LANE)
        return pl.pallas_call(
            functools.partial(_bwd_kernel, chunk),
            out_shape=(f32(batch, tp, dp), f32(batch, tp, dp),
                       f32(batch, d_state, dp), partial, partial),
            grid=(batch, n_blocks, n_chunks),
            in_specs=[seq, seq, seq, a_s, bc, bc, sk, edge],
            out_specs=(seq, seq,
                       vmem((1, d_state, lanes), lambda b, j, k: (b, 0, j)),
                       per_block, per_block),
            scratch_shapes=[
                pltpu.VMEM((chunk + 1, d_state, lanes), jnp.float32),
                pltpu.VMEM((d_state, lanes), jnp.float32)],
            compiler_params=params, interpret=use_interpret(),
        )(delta, x, dy, a_t, b, c, skip, edges)

    @jax.custom_vjp
    def scan(x, delta, a_t, b, c, skip):
        return forward(x, delta, a_t, b, c, skip)[0]

    def scan_fwd(x, delta, a_t, b, c, skip):
        y, edges = forward(x, delta, a_t, b, c, skip)
        return y, (x, delta, a_t, b, c, skip, edges)

    def scan_bwd(res, dy):
        ddelta, dx, da, db, dc = backward(*res, dy)

        def per_step(partials):
            """[B, blocks, chunks, N, 128] -> [B, Tp, N] broadcast over
            the 128 lanes ``b`` and ``c`` came in."""
            steps = partials.sum(1)[..., :chunk]          # [B, chunks, N, chunk]
            steps = jnp.swapaxes(steps, 2, 3).reshape(batch, tp, d_state)
            # every lane held the same value: its gradient is theirs, once
            return pad_axis(steps[..., None], 3, LANE)

        d_skip = jnp.sum(dy * res[0], axis=(0, 1))[None]
        return dx, ddelta, da.sum(0), per_step(db), per_step(dc), d_skip

    scan.defvjp(scan_fwd, scan_bwd)
    return scan


def selective_scan(x: jax.Array, delta: jax.Array, a: jax.Array,
                   b: jax.Array, c: jax.Array, d_skip: jax.Array
                   ) -> jax.Array:
    """``y [B, T, d_inner]`` of the recurrence in the module header.

    ``x`` and ``delta`` are ``[B, T, d_inner]`` (``delta`` already
    positive: after its softplus), ``a`` ``[d_inner, d_state]``
    (negative), ``b`` and ``c`` ``[B, T, d_state]``, ``d_skip``
    ``[d_inner]``. Float32 inside whatever comes in; ``y`` leaves in
    ``x``'s type; differentiable in all six."""
    batch, t, d_inner = x.shape
    d_state = a.shape[1]
    if (delta.shape != x.shape or a.shape[0] != d_inner
            or b.shape != (batch, t, d_state) or c.shape != b.shape
            or d_skip.shape != (d_inner,)):
        raise ValueError(
            f"x {x.shape}, delta {delta.shape}, a {a.shape}, b {b.shape}, "
            f"c {c.shape}, d_skip {d_skip.shape} are no selective scan")
    _, tp, _, dp = _pick_blocks(t, d_inner)
    f32 = lambda v: v.astype(jnp.float32)
    seq = lambda v: pad_axis(pad_axis(f32(v), 1, tp), 2, dp)
    over_lanes = lambda v: jnp.broadcast_to(
        pad_axis(f32(v), 1, tp)[..., None], (batch, tp, d_state, LANE))
    y = _make_scan(batch, t, d_inner, d_state)(
        seq(x), seq(delta), pad_axis(f32(a).T, 1, dp), over_lanes(b),
        over_lanes(c), pad_axis(f32(d_skip)[None], 1, dp))
    return y[:, :t, :d_inner].astype(x.dtype)
