"""A Mamba layer's causal depthwise convolution with its bias and silu, as one
operation: ``y = silu(bias + causal_depthwise_conv(x, taps))`` per channel,
``x [B, T, C]``, ``taps [K, C]``, ``bias [C]``, zeros before a sequence's
start, the last tap on the current token (ops/common.py's convolution).
Pallas TPU kernels where the shapes fill their tiles, forward and backward
under one ``custom_vjp``; the shifted sum of ops/common.py, differentiated
by ``jax`` itself, at any other shape (:func:`conv_silu` chooses, from the
arguments alone).

``x`` comes as it is stored (bfloat16 on the chip) and is made float32 a
block at a time; the tap products, their sum (tap 0 first), the bias and the
silu are float32 in the plain form's order, and the result is rounded once
to the type the caller names. The plain form converts all of ``x`` first,
and its transpose is ``K`` padded float32 ``[T + K - 1, C]`` arrays that XLA
writes one by one: nine float32 passes a layer where the bytes ask for five
in the stored type (PERF.md, Findings PR 48).

**The kernels** (``conv_silu_fwd``, ``conv_silu_bwd``: the names of their
calls in a trace, under the models' scope ``ssm_conv``) run where ``C`` is a
multiple of 128 lanes, ``T`` of the token tile (1024), ``K <= 8`` and ``x``
is bfloat16 or float32. One grid step is one (sequence, channel tile, token
tile), the token tile innermost and sequential; inside it a lane tile at a
time, strips of 128 tokens at a time, so that a strip's float32 values stay
in registers or near them (sixteen a value; PERF.md, Findings PR 48, has
the tilings tried). A token's neighbours come through VMEM: a strip is stored
behind the eight rows before it (``[8 + 128, 128]`` float32 a lane tile)
and read back one, two, ``K - 1`` rows up, which a load does at no cost
where a shift in registers is a rotate and a select a register.

- forward: the eight rows before a strip stay where the strip before left
  them, over a tile's edge too (zeros at a sequence's first tile). Reads
  ``x`` once, writes ``y`` once.
- backward, token tiles and strips last to first: the residuals are the
  inputs alone. The pre-activation is made again from ``x`` (the rows
  before a strip are read from ``x`` again, before a tile from a second,
  16-row block of ``x``), ``dpre = dy silu'(pre)``, ``dx_t = sum_k taps[k]
  dpre_{t + (K - 1) - k}`` with ``dpre`` stored before the first eight rows
  of the strip after and read back rows down, and ``dtaps[k] = sum_t dpre_t
  x_{t - (K - 1) + k}`` and ``dbias`` summed by sublane in registers over a
  tile's strips and in the resident output block over the token tiles,
  written once a channel tile (``[B, K + 1, 8, C]`` float32; XLA adds the
  eight sublanes and the sequences). Reads ``dy`` and ``x`` once, writes
  ``dx`` once in ``x``'s type: nothing of ``[T, C]`` elements in float32
  reaches HBM unless the caller asked for a float32 ``y``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from split_learning_tpu.ops.common import (
    LANE, SUBLANE, causal_depthwise_conv, traced_once, use_interpret)

_F32 = jnp.float32
TOKENS = 1024       # a block's tokens
_CHANNELS = 512     # and at most its channels
_STRIP = 128        # tokens whose float32 values are worked on together
_BEFORE = 16        # rows of the block before a tile: a bfloat16 tile's


def fills_tiles(x: jax.typing.ArrayLike, taps: jax.typing.ArrayLike) -> bool:
    """Whether the kernels' tiling holds ``x [B, T, C]`` under ``taps [K,
    C]`` (the module header)."""
    (_, t, c), k = x.shape, taps.shape[0]
    return (t % TOKENS == 0 and c % LANE == 0 and k <= SUBLANE
            and x.dtype in (jnp.bfloat16, jnp.float32))


def conv_silu(x: jax.Array, taps: jax.Array, bias: jax.Array,
              dtype) -> jax.Array:
    """``silu(bias + causal_depthwise_conv(x, taps))`` computed in float32,
    in ``dtype``: ``x [B, T, C]``, ``taps [K, C]``, ``bias [C]`` (the module
    header)."""
    if fills_tiles(x, taps):
        bsz, t, c = x.shape
        op = _make_conv_silu(bsz, t, c, taps.shape[0], jnp.dtype(x.dtype),
                             jnp.dtype(dtype))
        return op(x, taps.astype(_F32), bias.astype(_F32)[None])
    return jax.nn.silu(
        bias + causal_depthwise_conv(x.astype(_F32), taps)).astype(dtype)


# ------------------------------------------------------------------ #
# the kernels: a strip is ``[_STRIP, 128]`` float32, sixteen registers

def _neighbours(behind_ref, x, k: int) -> list:
    """``[x_{t - (K - 1) + i} for i in range(K)]`` over a strip's tokens:
    ``x [S, 128]`` stored behind the eight rows before it in ``behind_ref
    [8 + S, 128]`` and read back ``K - 1 - i`` rows up."""
    behind_ref[SUBLANE:] = x
    return [behind_ref[pl.ds(SUBLANE - j, _STRIP)] for j in range(k - 1, 0, -1)
            ] + [x]


def _pre(xs, taps, bias):
    """The pre-activation, in the plain form's order: tap 0 first, then the
    bias."""
    acc = taps[0] * xs[0]
    for tap, v in zip(taps[1:], xs[1:]):
        acc = acc + tap * v
    return bias + acc


def _rows(i):
    return pl.ds(pl.multiple_of(i * _STRIP, _STRIP), _STRIP)


def _lane_tiles(ref):
    return [pl.ds(at, LANE) for at in range(0, ref.shape[-1], LANE)]


def _fwd_kernel(x_ref, taps_ref, bias_ref, y_ref, behind_ref):
    """One token tile of one channel tile. ``behind_ref [ct / 128, 8 + S,
    128]``: a lane tile's first eight rows are the rows of ``x`` before the
    strip at hand, from one strip, tile and grid step to the next."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        behind_ref[:, :SUBLANE] = jnp.zeros_like(behind_ref[:, :SUBLANE])

    k = taps_ref.shape[0]
    for tile, lanes in enumerate(_lane_tiles(x_ref)):
        taps = [taps_ref[i:i + 1, lanes] for i in range(k)]
        bias, behind = bias_ref[:, lanes], behind_ref.at[tile]

        def strip(i, _):
            x = x_ref[0, _rows(i), lanes].astype(_F32)
            pre = _pre(_neighbours(behind, x, k), taps, bias)
            y_ref[0, _rows(i), lanes] = jax.nn.silu(pre).astype(y_ref.dtype)
            behind[:SUBLANE] = x[-SUBLANE:]
            return 0

        jax.lax.fori_loop(0, x_ref.shape[1] // _STRIP, strip, 0)


def _by_sublane(v):
    """``[S, 128] -> [8, 128]``: the rows summed eight apart (adds of whole
    registers; the eight sublanes are XLA's to add)."""
    return functools.reduce(jnp.add, [
        v[at:at + SUBLANE] for at in range(0, v.shape[0], SUBLANE)])


def _bwd_kernel(x_ref, before_ref, taps_ref, bias_ref, dy_ref,
                dx_ref, sums_ref, behind_ref, ahead_ref):
    """One token tile, visited after the tiles that follow it. ``ahead_ref
    [ct / 128, S + 8, 128]``: a lane tile's last eight rows are the first
    rows of ``dpre`` after the strip at hand, as the forward's ``behind_ref``
    holds the rows before (here ``[8 + S, 128]``, filled a strip at a time
    from ``x``). ``sums_ref [1, K + 1, 8, ct]`` takes the taps' and the
    bias's gradients by sublane."""
    step, last = pl.program_id(2), pl.num_programs(2) - 1

    @pl.when(step == 0)
    def _init():
        ahead_ref[:, _STRIP:] = jnp.zeros_like(ahead_ref[:, _STRIP:])
        sums_ref[:] = jnp.zeros_like(sums_ref)

    k, strips = taps_ref.shape[0], x_ref.shape[1] // _STRIP
    for tile, lanes in enumerate(_lane_tiles(x_ref)):
        taps = [taps_ref[i:i + 1, lanes] for i in range(k)]
        bias, ahead = bias_ref[:, lanes], ahead_ref.at[tile]
        # zeros before a sequence's first tile, the grid's last step
        before_tile = jnp.where(
            step == last, 0.0,
            before_ref[0, :, lanes].astype(_F32)[-SUBLANE:])

        def strip(n, sums):
            i = strips - 1 - n
            at = pl.multiple_of(jnp.maximum(i * _STRIP - _BEFORE, 0), _BEFORE)
            behind_ref[:SUBLANE] = jnp.where(
                i == 0, before_tile,
                x_ref[0, pl.ds(at, _BEFORE), lanes].astype(_F32)[-SUBLANE:])
            xs = _neighbours(behind_ref,
                             x_ref[0, _rows(i), lanes].astype(_F32), k)
            pre = _pre(xs, taps, bias)
            sig = jax.nn.sigmoid(pre)
            dpre = dy_ref[0, _rows(i), lanes].astype(_F32) * (
                sig * (1.0 + pre * (1.0 - sig)))
            sums = tuple(s + _by_sublane(dpre * v) for s, v in zip(sums, xs)
                         ) + (sums[k] + _by_sublane(dpre),)
            # dpre_{t + j}: the strip read back j rows down
            ahead[:_STRIP] = dpre
            dx = taps[k - 1] * dpre
            for j in range(1, k):
                dx = dx + taps[k - 1 - j] * ahead[pl.ds(j, _STRIP)]
            dx_ref[0, _rows(i), lanes] = dx.astype(dx_ref.dtype)
            ahead[_STRIP:] = dpre[:SUBLANE]
            return sums

        zero = jnp.zeros((SUBLANE, LANE), _F32)
        for j, s in enumerate(jax.lax.fori_loop(
                0, strips, strip, (zero,) * (k + 1))):
            sums_ref[0, j, :, lanes] += s


@functools.lru_cache(maxsize=None)
def _make_conv_silu(bsz: int, t: int, c: int, k: int, x_dtype, y_dtype):
    """The custom-VJP operation for one static shape: ``(x [B, T, C], taps
    [K, C], bias [1, C]) -> y [B, T, C]`` in ``y_dtype``."""
    ct = next(w for w in (_CHANNELS, 2 * LANE, LANE) if c % w == 0)
    tiles = t // TOKENS
    grid = (bsz, c // ct, tiles)
    vmem = lambda shape, index: pl.BlockSpec(shape, index,
                                             memory_space=pltpu.VMEM)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))
    a_channel = lambda rows: vmem((rows, ct), lambda z, j, i: (0, j))
    strip = pltpu.VMEM((SUBLANE + _STRIP, LANE), _F32)
    strips = pltpu.VMEM((ct // LANE, SUBLANE + _STRIP, LANE), _F32)
    fwd_kernel, bwd_kernel = traced_once(_fwd_kernel), traced_once(_bwd_kernel)

    def forward(x, taps, bias):
        seq = vmem((1, TOKENS, ct), lambda z, j, i: (z, i, j))
        return pl.pallas_call(
            fwd_kernel, out_shape=jax.ShapeDtypeStruct((bsz, t, c), y_dtype),
            grid=grid, in_specs=[seq, a_channel(k), a_channel(1)],
            out_specs=seq, scratch_shapes=[strips], compiler_params=params,
            interpret=use_interpret(), name="conv_silu_fwd")(x, taps, bias)

    def backward(x, taps, bias, dy):
        seq = vmem((1, TOKENS, ct), lambda z, j, i: (z, tiles - 1 - i, j))
        before = vmem((1, _BEFORE, ct), lambda z, j, i: (
            z, jnp.maximum((tiles - 1 - i) * (TOKENS // _BEFORE) - 1, 0), j))
        return pl.pallas_call(
            bwd_kernel,
            out_shape=(jax.ShapeDtypeStruct((bsz, t, c), x_dtype),
                       jax.ShapeDtypeStruct((bsz, k + 1, SUBLANE, c), _F32)),
            grid=grid,
            in_specs=[seq, before, a_channel(k), a_channel(1), seq],
            out_specs=(seq, vmem((1, k + 1, SUBLANE, ct),
                                 lambda z, j, i: (z, 0, 0, j))),
            scratch_shapes=[strip, strips], compiler_params=params,
            interpret=use_interpret(), name="conv_silu_bwd",
        )(x, x, taps, bias, dy)

    @jax.custom_vjp
    def op(x, taps, bias):
        return forward(x, taps, bias)

    def op_fwd(x, taps, bias):
        return forward(x, taps, bias), (x, taps, bias)

    def op_bwd(res, dy):
        dx, sums = backward(*res, dy)
        sums = sums.sum(axis=(0, 2))
        return dx, sums[:k], sums[k:]

    op.defvjp(op_fwd, op_bwd)
    return op
