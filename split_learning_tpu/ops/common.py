"""Shared helpers for the Pallas kernel layer."""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax._src.pallas import core as pallas_core

# float32 native tile: 8 sublanes x 128 lanes
SUBLANE = 8
LANE = 128

# shared additive-mask value for softmax-family kernels: large enough to
# zero out after exp, small enough that (x - NEG_BIG) never overflows —
# masked entries must still be re-zeroed after any exp rebase
NEG_BIG = -1e30


@functools.lru_cache(maxsize=None)
def _default_backend_platform() -> str:
    # a backend that fails to initialize raises here: answering "cpu"
    # would silently turn every kernel to interpret mode on a broken chip
    return jax.default_backend()


def use_interpret() -> bool:
    """Pallas TPU kernels compile only on real TPU; everywhere else
    (the 8-virtual-CPU-device test mesh, SURVEY.md §4) run the Mosaic
    interpreter so the same kernel code is exercised."""
    if os.environ.get("SLT_PALLAS_INTERPRET", "") == "1":
        return True
    return _default_backend_platform() != "tpu"


def round_up(n: int, m: int) -> int:
    return (n + m - 1) // m * m


def pad_axis(x: jax.Array, axis: int, target: int) -> jax.Array:
    """Zero-pad one axis up to ``target`` length."""
    if x.shape[axis] == target:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, target - x.shape[axis])
    return jnp.pad(x, pad)


def traced_once(kernel):
    """``kernel`` as a function whose Python runs once a grid. A kernel body
    is a pure function of its refs' types and of the grid it runs under
    (``pl.num_programs`` is a constant of the trace), and a model calls one
    kernel at many sites (GPT-2's step its attention 24 times, and twice
    more while the harness asks for its shapes; a Mamba-2 hybrid its
    recurrence once a layer), each of which would trace the body anew:
    about 0.1 s a site on the chip's host for the flash kernels' whole-pair
    bodies, and several times that for a kernel with cut pairs (PR 31). So
    the first site's jaxpr is kept and later sites replay it, one bind an
    equation."""
    kept = {}

    def run(*refs):
        key = (pallas_core.axis_frame().grid,
               *(jax.typeof(r) for r in refs))
        if key not in kept:
            # through a function of its own: jax keeps traces by function
            # and types, and would answer with another grid's
            kept[key] = jax.make_jaxpr(lambda *r: kernel(*r))(*refs)
        jax.core.eval_jaxpr(kept[key].jaxpr, kept[key].consts, *refs)

    return run


def causal_depthwise_conv(x: jax.Array, taps: jax.Array) -> jax.Array:
    """``y_t = sum_k taps[k] * x_{t - (K - 1) + k}`` per channel: ``x [B,
    T, C]``, ``taps [K, C]``, zeros before the sequence's start, so the
    last tap weighs the current token. A shifted sum of slices, which
    XLA fuses into one pass (models/phi4flash.py's Mamba at four taps,
    models/lfm2_moe.py's gated convolution at three)."""
    t, k = x.shape[1], taps.shape[0]
    past = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(taps[i] * past[:, i:i + t] for i in range(k))
