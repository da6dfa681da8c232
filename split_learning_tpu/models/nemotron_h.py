"""Split Nemotron-H — one mixer a layer: Mamba-2 state-space layers,
routed feed-forward layers of ungated ``relu^2`` experts beside a shared
one, and now and then grouped-head attention without positions (the
``nemotron_h`` family).

The kind of published layer ``i`` is letter ``i`` of the published
``hybrid_override_pattern``: ``M`` Mamba-2, ``E`` the routed feed-forward
part, ``*`` attention. **A layer is one mixer alone**, pre-norm with
nothing after the branch (RMSNorm(x) = x / sqrt(mean(x^2) + eps) * scale,
statistics in float32, no bias in any product)::

    h = h + Mixer(norm(h))

- **``M``** (:class:`Mamba2Mixer`), ``d_inner = heads x head_dim``: ``[z |
  xBC | dt] = u W_in`` (``d_inner + (d_inner + 2 groups x state) + heads``
  columns); ``xBC = silu(conv(xBC) + b_conv)``, a causal depthwise
  convolution over ``conv_taps`` tokens (ops/causal_conv.py's ``conv_silu``:
  at the published sizes one Pallas pass forward and one backward, at a
  test's or a rehearsal's the shifted sum of ops/common.py);
  ``x [T, heads, head_dim]``, ``B``, ``C`` ``[T, groups, state]``, head
  ``n`` reads group ``n // (heads / groups)``; ``dt = softplus(dt +
  dt_bias)``, no clamp; ``a_n = -exp(A_log_n)``, one scalar a head; the
  recurrence ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t
  + D_n x_t`` in its chunked form (ops/ssd.py: four products a chunk of
  ``chunk`` tokens and the chunks' states carried from one to the next; at
  the published sizes, chunks and states of 128 and eight heads of 64 a
  group, two Pallas kernels that keep a chunk's scores, decays and their
  product and a group's state in VMEM, write ``y`` and each chunk's first
  state, and make the rest again in the backward; at any other size, the
  tests' and a rehearsal's chunks of 8 among them, batched ``einsum``s);
  ``y = RMSNorm_groups(y * silu(z))``, **the gate before the norm**, the
  statistics over each group's ``d_inner / groups`` channels, one scale of
  ``d_inner``; ``y W_out``. The products run in ``dtype``; the
  convolution, ``dt``, ``a``, the recurrence's decays and states, the gate
  and the norm in float32.
- **``*``**: models/afmoe.py's :class:`~split_learning_tpu.models.afmoe.
  AfmoeAttention` in its ``plain`` form: ``q = u W_q`` ``[T, H, D]``, ``k``,
  ``v`` ``[T, H_kv, D]``, **no rotary and no other positions**, no norm of
  q or k, no gate; query head ``n`` reads key/value head ``n // (H /
  H_kv)``; scores times ``D^-0.5``, causal, softmax in float32; ``o W_o``.
- **``E``**: ``m = norm(h)``; models/afmoe.py's router to the letter
  (:func:`~split_learning_tpu.models.afmoe.route`: sigmoid scores in
  float32, the ``experts_per_token`` best of score plus a bias that takes
  no gradient, the chosen scores normalised, times ``route_scale``) and
  its :class:`~split_learning_tpu.models.afmoe.RoutedExperts` with
  ``gated`` off: ``y = sum_e w_e W_down,e relu(W_up,e m)^2`` over the
  experts held here, at the rows the routing fills, plus the shared
  expert ``W_down,s relu(W_up,s m)^2`` (:class:`Relu2MLP`, whole on every
  chip).

**Where ``dt_bias``, ``A_log``, ``D`` and the taps start.** The published
initialiser draws a head's first step ``dt`` log-uniform in
``[time_step_min, time_step_max]`` and its decay rate ``-a`` uniform in
``[1, 16]``, stores ``softplus^-1(dt)`` and ``log(-a)``, sets the skip
``D`` to 1, and leaves the convolution at its framework's default, every
tap uniform in ``+-conv_taps^-1/2``. Here the four leaves hold their
*distance* from that: from the two distributions' quantiles in head order
(:func:`mamba_starts`: head ``n`` of ``H`` starts at quantile ``(n + 1/2)
/ H`` of both, so the heads' memories span a token to a thousand), from 1,
and from the uniform's quantiles along a sequence that fills the unit
cube of a channel's taps evenly (:func:`tap_starts`), so that a leaf of
zeros, or of small seeded noise, is a model inside the published ranges.
A constant beside a leaf moves no gradient. (With a skip near 0 instead,
what the grouped norm is given is a fiftieth of the size and its backward
pass multiplies every gradient of the layer by fifty; with taps of 0.02,
``x``, ``B`` and ``C`` are a fifteenth of the size and what the recurrence
adds to ``y`` is 0.06 % of the skip's part, where at the published taps it
is a sixth, a thirteenth to a third by the head's decay: PERF.md,
Findings PR 39.)

The stages are models/cut.py's, given this family's layers
(:func:`_run_layers`) and its RMSNorm as the final norm: split =
client(embedding, unscaled, + the first ``client_depth`` kept layers) ->
server(the rest + final norm + untied head); u_split moves norm and head
back to the client; federated is the composition.

**What ``remat`` recomputes**, in the backward pass: the routed part of
each ``E`` layer (models/afmoe.py's header: it is what gives the three rungs
of rows), and the two elementwise passes of each ``M`` layer, which are
bound by bytes and not by arithmetic: ``silu(conv(xBC) + b_conv)`` is made
again from the first product's output (by ``jax.checkpoint`` around the
plain form; the kernels of ops/causal_conv.py keep their inputs alone and
their backward makes the pre-activation again in registers, so no second
forward call runs), the gate with the grouped norm
from ``z`` and the recurrence's ``y``, so that of each the inputs are
kept and none of the float32 values between (2.2 GB a step at the
benchmark's sizes). Every product's output, the shared expert's hidden
layer and what the flash kernels' backward reads are kept: no product
outside the recurrence, no flash forward and no grouped product outside
the routed part runs a second time. Of the recurrence its operands, ``l``
and the chunks' first states are kept (ops/ssd.py's ``custom_vjp``, at
the kernels' sizes: 67 MB a layer in ``dtype``), and its backward kernel
makes a chunk's scores, decays and masked product again in VMEM; the
plain form keeps those three (402 MB a layer). XLA's analysis of the
fused step at the benchmark's sizes (T 8192, seven layers;
scripts/fused_step_memory.py, the fit rule is 14.5 GB): 12.094 GB with
the kernels (PR 42), 13.933 with the plain form and three rungs (PR 41),
of which PR 39 read with two rungs: the routed part alone 14.897 GB,
this form 13.812; with the chunked form's intra-chunk arrays made again
from the chunks' states as well 12.288, and those in place of the two
passes 14.477: each was run on the chip, and this form was the fastest
of the three (PERF.md, Findings PR 39).
Decoding is not built: it needs a ``[heads, head_dim, state]`` state and
the convolution's last ``conv_taps - 1`` tokens beside a key/value cache
(runtime/generate.py, ROADMAP.md M7).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from split_learning_tpu.core.stage import SplitPlan
from split_learning_tpu.models import cut
from split_learning_tpu.models.afmoe import (
    AfmoeAttention, RMSNorm, RoutedExperts)
from split_learning_tpu.obs import spans
from split_learning_tpu.ops import causal_conv
from split_learning_tpu.ops.ssd import ssd_chunked

_KINDS = "ME*"
_INIT = nn.initializers.normal(0.02)
_F32 = jnp.float32
_DECAY_RATES = (1.0, 16.0)      # the published initialiser's range of -a


def mamba_starts(heads: int, dt_min: float, dt_max: float) -> tuple:
    """(``dt_bias``, ``A_log``) ``[heads]`` each: the quantiles in head
    order of the published initialiser (the module header)."""
    at = [(n + 0.5) / heads for n in range(heads)]
    dt = [math.exp(math.log(dt_min) + q * math.log(dt_max / dt_min))
          for q in at]
    low, high = _DECAY_RATES
    return (jnp.asarray([x + math.log(-math.expm1(-x)) for x in dt], _F32),
            jnp.asarray([math.log(low + q * (high - low)) for q in at], _F32))


def tap_starts(taps: int, channels: int) -> jax.Array:
    """``[taps, channels]``: where a convolution's taps start (the module
    header). Channel ``c``'s tap ``k`` is the quantile ``frac((c + 1)
    sqrt(p_k))`` of a uniform in ``+-taps^-1/2``, ``p_k`` the ``k``-th
    prime: the roots are independent over the rationals, so the channels'
    points fill ``[0, 1)^taps`` evenly and no tap follows from another."""
    primes: list = []
    k = 2
    while len(primes) < taps:
        if all(k % p for p in primes):
            primes.append(k)
        k += 1
    at = np.sqrt(np.asarray(primes, np.float64))[:, None] * np.arange(
        1.0, channels + 1.0) % 1.0
    return jnp.asarray((2.0 * at - 1.0) * taps ** -0.5, _F32)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What every layer of one model shares; :func:`nemotron_h_plan`
    documents each."""

    pattern: str
    mamba_heads: int
    mamba_head_dim: int
    ssm_state: int
    ssm_groups: int
    conv_taps: int
    chunk: int
    time_step_min: float
    time_step_max: float
    num_heads: int
    num_kv_heads: int
    head_dim: int
    expert_width: int
    shared_width: int
    experts_total: int
    experts_held: int
    expert_offset: int
    experts_per_token: int
    route_scale: float
    eps: float
    attn: str
    dtype: Any
    remat: bool

    def norm(self, name: str, dtype=None) -> RMSNorm:
        return RMSNorm(self.eps, dtype or self.dtype, name=name)

    def linear(self, features: int, name: str) -> nn.Dense:
        return nn.Dense(features, use_bias=False, dtype=self.dtype,
                        kernel_init=_INIT, name=name)


class GatedGroupNorm(nn.Module):
    """``RMSNorm_groups(y * silu(z))``: the gate first, then an RMSNorm
    whose statistics run over each of ``groups`` equal parts of the last
    axis, under one ``scale`` of its whole width; float32 inside, the
    result in ``dtype``."""

    groups: int
    eps: float
    dtype: Any

    @nn.compact
    def __call__(self, y, z):
        scale = self.param("scale", nn.initializers.ones, (y.shape[-1],))
        gated = y.astype(_F32) * jax.nn.silu(z.astype(_F32))
        parts = gated.reshape(*y.shape[:-1], self.groups, -1)
        parts = parts * jax.lax.rsqrt(
            jnp.mean(parts * parts, -1, keepdims=True) + self.eps)
        return (parts.reshape(y.shape) * scale).astype(self.dtype)


def _conv_act(xbc, taps, bias):
    """``silu(conv(xbc) + bias)``: float32 from the product's output, the
    result back in its type."""
    with jax.named_scope(spans.SSM_CONV):
        return causal_conv.conv_silu(xbc, taps, bias, xbc.dtype)


class Mamba2Mixer(nn.Module):
    sizes: Sizes

    @nn.compact
    def __call__(self, u):
        s = self.sizes
        bsz, t, e = u.shape
        h, p, g, n = s.mamba_heads, s.mamba_head_dim, s.ssm_groups, s.ssm_state
        inner, wide = h * p, h * p + 2 * g * n
        z, xbc, dt = jnp.split(s.linear(inner + wide + h, "in_proj")(u),
                               [inner, inner + wide], axis=-1)
        taps = self.param("conv_kernel", _INIT, (s.conv_taps, wide))
        conv_bias = self.param("conv_bias", nn.initializers.zeros, (wide,))
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (h,))
        a_log = self.param("A_log", nn.initializers.zeros, (h,))
        d_skip = self.param("D", nn.initializers.zeros, (h,))
        conv_act, norm = _conv_act, GatedGroupNorm
        if s.remat:
            # the two elementwise passes are made again from what the
            # products gave (the module header); the convolution's kernels
            # keep their inputs alone as it is
            norm = nn.remat(norm)
            if not causal_conv.fills_tiles(xbc, taps):
                conv_act = jax.checkpoint(conv_act)
        x, b, c = jnp.split(
            conv_act(xbc, taps + tap_starts(s.conv_taps, wide), conv_bias),
            [inner, inner + g * n], axis=-1)
        dt_start, a_start = mamba_starts(h, s.time_step_min, s.time_step_max)
        dt = jax.nn.softplus(dt.astype(_F32) + dt_bias + dt_start)
        y = ssd_chunked(
            x.reshape(bsz, t, h, p), dt, -jnp.exp(a_log + a_start),
            b.reshape(bsz, t, g, n), c.reshape(bsz, t, g, n), 1.0 + d_skip,
            s.chunk)
        y = norm(g, s.eps, s.dtype, name="norm")(y.reshape(bsz, t, inner), z)
        return s.linear(e, "out_proj")(y)


class Relu2MLP(nn.Module):
    """``W_down relu(W_up m)^2``: an expert's form, dense."""

    sizes: Sizes
    width: int

    @nn.compact
    def __call__(self, m):
        s = self.sizes
        return s.linear(m.shape[-1], "down")(
            jnp.square(jax.nn.relu(s.linear(self.width, "up")(m))))


class NemotronLayer(nn.Module):
    """The layer of published index ``index``."""

    sizes: Sizes
    index: int

    @nn.compact
    def __call__(self, h):
        s = self.sizes
        b, t, e = h.shape
        kind = s.pattern[self.index]
        if kind == "M":
            return h + Mamba2Mixer(s, name="mamba")(s.norm("norm")(h))
        if kind == "*":
            return h + AfmoeAttention(
                s.num_heads, s.num_kv_heads, s.head_dim, None, eps=s.eps,
                attn=s.attn, dtype=s.dtype, plain=True,
                name="attn")(s.norm("norm")(h))
        # the router reads the float32 norm, the experts its rounding
        m32 = s.norm("norm", _F32)(h)
        with jax.named_scope(spans.MOE_SHARED):
            y = Relu2MLP(s, s.shared_width,
                         name="shared")(m32.astype(s.dtype))
        routed = RoutedExperts(
            s.expert_width, s.experts_total, s.experts_held, s.expert_offset,
            s.experts_per_token, s.route_scale, s.dtype, s.remat, gated=False,
            name="experts")
        return h + y + routed(m32.reshape(b * t, e)).reshape(b, t, e)


def _run_layers(h, sizes: Sizes, indices: Sequence[int]):
    """The published layers ``indices`` in order, named ``layer<i>`` (call
    inside a compact method: models/cut.py's stages do, as their
    ``run``)."""
    for i in indices:
        h = NemotronLayer(sizes, i, name=f"layer{i}")(h)
    return h


def nemotron_h_plan(mode: str = "split", dtype: Any = jnp.float32, *,
                    vocab: int = 256, d_model: int = 64,
                    pattern: str = "MEMEM*EMEMEM*E",
                    layers_kept: Sequence[int] = (0, 1, 2, 3, 4, 5, 6),
                    client_depth: int = 1, mamba_heads: int = 8,
                    mamba_head_dim: int = 8, ssm_state: int = 16,
                    ssm_groups: int = 2, conv_taps: int = 4, chunk: int = 8,
                    time_step_min: float = 0.001, time_step_max: float = 0.1,
                    num_heads: int = 4, num_kv_heads: int = 2,
                    head_dim: int = 16, expert_width: int = 32,
                    shared_width: int = 64, experts_total: int = 8,
                    experts_held: Optional[int] = None, expert_offset: int = 0,
                    experts_per_token: int = 2, route_scale: float = 1.0,
                    norm_eps: float = 1e-5, attn: str = "auto",
                    remat: bool = True) -> SplitPlan:
    """Build the Nemotron-H :class:`SplitPlan` for ``mode``.

    The arguments carry the published names' values: ``pattern`` is
    ``hybrid_override_pattern`` whole, a letter a published layer;
    ``mamba_heads`` of ``mamba_head_dim``, ``ssm_state``, ``ssm_groups``
    (``n_groups``), ``conv_taps`` (``conv_kernel``), ``chunk``
    (``chunk_size``) and the two ``time_step`` ends describe an ``M``
    layer; ``num_heads`` over ``num_kv_heads`` of ``head_dim`` a ``*``
    layer; an ``E`` layer holds ``experts_held`` of ``experts_total``
    experts of ``expert_width`` from ``expert_offset`` on,
    ``experts_per_token`` a token, beside one shared expert of
    ``shared_width``. ``layers_kept`` are the published indices of the
    layers built, in order, of which the client holds the first
    ``client_depth`` beside the embedding. A cut that keeps no layer of a
    letter the pattern has is refused: it would be another model.
    ``remat`` recomputes each ``E`` layer's routed part in the backward
    pass, at the rows its routing fills, and each ``M`` layer's two
    elementwise passes, and keeps everything else (the module header)."""
    cut.check_attn(attn)
    bad = sorted(set(pattern) - set(_KINDS))
    if bad or not pattern:
        raise ValueError(f"Unknown layer letters {bad} in pattern "
                         f"{pattern!r} (expected some of {_KINDS!r})")
    kept = cut.kept_layers(layers_kept, len(pattern), pattern)
    held = cut.held_experts(experts_total, experts_held, expert_offset)
    cut.check_client_depth(client_depth, len(kept))
    cut.check_heads(num_heads, num_kv_heads)
    if mamba_heads % ssm_groups:
        raise ValueError(f"{ssm_groups} groups do not divide {mamba_heads} "
                         "Mamba heads")
    if not 0 < time_step_min <= time_step_max:
        raise ValueError(f"time steps [{time_step_min}, {time_step_max}]")
    eps = float(norm_eps)
    sizes = Sizes(
        pattern=pattern, mamba_heads=mamba_heads,
        mamba_head_dim=mamba_head_dim, ssm_state=ssm_state,
        ssm_groups=ssm_groups, conv_taps=conv_taps, chunk=chunk,
        time_step_min=float(time_step_min),
        time_step_max=float(time_step_max), num_heads=num_heads,
        num_kv_heads=num_kv_heads, head_dim=head_dim,
        expert_width=expert_width, shared_width=shared_width,
        experts_total=experts_total, experts_held=held,
        expert_offset=expert_offset, experts_per_token=experts_per_token,
        route_scale=float(route_scale), eps=eps, attn=attn, dtype=dtype,
        remat=bool(remat))
    return cut.split_plan(
        mode, cut.EmbedStage(vocab, d_model, _run_layers,
                             (sizes, kept[:client_depth]), dtype),
        (sizes, kept[client_depth:]),
        cut.HeadStage(vocab, RMSNorm(eps, dtype), dtype))
