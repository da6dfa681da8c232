"""Split LFM2-MoE — gated short convolutions in most layers, grouped-head
attention in the others, and a routed FFN with no shared expert (the
``lfm2_moe`` family).

The kind of a layer is its entry of the published ``layer_types``
(``conv`` or ``full_attention``); its FFN is dense where its published
index is under ``dense_layers``, routed otherwise. Every layer is pre-norm
with nothing after a branch (RMSNorm(x) = x / sqrt(mean(x^2) + eps) *
scale, statistics in float32, no bias anywhere)::

    h = h + Op(operator_norm(h));  h = h + FFN(ffn_norm(h))

- **A ``conv`` layer's operator** (:class:`ShortConv`): ``[B | C | x] = u
  W_in`` (``d -> 3 d``, three parts in that order); ``g = B * x``; ``c_t =
  sum_k w_k * g_{t - (K - 1) + k}`` per channel, a causal depthwise
  convolution over ``conv_taps`` tokens (``ops/common.causal_depthwise_conv``:
  the last tap weighs the current token, zeros before the sequence's
  start, no bias); ``y = (C * c) W_out``. No activation and no softmax
  anywhere in it: a token mixer that is neither attention nor a scan. The
  two products run in ``dtype``; both gates and the taps' sum in float32
  from the first product's output, rounded to ``dtype`` once.
- **A ``full_attention`` layer's operator** (:class:`Lfm2Attention`): ``q
  = u W_q`` ``[T, H, D]``, ``k``, ``v`` ``[T, H_kv, D]``; ``q`` and ``k``
  RMS-normed over ``D`` with a scale each, **then** rotary positions
  (rotate-half, ``models/afmoe.py:rope``) on both, in every attention
  layer; query head ``n`` reads key/value head ``n // (H // H_kv)``;
  scores times ``D^-0.5``, causal, softmax in float32; ``o W_o``. No
  output gate.
- **FFN**: dense, a SwiGLU of ``dense_width``; routed, models/afmoe.py's
  routed layer to the letter and alone (:func:`~split_learning_tpu.models.
  afmoe.route`: sigmoid scores in float32, the ``experts_per_token`` best
  of score plus a bias that takes no gradient, the chosen scores
  normalised, times ``route_scale``; :class:`~split_learning_tpu.models.
  afmoe.RoutedExperts` holds experts ``[expert_offset, expert_offset +
  experts_held)`` of ``experts_total`` and computes their part at the rows
  the routing fills): ``y = sum_e w_e FFN_e(m)``. **No shared expert**
  beside it and no norm after it.

The stages are models/cut.py's, given this family's layers
(:func:`_run_layers`) and its RMSNorm as the final norm: split =
client(embedding, unscaled, + the first ``client_depth`` kept layers) ->
server(the rest + final norm + untied head); u_split moves norm and head
back to the client; federated is the composition.

**What ``remat`` recomputes**, in the backward pass: the routed part of
each routed layer (models/afmoe.py's header: it is what gives the three
rungs of rows) and nothing else. Every dense product's output, both
gates' inputs, what the flash kernels' backward reads and the dense
layer's SwiGLU are kept: no product, no flash forward and no
:class:`ShortConv` runs a second time. Decoding is not built: it needs a
convolution's last ``conv_taps - 1`` tokens beside a key/value cache
(runtime/generate.py, ROADMAP.md M7).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from split_learning_tpu.core.stage import SplitPlan
from split_learning_tpu.models import cut
from split_learning_tpu.models.afmoe import (
    RMSNorm, RoutedExperts, SwiGLU, rope)
from split_learning_tpu.obs import spans
from split_learning_tpu.ops.common import causal_depthwise_conv
from split_learning_tpu.ops.flash_attention import (
    flash_attention, select_attention)
from split_learning_tpu.ops.ring_attention import full_attention

_LAYER_TYPES = ("conv", "full_attention")
_INIT = nn.initializers.normal(0.02)
_F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What every layer of one model shares; :func:`lfm2_moe_plan`
    documents each."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    conv_taps: int
    dense_width: int
    expert_width: int
    experts_total: int
    experts_held: int
    expert_offset: int
    experts_per_token: int
    route_scale: float
    rope_theta: float
    eps: float
    layer_types: tuple
    dense_layers: int
    attn: str
    dtype: Any
    remat: bool

    def norm(self, name: str, dtype=None) -> RMSNorm:
        return RMSNorm(self.eps, dtype or self.dtype, name=name)

    def linear(self, features: int, name: str) -> nn.Dense:
        return nn.Dense(features, use_bias=False, dtype=self.dtype,
                        kernel_init=_INIT, name=name)


class ShortConv(nn.Module):
    """``(C * conv(B * x)) W_out`` with ``[B | C | x] = u W_in``."""

    sizes: Sizes

    @nn.compact
    def __call__(self, u):
        s = self.sizes
        d = u.shape[-1]
        bcx = s.linear(3 * d, "in_proj")(u)
        taps = self.param("conv_kernel", _INIT, (s.conv_taps, d))
        with jax.named_scope(spans.SHORT_CONV):
            b, c, x = jnp.split(bcx.astype(_F32), 3, axis=-1)
            y = (c * causal_depthwise_conv(b * x, taps)).astype(s.dtype)
        return s.linear(d, "out_proj")(y)


class Lfm2Attention(nn.Module):
    sizes: Sizes

    @nn.compact
    def __call__(self, u):
        s = self.sizes
        b, t, e = u.shape
        h, hk, d = s.num_heads, s.num_kv_heads, s.head_dim
        q = s.linear(h * d, "q")(u).reshape(b, t, h, d)
        k = s.linear(hk * d, "k")(u).reshape(b, t, hk, d)
        v = s.linear(hk * d, "v")(u).reshape(b, t, hk, d)
        # the norms first, the rotary on what they give
        q = rope(s.norm("q_norm")(q), s.rope_theta)
        k = rope(s.norm("k_norm")(k), s.rope_theta)
        impl = s.attn
        if impl == "auto":
            impl = select_attention(b, t, h, jnp.dtype(s.dtype).itemsize)
        fn = {"flash": flash_attention, "full": full_attention}[impl]
        # the scope names the kernels' calls in a device trace
        with jax.named_scope(spans.ATTN_FULL):
            o = fn(q, k, v, causal=True)
        return s.linear(e, "out")(o.reshape(b, t, h * d))


class Lfm2Layer(nn.Module):
    """The layer of published index ``index``."""

    sizes: Sizes
    index: int

    @nn.compact
    def __call__(self, h):
        s = self.sizes
        b, t, e = h.shape
        u = s.norm("operator_norm")(h)
        if s.layer_types[self.index] == "conv":
            h = h + ShortConv(s, name="conv")(u)
        else:
            h = h + Lfm2Attention(s, name="attn")(u)
        if self.index < s.dense_layers:
            return h + SwiGLU(s.dense_width, s.dtype, name="mlp")(
                s.norm("ffn_norm")(h))
        # the router reads the float32 norm, the experts its rounding
        m32 = s.norm("ffn_norm", _F32)(h)
        return h + RoutedExperts(
            s.expert_width, s.experts_total, s.experts_held, s.expert_offset,
            s.experts_per_token, s.route_scale, s.dtype, s.remat,
            name="experts")(m32.reshape(b * t, e)).reshape(b, t, e)


def _run_layers(h, sizes: Sizes, indices: Sequence[int]):
    """The published layers ``indices`` in order, named ``layer<i>`` (call
    inside a compact method: models/cut.py's stages do, as their
    ``run``)."""
    for i in indices:
        h = Lfm2Layer(sizes, i, name=f"layer{i}")(h)
    return h


def lfm2_moe_plan(mode: str = "split", dtype: Any = jnp.float32, *,
                  vocab: int = 256, d_model: int = 64, num_heads: int = 4,
                  num_kv_heads: int = 2, head_dim: int = 16,
                  conv_taps: int = 3, dense_width: int = 192,
                  expert_width: int = 32, experts_total: int = 8,
                  experts_held: Optional[int] = None, expert_offset: int = 0,
                  experts_per_token: int = 2, route_scale: float = 1.0,
                  layer_types: Sequence[str] = ("conv", "conv",
                                                "full_attention", "conv") * 2,
                  dense_layers: int = 2,
                  layers_kept: Sequence[int] = (1, 2, 3, 4, 5),
                  client_depth: int = 1, rope_theta: float = 1e6,
                  norm_eps: float = 1e-5, attn: str = "auto",
                  remat: bool = True) -> SplitPlan:
    """Build the LFM2-MoE :class:`SplitPlan` for ``mode``.

    The arguments carry the published names' values: ``layer_types`` one
    entry a published layer, the first ``dense_layers`` of them with a
    SwiGLU of ``dense_width``, the rest routed (``experts_held`` of
    ``experts_total`` experts of ``expert_width`` from ``expert_offset``
    on, ``experts_per_token`` a token, no shared one); ``conv_taps`` is
    ``conv_L_cache``. ``layers_kept`` are the published indices of the
    layers built, in order (each keeps the kind and the FFN of its
    published place), of which the client holds the first
    ``client_depth`` beside the embedding. A cut that keeps no layer of a
    kind the model has is refused: it would be another model. ``remat``
    recomputes each routed layer's routed part in the backward pass, at
    the rows its routing fills, and keeps everything else (the module
    header)."""
    cut.check_attn(attn)
    layer_types = tuple(layer_types)
    bad = sorted(set(layer_types) - set(_LAYER_TYPES))
    if bad:
        raise ValueError(f"Unknown layer types {bad} (expected {_LAYER_TYPES})")
    kept = cut.kept_layers(layers_kept, len(layer_types), layer_types)
    held = cut.held_experts(experts_total, experts_held, expert_offset)
    cut.check_client_depth(client_depth, len(kept))
    cut.check_heads(num_heads, num_kv_heads)
    if head_dim % 2:
        raise ValueError(f"rotate-half needs an even head_dim, got {head_dim}")
    eps = float(norm_eps)
    sizes = Sizes(
        num_heads=num_heads, num_kv_heads=num_kv_heads, head_dim=head_dim,
        conv_taps=conv_taps, dense_width=dense_width,
        expert_width=expert_width, experts_total=experts_total,
        experts_held=held, expert_offset=expert_offset,
        experts_per_token=experts_per_token, route_scale=float(route_scale),
        rope_theta=float(rope_theta), eps=eps, layer_types=layer_types,
        dense_layers=dense_layers, attn=attn, dtype=dtype, remat=bool(remat))
    return cut.split_plan(
        mode, cut.EmbedStage(vocab, d_model, _run_layers,
                             (sizes, kept[:client_depth]), dtype),
        (sizes, kept[client_depth:]),
        cut.HeadStage(vocab, RMSNorm(eps, dtype), dtype))
