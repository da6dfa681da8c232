"""Split latent-attention MoE with a multi-token-prediction module (the
``joyai_llm_flash`` family: DeepSeek-V3's layer at another size).

Every layer is pre-norm (RMSNorm(x) = x / sqrt(mean(x^2) + eps) * scale,
statistics in float32, no bias anywhere)::

    h = h + Attn(norm_attn(h));  h = h + FFN(norm_mlp(h))

- **Latent attention (MLA).** ``c_q = norm(u W_qa)`` and ``q = c_q
  W_qb``, a head of which is ``[q_n | q_r]`` (``qk_nope_head_dim`` and
  ``qk_rope_head_dim`` wide); ``u W_kva = [c_kv | k_r]``, ``c_kv =
  norm(c_kv)``, and ``c_kv W_kvb`` gives every head ``[k_n | v]``.
  Rotary positions on **interleaved pairs** (lanes ``2i`` and ``2i + 1``
  turn by ``pos * theta^(-2i / d_r)``; :func:`rope_interleaved`) go on
  ``q_r`` of every head and on the one ``k_r``, which all heads share;
  ``k = [k_n | k_r]``; scores ``q . k * (d_n + d_r)^-0.5``, causal,
  softmax in float32; ``o = P v``; output ``concat(o) W_o``. A query and
  a key are wider than a value (192 against 128 as published), and
  ops/flash_attention.py pads each to its own lane tiles, so the second
  product runs at the values' width.
- **FFN.** The first ``dense_layers`` layers a SwiGLU of
  ``dense_width``; every later one models/afmoe.py's routed layer to the
  letter (:func:`~split_learning_tpu.models.afmoe.route`: sigmoid
  scores in float32, the ``experts_per_token`` best of score plus a
  constant bias, the chosen scores normalised, times ``route_scale``;
  :class:`~split_learning_tpu.models.afmoe.RoutedExperts` holds experts
  ``[expert_offset, expert_offset + experts_held)`` of ``experts_total``
  and computes their part at the rows the routing fills) beside
  ``shared_experts`` shared ones: ``y = FFN_shared(m) + sum_e w_e
  FFN_e(m)``.
- **The multi-token-prediction module** (``mtp_layers`` 1; DeepSeek-V3
  section 2.2). With ``g_i`` the trunk's output after the final norm at
  position ``i`` and ``t_{i+1}`` the label there: ``u_i =
  [norm_e(Emb'(t_{i+1})) ; norm_h(g_i)] W_eh``; ``u' = Block(u)``, an
  expert layer with its own weights, causal, positions ``i``;
  ``logits'_i = Head(norm_s(u'_i))`` through the main head's own leaf.
  The objective is ``mean_i CE(logits_i, t_{i+1}) + mtp_lambda *
  mean_{i < T-1} CE(logits'_i, t_{i+2})``: the last position has no
  second target. **The loss reads the labels as an input**, so the
  final stage carries it as its own objective (``core/stage.Stage``):
  ``apply`` still returns the main logits, and ``objective(params, h,
  labels)`` one loss a token whose mean is the sum above
  (``core/losses.final_loss``). In a split the labels are the server's
  already, and they are token ``i + 1``: nothing new crosses the cut.
  ``Emb'`` is a leaf of the stage that holds the module, not the
  client's embedding (a departure: a leaf shared across parties is
  ROADMAP.md M4b).

Stages as the other families have them (models/cut.py's embedding and
trunk stages; the stage that ends the model is :class:`HeadStage` here,
for the objective it carries): split = client(embedding + the first
``client_depth`` layers) -> server(the rest + final norm + head + module);
u_split moves norm, head and module back to the client; federated is the
composition.

**What ``remat`` recomputes**, in the backward pass: the routed part of
each expert layer (models/afmoe.py's header) and nothing else. Every
dense product's output and what the flash kernels' backward reads (the
padded ``q``, ``k`` and ``v``, 20.5 k values a token and layer, the
output and the row logsumexp) are kept, so no kernel and no dense
product runs a second time. Making ``q``, ``k`` and ``v`` again from
``c_q``, ``c_kv`` and ``k_r`` (2.1 k values) was built and measured: it
takes 1.9 GB off the step's 13.1 and 8 % off its rate (PERF.md,
Findings PR 32), and the step fits without. Without ``remat`` autodiff
keeps everything and the routed part runs at its top rung.

Decoding through a cache is not built: a latent cache holds ``c_kv`` and
``k_r`` and never the keys (the absorbed form, runtime/generate.py,
ROADMAP.md M7).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax

from split_learning_tpu.core.stage import SplitPlan
from split_learning_tpu.models import cut
from split_learning_tpu.models.afmoe import RMSNorm, RoutedExperts, SwiGLU
from split_learning_tpu.obs import spans
from split_learning_tpu.ops.flash_attention import (
    flash_attention, select_attention)
from split_learning_tpu.ops.ring_attention import full_attention

_INIT = nn.initializers.normal(0.02)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The published names' values, as every module of the family reads
    them; :func:`joyai_llm_flash_plan` documents each."""

    d_model: int
    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    dense_width: int
    expert_width: int
    experts_total: int
    experts_held: int
    expert_offset: int
    experts_per_token: int
    shared_experts: int
    route_scale: float
    rope_theta: float
    eps: float
    mtp_lambda: float
    attn: str
    dtype: Any
    remat: bool

    def norm(self, name: str | None = None, dtype=None) -> RMSNorm:
        return RMSNorm(self.eps, dtype or self.dtype, name=name)

    def linear(self, features: int, name: str) -> nn.Dense:
        return nn.Dense(features, use_bias=False, dtype=self.dtype,
                        kernel_init=_INIT, name=name)


def rope_interleaved(x: jax.Array, theta: float) -> jax.Array:
    """Rotary positions on interleaved pairs: lanes ``2i`` and ``2i + 1``
    of the last axis turn by ``pos * theta^(-2i / d)``, position = index
    along axis 1 of ``[B, T, H, d]``; computed in float32."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], d // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    turned = jnp.stack([a * cos - b * sin, a * sin + b * cos], -1)
    return turned.reshape(x.shape).astype(x.dtype)


def _up_projection(sizes: Sizes, c_q, c_kv, k_r, w_qb, w_kvb):
    """``(q, k, v)`` as the attention takes them, from the two compressed
    states ``[B, T, rank]``, the shared rotary key ``[B, T, d_r]`` (not
    yet turned) and the two up-projections' kernels."""
    b, t, _ = c_q.shape
    h, d_n = sizes.num_heads, sizes.qk_nope_head_dim
    dtype, theta = sizes.dtype, sizes.rope_theta
    q = jnp.dot(c_q, w_qb.astype(dtype)).reshape(b, t, h, -1)
    kv = jnp.dot(c_kv, w_kvb.astype(dtype)).reshape(b, t, h, -1)
    q = jnp.concatenate(
        [q[..., :d_n], rope_interleaved(q[..., d_n:], theta)], -1)
    k_r = rope_interleaved(k_r[:, :, None, :], theta)
    k = jnp.concatenate(
        [kv[..., :d_n], jnp.broadcast_to(k_r, (b, t, h, k_r.shape[-1]))], -1)
    return q, k, kv[..., d_n:]


class LatentAttention(nn.Module):
    sizes: Sizes

    @nn.compact
    def __call__(self, u):
        s = self.sizes
        b, t, e = u.shape
        h = s.num_heads
        with jax.named_scope(spans.MLA_PROJ):
            c_q = s.norm("q_a_norm")(s.linear(s.q_lora_rank, "q_a")(u))
            kv_a = s.linear(s.kv_lora_rank + s.qk_rope_head_dim, "kv_a")(u)
            c_kv = s.norm("kv_a_norm")(kv_a[..., :s.kv_lora_rank])
            k_r = kv_a[..., s.kv_lora_rank:]
            w_qb = self.param("q_b", _INIT, (s.q_lora_rank, h * (
                s.qk_nope_head_dim + s.qk_rope_head_dim)))
            w_kvb = self.param("kv_b", _INIT, (s.kv_lora_rank, h * (
                s.qk_nope_head_dim + s.v_head_dim)))
            q, k, v = _up_projection(s, c_q, c_kv, k_r, w_qb, w_kvb)
        impl = s.attn
        if impl == "auto":
            impl = select_attention(b, t, h, jnp.dtype(s.dtype).itemsize)
        fn = {"flash": flash_attention, "full": full_attention}[impl]
        # the scope names the kernels' calls in a device trace
        with jax.named_scope(spans.ATTN_LATENT):
            o = fn(q, k, v, causal=True)
        with jax.named_scope(spans.MLA_PROJ):
            return s.linear(e, "out")(o.reshape(b, t, h * s.v_head_dim))


class Layer(nn.Module):
    """One layer; ``dense``: its FFN is the SwiGLU of ``dense_width``."""

    sizes: Sizes
    dense: bool

    @nn.compact
    def __call__(self, h):
        s = self.sizes
        b, t, e = h.shape
        h = h + LatentAttention(s, name="attn")(s.norm("norm_attn")(h))
        if self.dense:
            return h + SwiGLU(s.dense_width, s.dtype, name="mlp")(
                s.norm("norm_mlp")(h))
        # the router reads the float32 norm, the experts its rounding
        m32 = s.norm("norm_mlp", jnp.float32)(h)
        with jax.named_scope(spans.MOE_SHARED):
            y = SwiGLU(s.expert_width * s.shared_experts, s.dtype,
                       name="shared")(m32.astype(s.dtype))
        y = y + RoutedExperts(
            s.expert_width, s.experts_total, s.experts_held, s.expert_offset,
            s.experts_per_token, s.route_scale, s.dtype, s.remat,
            name="experts")(m32.reshape(b * t, e)).reshape(b, t, e)
        return h + y


def _run_layers(h, sizes: Sizes, first: int, count: int, dense_layers: int):
    """Layers ``[first, first + count)`` of the model, named ``layer<i>``
    by their index in it (call inside a compact method)."""
    for i in range(first, first + count):
        h = Layer(sizes, i < dense_layers, name=f"layer{i}")(h)
    return h


class PredictionModule(nn.Module):
    """The multi-token-prediction module up to its last norm: ``[B, T,
    d]`` trunk states after the final norm and ``[B, T]`` labels (the
    token after each) in, ``norm_s(Block([norm_e(Emb'(label)) ; norm_h(
    state)] W_eh))`` out, for the head to read."""

    vocab: int
    sizes: Sizes

    @nn.compact
    def __call__(self, g, labels):
        s = self.sizes
        emb = nn.Embed(self.vocab, s.d_model, dtype=s.dtype,
                       embedding_init=_INIT, name="tok")(labels)
        u = s.linear(s.d_model, "eh")(jnp.concatenate(
            [s.norm("norm_e")(emb), s.norm("norm_h")(g)], -1))
        return s.norm("norm_s")(Layer(s, False, name="block")(u))


class HeadStage(nn.Module):
    """The rest of the layers (none in the U-shape, where this is the
    client's top stage), the final norm, the untied head over the
    vocabulary rows held and, with ``predict``, the prediction module, whose
    logits go through the same head leaf. Products in the compute type,
    accumulated in float32; logits and losses float32."""

    vocab: int
    sizes: Sizes
    layers: tuple
    predict: bool = True

    def setup(self):
        s = self.sizes
        first, count, dense_layers = self.layers
        for i in range(first, first + count):
            setattr(self, f"layer{i}", Layer(s, i < dense_layers))
        self.norm_f = s.norm()
        self.lm_head = self.param("lm_head", _INIT, (s.d_model, self.vocab))
        if self.predict:
            self.mtp = PredictionModule(self.vocab, s)

    def _normed(self, h):
        first, count, _ = self.layers
        for i in range(first, first + count):
            h = getattr(self, f"layer{i}")(h)
        return self.norm_f(h)

    def _logits(self, x):
        return jnp.dot(x, self.lm_head.astype(self.sizes.dtype),
                       preferred_element_type=jnp.float32)

    def __call__(self, h, *, cache_len: int = 0, decode_cache=None,
                 pos=None):
        cut.no_cache(cache_len, decode_cache)
        return self._logits(self._normed(h))

    def losses(self, h, labels):
        """``[B, T]`` float32, one loss a token, whose mean is the
        objective of the module header: the main cross-entropy, and on
        every position but a row's last ``mtp_lambda * T / (T - 1)``
        times the module's at the label one further on."""
        ce = optax.softmax_cross_entropy_with_integer_labels
        g = self._normed(h)
        main = ce(self._logits(g), labels)
        if not self.predict:
            return main
        t = labels.shape[1]
        if t < 2:
            raise ValueError("the second prediction needs two positions")
        with jax.named_scope(spans.MTP):
            second = ce(self._logits(self.mtp(g, labels)),
                        jnp.roll(labels, -1, axis=1))
        weight = self.sizes.mtp_lambda * t / (t - 1)
        return main + jnp.where(jnp.arange(t) < t - 1, weight * second, 0.0)


def joyai_llm_flash_plan(
        mode: str = "split", dtype: Any = jnp.float32, *, vocab: int = 256,
        d_model: int = 64, num_heads: int = 4, q_lora_rank: int = 48,
        kv_lora_rank: int = 32, qk_nope_head_dim: int = 16,
        qk_rope_head_dim: int = 8, v_head_dim: int = 16,
        dense_width: int = 192, expert_width: int = 32,
        experts_total: int = 8, experts_held: int | None = None,
        expert_offset: int = 0, experts_per_token: int = 2,
        shared_experts: int = 1, route_scale: float = 2.5, layers: int = 5,
        dense_layers: int = 1, client_depth: int = 1,
        rope_theta: float = 32e6, rms_norm_eps: float = 1e-6,
        mtp_layers: int = 1, mtp_lambda: float = 0.3, attn: str = "auto",
        remat: bool = True) -> SplitPlan:
    """Build the family's :class:`SplitPlan` for ``mode``.

    The arguments carry the published names' values for the ``layers``
    kept: the first ``dense_layers`` of them dense (SwiGLU of
    ``dense_width``), the rest routed (``experts_held`` of
    ``experts_total`` experts of ``expert_width`` from ``expert_offset``
    on, ``experts_per_token`` a token, beside ``shared_experts`` shared
    ones), every one with latent attention (``q_lora_rank``,
    ``kv_lora_rank``, the three head widths). The client holds the
    embedding and the first ``client_depth`` layers. ``mtp_layers`` (0 or
    1) is ``num_nextn_predict_layers``: with 1 the final stage carries
    the module and its objective, whose second loss weighs
    ``mtp_lambda``. ``remat``: the module header."""
    cut.check_attn(attn)
    held = cut.held_experts(experts_total, experts_held, expert_offset)
    cut.check_client_depth(client_depth, layers)
    if mtp_layers not in (0, 1):
        raise ValueError(f"mtp_layers {mtp_layers}: one prediction module "
                         "or none")
    if qk_rope_head_dim % 2:
        raise ValueError(f"rotary pairs need an even qk_rope_head_dim, got "
                         f"{qk_rope_head_dim}")
    sizes = Sizes(
        d_model=d_model, num_heads=num_heads, q_lora_rank=q_lora_rank,
        kv_lora_rank=kv_lora_rank, qk_nope_head_dim=qk_nope_head_dim,
        qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
        dense_width=dense_width, expert_width=expert_width,
        experts_total=experts_total, experts_held=held,
        expert_offset=expert_offset, experts_per_token=experts_per_token,
        shared_experts=shared_experts, route_scale=float(route_scale),
        rope_theta=float(rope_theta), eps=float(rms_norm_eps),
        mtp_lambda=float(mtp_lambda), attn=attn, dtype=dtype,
        remat=bool(remat))
    span = lambda first, count: (first, count, dense_layers)
    rest = span(client_depth, layers - client_depth)
    # the head carries the objective over its own leaves, so the stage that
    # ends the model is the family's in either mode
    ends = lambda own: HeadStage(vocab, sizes, own, bool(mtp_layers))
    return cut.split_plan(
        mode, cut.EmbedStage(vocab, d_model, _run_layers,
                             (sizes, *span(0, client_depth)), dtype),
        (sizes, *rest), ends(span(layers, 0)), top=ends(rest),
        objective="losses" if mtp_layers else None)
