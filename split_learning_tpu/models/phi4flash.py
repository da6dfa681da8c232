"""Split SambaY — a decoder-hybrid-decoder (the ``phi4flash`` family,
arXiv:2507.06607): state-space layers beside differential attention, and
a second half whose layers read a memory and a key/value set that two
layers of the first half made, instead of making their own.

The kind of layer ``i`` of ``L`` follows from ``L`` and ``mb_per_layer``
alone (:func:`layer_kind`): ``i % mb_per_layer == 0`` is a state-space
layer, the others attention; below ``L / 2`` they are Mamba and
sliding-window attention; layer ``L / 2`` is a Mamba that also
**exports its scan output** ``m``, layer ``L / 2 + 1`` a full attention
that also **exports its keys and values**; from ``L / 2 + 2`` on a
state-space layer is a Gated Memory Unit that reads ``m`` and an
attention layer projects queries only and reads those keys and values.
So a layer is no function of the hidden state alone: ``m`` and ``(K,
V)`` travel beside ``h`` from layer to layer *inside one stage*. A
stage stays ``(params, x) -> y`` with one cut tensor, so a plan whose
reading layers lie on another stage than the layers they read, or that
keeps no such layer, is refused (ROADMAP.md M4: a cut that carries more
than one tensor).

Every layer (LN = LayerNorm with scale and bias)::

    h = h + mixer(LN_1(h));  h = h + (silu(g) * u) W_down,
    [g; u] = LN_2(h) W_gu

- **Mamba** (Mamba-1): ``[x; z] = u W_in``; ``x' = silu(conv(x) +
  b_c)``, a causal depthwise convolution over ``d_conv`` tokens
  (ops/causal_conv.py's ``conv_silu``, float32 from ``x`` as stored); ``[r;
  B; C] = x' W_x``; ``delta = softplus(r W_dt + b_dt)``; ``A =
  -exp(A_log)``; the selective scan (ops/selective_scan.py) gives ``y``;
  **m = y**, after the skip and before the gate; ``out = (y * silu(z))
  W_out``. The convolution, ``delta``, ``A`` and the scan in float32.
- **GMU**: ``out = (m * silu(u W_1)) W_2``. No scan, no state.
- **Differential attention** (arXiv:2410.05258, two softmaxes): ``[q;
  k; v] = u W_qkv + b``; adjacent heads pair: ``q1 = q[0::2]``, ``q2 =
  q[1::2]``, likewise ``k``; ``v' = [v[0::2]; v[1::2]]`` side by side,
  twice the head size; query pair ``n`` reads key/value pair ``n //
  (H / H_kv)``; ``a_i = softmax(mask(q_i k_i^T / sqrt(D))) v'``;
  ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``,
  ``lambda_init = 0.8 - 0.6 exp(-0.3 l)`` with ``l`` the layer's
  published index; ``o = RMSNorm(a_1 - lambda a_2) * (1 -
  lambda_init)``; ``out = o W_o + b_o``. Causal everywhere; on a window
  layer also ``0 <= i - j < window``. **One kernel call a layer serves
  both maps** (ops/flash_attention.py takes one head size): queries
  ``[q1; q2]`` and keys ``[k1; k2]`` zero-padded from ``D`` to ``2 D``
  (and the queries scaled so that the kernel's own ``(2 D)^-0.5``
  comes out as ``D^-0.5``), values ``[v'; v']``; head ``n`` reads ``n
  // group`` as the kernel has it.
- **Cross-attention**: ``q = u W_q + b_q`` only; ``k``, ``v`` are the
  exporting layer's, paired as there; its own lambdas, norm and ``W_o``.

Stages as the other families have them (models/cut.py, with a LayerNorm
as the final norm): split = client(embedding + the first ``client_depth``
kept layers) -> server(the rest + norm + head); u_split moves norm + head
back to the client; federated is the composition. ``remat`` recomputes, in the backward pass, the two passes
around each layer's widest product and nothing else: an MLP keeps the
output of its ``2 * mlp_width`` wide product ``LN_2(h) W_gu`` and makes
its LayerNorm and ``silu(g) * u`` again. What costs a pass over memory
is made twice, what costs a product is not, every mixer's values are
kept, and so no product and no kernel's forward runs a second time. XLA's
analysis of the fused step at the benchmark's sizes (T 8192, five
layers; scripts/fused_step_memory.py, the fit rule is 14.5 GB): nothing
recomputed 14.916 GB, this form 14.496 GB, the MLPs whole (the product
too: five more products a step) 12.822 GB, whole layers 10.282 GB. Weights
are float32, products run in ``dtype``. Decoding is not built: it needs
a recurrent state beside a key/value cache (runtime/generate.py).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from split_learning_tpu.core.stage import SplitPlan
from split_learning_tpu.models import cut
from split_learning_tpu.models.afmoe import RMSNorm
from split_learning_tpu.obs import spans
from split_learning_tpu.ops.causal_conv import conv_silu
from split_learning_tpu.ops.flash_attention import (
    flash_attention, select_attention)
from split_learning_tpu.ops.ring_attention import full_attention
from split_learning_tpu.ops.selective_scan import selective_scan

_INIT = nn.initializers.normal(0.02)
_F32 = jnp.float32
_KEPT = "mlp_gate_up"    # the one value a layer's ``remat`` keeps


def layer_kind(index: int, num_layers: int, mb_per_layer: int) -> str:
    """The kind of published layer ``index`` (the module header)."""
    half = num_layers // 2
    ssm = mb_per_layer > 0 and index % mb_per_layer == 0
    if index >= half + 2:
        return "gmu" if ssm else "cross"
    if ssm:
        return "mamba"
    return "window" if index < half else "full"


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What every layer of one model shares."""

    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    mlp_width: int
    window: int
    d_state: int
    d_conv: int
    d_inner: int
    dt_rank: int
    layers_published: int
    mb_per_layer: int
    eps: float
    attn: str
    dtype: Any
    remat: bool

    def kind(self, index: int) -> str:
        return layer_kind(index, self.layers_published, self.mb_per_layer)

    @property
    def memory_layer(self) -> int:      # exports m
        return self.layers_published // 2

    @property
    def kv_layer(self) -> int:          # exports (K, V)
        return self.layers_published // 2 + 1


def _dense(features: int, dtype, name: str, bias: bool = False) -> nn.Dense:
    return nn.Dense(features, use_bias=bias, dtype=dtype, kernel_init=_INIT,
                    name=name)


def _product(x, kernel, dtype):
    """``x @ kernel`` with operands in ``dtype``, the result float32."""
    return jnp.dot(x.astype(dtype), kernel.astype(dtype),
                   preferred_element_type=_F32)


class Mamba(nn.Module):
    """Returns ``(out, y)``: the mixer's output and the scan's, which the
    exporting layer hands on as the memory."""

    sizes: Sizes

    @nn.compact
    def __call__(self, u):
        z_ = self.sizes
        dtype, inner, n = z_.dtype, z_.d_inner, z_.d_state
        x, z = jnp.split(_dense(2 * inner, dtype, "in_proj")(u), 2, axis=-1)
        conv_w = self.param("conv_kernel", _INIT, (z_.d_conv, inner))
        conv_b = self.param("conv_bias", nn.initializers.zeros, (inner,))
        x_proj = self.param("x_proj", _INIT, (inner, z_.dt_rank + 2 * n))
        dt_proj = self.param("dt_proj", _INIT, (z_.dt_rank, inner))
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (inner,))
        # [d_state, d_inner]: the wide axis last, as the scan kernel lays
        # the state out (a 16-wide last axis pads eightfold on the chip)
        a_log = self.param("A_log", nn.initializers.zeros, (n, inner))
        d_skip = self.param("D", nn.initializers.ones, (inner,))
        with jax.named_scope(spans.SSM_CONV):
            # x'_t from x_{t - d_conv + 1 .. t}: tap k weighs x_{t-(K-1)+k}
            x = conv_silu(x, conv_w, conv_b, _F32)
        r, b, c = jnp.split(_product(x, x_proj, dtype),
                            [z_.dt_rank, z_.dt_rank + n], axis=-1)
        delta = jax.nn.softplus(_product(r, dt_proj, dtype) + dt_bias)
        with jax.named_scope(spans.SSM_SCAN):
            y = selective_scan(x, delta, -jnp.exp(a_log).T, b, c, d_skip)
        gated = y * jax.nn.silu(z.astype(_F32))
        return _dense(u.shape[-1], dtype, "out_proj")(gated.astype(dtype)), y


class GatedMemoryUnit(nn.Module):
    sizes: Sizes

    @nn.compact
    def __call__(self, u, memory):
        dtype = self.sizes.dtype
        with jax.named_scope(spans.GMU):
            gate = _dense(memory.shape[-1], dtype, "in_proj")(u)
            gated = memory * jax.nn.silu(gate.astype(_F32))
            return _dense(u.shape[-1], dtype, "out_proj")(gated.astype(dtype))


def pair_heads(x, side_by_side: bool = False):
    """``[B, T, H, D]`` with adjacent heads paired: the even heads then
    the odd ones (``[B, T, H, D]``), or each pair's two side by side
    (``[B, T, H / 2, 2 D]``, the values' form)."""
    return jnp.concatenate([x[:, :, 0::2], x[:, :, 1::2]],
                           axis=-1 if side_by_side else 2)


def two_maps(q, k, v, window, impl: str, scope: str):
    """Both softmax maps of a differential attention in one call: ``q [B,
    T, H, D]`` and ``k [B, T, H_kv, D]`` paired by :func:`pair_heads`,
    ``v [B, T, H_kv / 2, 2 D]`` the paired values. Returns ``(a1, a2)``,
    each ``[B, T, H / 2, 2 D]``."""
    d = q.shape[-1]
    if impl == "flash":
        # the kernel has one head size and scales by its -1/2 power
        grow = lambda x: jnp.pad(x, ((0, 0),) * 3 + ((0, v.shape[-1] - d),))
        q = grow(q * jnp.asarray((v.shape[-1] / d) ** 0.5, q.dtype))
        k = grow(k)
    fn = {"flash": flash_attention, "full": full_attention}[impl]
    with jax.named_scope(scope):    # names the kernels' calls in a trace
        o = fn(q, k, jnp.concatenate([v, v], axis=2), causal=True,
               window=window)
    return jnp.split(o, 2, axis=2)


def lambda_init(index: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * index)


class DiffAttention(nn.Module):
    """Returns ``(out, (k, v))``, the keys and values in the paired form
    :func:`two_maps` takes: what the exporting layer hands on and a
    cross layer is handed."""

    sizes: Sizes
    kind: str                 # "window", "full" or "cross"
    index: int                # the layer's published index

    @nn.compact
    def __call__(self, u, kv=None):
        z_ = self.sizes
        b, t, e = u.shape
        h, hk, d, dtype = z_.num_heads, z_.num_kv_heads, z_.head_dim, z_.dtype
        if self.kind == "cross":
            q = _dense(h * d, dtype, "q", bias=True)(u)
            k, v = kv
        else:
            # one product; its bias is three leaves, because the keys'
            # part has no gradient (a softmax does not see a shift of
            # its keys) and an optimizer that normalises steps would
            # move that part of a shared leaf by rounding noise
            bias = lambda name, heads: self.param(
                name, nn.initializers.zeros, (heads * d,)).astype(dtype)
            q, k, v = jnp.split(_dense((h + 2 * hk) * d, dtype, "qkv")(u),
                                [h * d, (h + hk) * d], axis=-1)
            q = q + bias("q_bias", h)
            k = pair_heads((k + bias("k_bias", hk)).reshape(b, t, hk, d))
            v = pair_heads((v + bias("v_bias", hk)).reshape(b, t, hk, d),
                           side_by_side=True)
        impl = z_.attn
        if impl == "auto":
            impl = select_attention(b, t, h, jnp.dtype(dtype).itemsize)
        scope = {"window": spans.ATTN_WINDOW, "full": spans.ATTN_FULL,
                 "cross": spans.ATTN_CROSS}[self.kind]
        a1, a2 = two_maps(
            pair_heads(q.reshape(b, t, h, d)), k, v,
            z_.window if self.kind == "window" else None, impl, scope)
        vec = lambda name: self.param(name, _INIT, (d,))
        start = lambda_init(self.index)
        lam = (jnp.exp(jnp.sum(vec("lambda_q1") * vec("lambda_k1")))
               - jnp.exp(jnp.sum(vec("lambda_q2") * vec("lambda_k2"))) + start)
        o = a1.astype(_F32) - lam * a2.astype(_F32)
        o = RMSNorm(z_.eps, _F32, name="subln")(o) * (1.0 - start)
        out = _dense(e, dtype, "out", bias=True)(
            o.reshape(b, t, h * d).astype(dtype))
        return out, (k, v)


class SwiGLU(nn.Module):
    """``(silu(g) * u) W_down`` with ``[g; u] = LN(h) W_gu``. The product
    ``[g; u]`` carries the name :data:`_KEPT`: what a ``remat`` of this
    module keeps."""

    sizes: Sizes

    @nn.compact
    def __call__(self, h):
        z_ = self.sizes
        x = nn.LayerNorm(epsilon=z_.eps, dtype=z_.dtype, name="ln")(h)
        gu = checkpoint_name(
            _dense(2 * z_.mlp_width, z_.dtype, "gate_up")(x), _KEPT)
        g, up = jnp.split(gu, 2, axis=-1)
        return _dense(h.shape[-1], z_.dtype, "down")(jax.nn.silu(g) * up)


class Phi4FlashLayer(nn.Module):
    """``(h, memory, kv) -> (h, shared)``: ``shared`` is what the mixer
    made that a later layer may read (a Mamba's scan output, an
    attention's keys and values), None for a GMU."""

    sizes: Sizes
    index: int

    @nn.compact
    def __call__(self, h, memory, kv):
        z_ = self.sizes
        kind = z_.kind(self.index)
        u = nn.LayerNorm(epsilon=z_.eps, dtype=z_.dtype, name="ln1")(h)
        if kind == "mamba":
            out, shared = Mamba(z_, name="mamba")(u)
        elif kind == "gmu":
            out, shared = GatedMemoryUnit(z_, name="gmu")(u, memory), None
        else:
            out, shared = DiffAttention(z_, kind, self.index,
                                        name="attn")(u, kv)
        h = h + out
        # the product is kept, the LayerNorm and the gate made again
        keep = jax.checkpoint_policies.save_only_these_names(_KEPT)
        mlp = nn.remat(SwiGLU, policy=keep) if z_.remat else SwiGLU
        return h + mlp(z_, name="mlp")(h), shared


def _run_layers(h, sizes: Sizes, indices: Sequence[int]):
    """The published layers ``indices`` in order, named ``layer<i>`` (call
    inside a compact method); the memory and the key/value set go from
    the layers that export them to the layers after."""
    memory = kv = None
    for i in indices:
        h, shared = Phi4FlashLayer(sizes, i, name=f"layer{i}")(h, memory, kv)
        if i == sizes.memory_layer:
            memory = shared
        if i == sizes.kv_layer:
            kv = shared
    return h


def _check_sharing(sizes: Sizes, stages: Sequence[Sequence[int]]) -> None:
    """A layer that reads the memory or the key/value set needs the layer
    that exports it earlier in its own stage."""
    for layers in stages:
        for at, i in enumerate(layers):
            source = {"gmu": sizes.memory_layer,
                      "cross": sizes.kv_layer}.get(sizes.kind(i))
            if source is not None and source not in layers[:at]:
                raise ValueError(
                    f"layer {i} ({sizes.kind(i)}) reads what layer {source} "
                    f"({sizes.kind(source)}) exports, and a stage hands on "
                    f"one tensor: keep layer {source} before it in the same "
                    f"stage (stages hold {[list(s) for s in stages]})")


def phi4flash_plan(mode: str = "split", dtype: Any = jnp.float32, *,
                   vocab: int = 256, d_model: int = 64, num_heads: int = 4,
                   num_kv_heads: int = 2, head_dim: int = 16,
                   mlp_width: int = 128, window: int = 8, d_state: int = 4,
                   d_conv: int = 4, expand: int = 2, dt_rank: int = 4,
                   layers_published: int = 32, mb_per_layer: int = 2,
                   layers_kept: Sequence[int] = (15, 16, 17, 18, 19),
                   client_depth: int = 1, eps: float = 1e-5,
                   attn: str = "auto", remat: bool = True) -> SplitPlan:
    """Build the SambaY :class:`SplitPlan` for ``mode``.

    The arguments carry the published names' values; ``layers_kept`` are
    the published indices of the layers built, in order (each keeps the
    kind and the ``lambda_init`` of its published place), of which the
    client holds the first ``client_depth`` beside the embedding.
    ``d_inner`` is ``expand * d_model``. ``remat`` makes each MLP's
    LayerNorm and gate again in the backward pass and keeps its ``2 *
    mlp_width`` wide product (the module header, with XLA's four figures:
    14.916 GB with nothing recomputed, 14.496 so, 12.822 with the product
    recomputed too, 10.282 with whole layers)."""
    cut.check_attn(attn)
    kept = cut.kept_layers(layers_kept, layers_published)
    cut.check_client_depth(client_depth, len(kept))
    if num_kv_heads % 2:
        raise ValueError(f"{num_kv_heads} key/value heads do not pair up")
    cut.check_heads(num_heads, num_kv_heads)
    sizes = Sizes(
        d_model=d_model, num_heads=num_heads, num_kv_heads=num_kv_heads,
        head_dim=head_dim, mlp_width=mlp_width, window=window,
        d_state=d_state, d_conv=d_conv, d_inner=expand * d_model,
        dt_rank=dt_rank, layers_published=layers_published,
        mb_per_layer=mb_per_layer, eps=float(eps), attn=attn, dtype=dtype,
        remat=bool(remat))
    bottom, rest = kept[:client_depth], kept[client_depth:]
    _check_sharing(sizes, (bottom, rest))
    return cut.split_plan(
        mode, cut.EmbedStage(vocab, d_model, _run_layers, (sizes, bottom),
                             dtype), (sizes, rest),
        cut.HeadStage(vocab, nn.LayerNorm(epsilon=sizes.eps, dtype=dtype),
                      dtype))
