"""The LFM2-MoE layer stack (LFM2-24B-A2B) and its loss, plain: float32
``jax.numpy``, every matrix product through ``common.matmul(precision)``,
nothing imported from the program.  The weights come in the program's tree
layout, made by ``weights.py``; the sizes from the configuration's
``plan.kwargs`` (the published names' values; ``layer_types`` one entry a
published layer, the layers built named ``layer<i>`` by published index).
RMSNorm, rotate-half rotary, the SwiGLU, the routed layer, the blocked
attention and the head's loss are ``reference/afmoe.py``'s: the models share
that routed layer to the letter.

RMSNorm(x) = x / sqrt(mean(x^2) + eps) * scale; no biases anywhere; pre-norm
residuals and nothing after a branch: ``h += Op(operator_norm(h))``, ``h +=
FFN(ffn_norm(h))``.

- embedding: ``h = E[tokens]``, no scaling, no position table.
- a ``conv`` layer's operator, ``u = operator_norm(h)``: ``[B | C | x] = u
  W_in`` (three parts of ``d`` in that order); ``g = B * x``; ``c_t = sum_k
  w_k * g_{t - (K - 1) + k}`` per channel (``w [K, d]``, the last tap weighs
  the current token, ``g`` is zero before the sequence's start, no bias);
  ``y = (C * c) W_out``.  No activation.
- a ``full_attention`` layer's operator: ``q = u Wq`` as [T, H, D], ``k = u
  Wk``, ``v = u Wv`` as [T, H_kv, D]; q and k RMS-normed over D with a scale
  each, then rotary positions (theta from the config, rotate-half, position
  = index) on both; query head n reads key/value head n // (H / H_kv);
  scores times D^-0.5, causal; softmax; ``(P V) Wo``.  No output gate.
- FFN, ``m = ffn_norm(h)``: a layer that holds ``mlp`` ``SwiGLU(m)``; the
  others ``s = sigmoid(m Wr)`` over all the router's outputs, chosen = top-k
  of ``s + expert_bias``, ``w = s[chosen] / (sum + 1e-20) * route_scale``,
  and the sum over the chosen experts **held here** of ``w_e SwiGLU_e(m)``.
  No shared expert.
- head: ``norm_f``, the untied head over the vocabulary rows held, mean
  cross-entropy.

Departures from the equations, all to fit T 8192 in float32 beside the
training state, none changing a value: attention runs one key/value head at
a time (its query heads' columns of Wq and rows of Wo, the partial output
projections summed) and inside that one block of queries at a time against
all keys, masked; the held experts are a scan in which every expert computes
all tokens and is weighted by ``w_e``; every SwiGLU, the head and its loss go
over blocks of tokens; every such group, block and expert, and every layer,
is recomputed in the backward pass.  The router's product is float32 at every
``precision``; the controls round every other product's operands (the
convolution's gates and taps are no product and stay float32).
``expert_bias`` is a constant under ``stop_gradient``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common
from .afmoe import banded_attention, head_loss, rms_norm, rope, routed, swiglu


def short_conv(c, u, mm):
    """The gated short convolution of one sequence, u [T, d] the normed
    input."""
    t = u.shape[0]
    b, gate, x = jnp.split(mm(u, c["in_proj"]["kernel"]), 3, axis=-1)
    taps = c["conv_kernel"]
    past = jnp.pad(b * x, ((taps.shape[0] - 1, 0), (0, 0)))
    conv = sum(taps[k] * past[k:k + t] for k in range(taps.shape[0]))
    return mm(gate * conv, c["out_proj"]["kernel"])


def attention(a, u, kw, mm):
    """The attention operator for one sequence, u [T, d] the normed input.
    A scan over the key/value heads: each takes the columns of Wq and the
    rows of Wo of its own query heads; each group recomputed in the
    backward pass."""
    t, eps, theta = u.shape[0], kw["norm_eps"], kw["rope_theta"]
    heads, hk, d = kw["num_heads"], kw["num_kv_heads"], kw["head_dim"]
    g = heads // hk
    k = rms_norm(a["k_norm"], mm(u, a["k"]["kernel"]).reshape(t, hk, d), eps)
    k = rope(k, theta)
    v = mm(u, a["v"]["kernel"]).reshape(t, hk, d)

    def one_group(wq, wo, kh, vh):
        q = rms_norm(a["q_norm"], mm(u, wq).reshape(t, g, d), eps)
        o = banded_attention(rope(q, theta), kh, vh, None, mm)
        return mm(o.reshape(t, g * d), wo)

    def step(acc, xs):
        return acc + jax.checkpoint(one_group)(*xs), None

    wq = a["q"]["kernel"]
    out, _ = jax.lax.scan(step, jnp.zeros_like(u), (
        wq.reshape(wq.shape[0], hk, g * d).transpose(1, 0, 2),
        a["out"]["kernel"].reshape(hk, g * d, -1),
        k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return out


def layer(p, h, kw: dict, mm):
    """One layer on one sequence h [T, d]; its kind is the operator it
    holds, its FFN dense where it holds ``mlp``."""
    eps = kw["norm_eps"]
    u = rms_norm(p["operator_norm"], h, eps)
    h = h + (short_conv(p["conv"], u, mm) if "conv" in p
             else attention(p["attn"], u, kw, mm))
    m = rms_norm(p["ffn_norm"], h, eps)
    if "mlp" in p:
        return h + swiglu(*(p["mlp"][n]["kernel"] for n in ("gate", "up", "down")),
                          m, mm)
    return h + routed(p["experts"], m, kw, mm)


def layers(p, h, kw: dict, mm):
    """Every ``layer<i>`` of ``p`` in order of i, each recomputed in the
    backward pass."""
    for i in sorted(int(name[5:]) for name in p if name.startswith("layer")):
        h = jax.checkpoint(lambda lp, y: layer(lp, y, kw, mm))(p[f"layer{i}"], h)
    return h


def loss_fn(config: dict, precision: str):
    kw = {"rope_theta": 1e6, "norm_eps": 1e-5, "route_scale": 1.0,
          "expert_offset": 0, **config["plan"]["kwargs"]}
    mm = common.matmul(precision)

    def one_sequence(c, s, tokens, labels):
        h = layers(s, layers(c, c["tok"]["embedding"][tokens], kw, mm), kw, mm)
        return head_loss(s["head"], h, labels, kw["norm_eps"], mm)

    def loss(client, server, tokens, labels):
        c, s = client["params"], server["params"]
        per_row = jax.lax.map(lambda a: one_sequence(c, s, *a), (tokens, labels))
        return per_row.mean()

    return loss
