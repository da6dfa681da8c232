"""The AFMoE layer stack (Trinity) and its loss, plain: float32
``jax.numpy``, every matrix product through ``common.matmul(precision)``,
nothing imported from the program.  The weights come in the program's tree
layout, made by ``weights.py``; the sizes from the configuration's
``plan.kwargs`` (the published names' values for the layers kept).

RMSNorm(x) = x / sqrt(mean(x^2) + eps) * scale; no biases anywhere.

- embedding: ``h = E[tokens] * sqrt(d_model)`` (``mup_enabled``), no
  position table.
- attention: ``u = norm_in(h)``; ``q = u Wq`` as [T, H, D], ``k = u Wk``,
  ``v = u Wv`` as [T, H_kv, D], ``g = u Wg`` [T, H D]; q and k RMS-normed
  over D; on a ``sliding_attention`` layer rotary positions (theta from the
  config, rotate-half, position = index), on a ``full_attention`` layer
  none; query head n reads key/value head n // (H / H_kv); scores times
  D^-0.5, causal, on a sliding layer only 0 <= i - j < window; softmax;
  ``a = (P V) * sigmoid(g)``; ``h = h + norm_post_attn(a Wo)``.
- dense layer: ``h = h + norm_post_mlp(SwiGLU(norm_pre_mlp(h)))``.
- expert layer: ``m = norm_pre_mlp(h)``; ``s = sigmoid(m Wr)`` over all the
  router's outputs; chosen = top-k of ``s + expert_bias``; ``w = s[chosen] /
  (sum + 1e-20) * route_scale``; routed = sum over the chosen experts
  **held here** of ``w_e SwiGLU_e(m)``; ``h = h + norm_post_mlp(shared(m) +
  routed)``.
- head: ``norm_f``, the untied head over the vocabulary rows held, mean
  cross-entropy.

Departures from the equations, all to fit T 8192 in float32 beside the
training state, none changing a value: attention runs one key/value head at
a time (its query heads' columns of Wq and Wg and rows of Wo, the partial
output projections summed) and inside that one block of queries at a time
against all keys, masked; the held experts are a scan over the stacked
leaves in which every expert computes all tokens and is weighted by ``w_e``
(zero for tokens that did not choose it); every SwiGLU, the head and its
loss go over blocks of tokens; every such group, block and expert, and every
layer, is recomputed in the backward pass.  The router's product is float32 at every
``precision``, as the model states it; the controls round every other
product's operands.  ``expert_bias`` is a constant under ``stop_gradient``
(configs/trinity-mini.json, departures).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common

_HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 256
TOKEN_BLOCK = 1024


def rms_norm(p, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * p["scale"]


def rope(x, theta):
    """x [T, H, D]; rotate-half, position = index."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _blocks(n: int, size: int) -> int:
    """The block edge: ``size`` where it divides ``n``, else all of it."""
    return size if n % size == 0 else n


def banded_attention(q, k, v, window, mm):
    """q [T, G, D] (the G query heads of one key/value head), k and v
    [T, D] -> [T, G, D]; causal, banded if ``window``.  One block of
    queries at a time against every key, masked; each block recomputed in
    the backward pass."""
    t, g, d = q.shape
    edge = _blocks(t, QUERY_BLOCK)
    cols = jnp.arange(t)[None, :]

    def one_block(qb, q0):         # qb [G, edge, D]
        s = mm(qb, jnp.broadcast_to(k.T, (g, d, t))) * d ** -0.5
        behind = (q0 + jnp.arange(edge))[:, None] - cols
        ok = behind >= 0
        if window is not None:
            ok &= behind < window
        p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
        return mm(p, jnp.broadcast_to(v, (g, t, d)))

    qb = q.reshape(t // edge, edge, g, d).transpose(0, 2, 1, 3)
    starts = jnp.arange(t // edge) * edge
    o = jax.lax.map(lambda a: jax.checkpoint(one_block)(*a), (qb, starts))
    return o.transpose(0, 2, 1, 3).reshape(t, g, d)


def attention(a, u, window, kw, mm):
    """The attention part of a layer for one sequence, u [T, d] the normed
    input: returns ``(attention * sigmoid(gate)) Wo``.  A scan over the
    key/value heads: each takes the columns of Wq and Wg, and the rows of
    Wo, of its own query heads, so only one group's activations live at a
    time; each group recomputed in the backward pass."""
    t, eps = u.shape[0], kw["rms_norm_eps"]
    heads, hk, d = kw["num_heads"], kw["num_kv_heads"], kw["head_dim"]
    g = heads // hk
    k = rms_norm(a["k_norm"], mm(u, a["k"]["kernel"]).reshape(t, hk, d), eps)
    v = mm(u, a["v"]["kernel"]).reshape(t, hk, d)
    if window is not None:
        k = rope(k, kw["rope_theta"])
    by_group = lambda w: w.reshape(w.shape[0], hk, g * d).transpose(1, 0, 2)

    def one_group(wq, wg, wo, kh, vh):
        q = rms_norm(a["q_norm"], mm(u, wq).reshape(t, g, d), eps)
        if window is not None:
            q = rope(q, kw["rope_theta"])
        o = banded_attention(q, kh, vh, window, mm).reshape(t, g * d)
        return mm(o * jax.nn.sigmoid(mm(u, wg)), wo)

    def step(acc, xs):
        return acc + jax.checkpoint(one_group)(*xs), None

    out, _ = jax.lax.scan(step, jnp.zeros_like(u), (
        by_group(a["q"]["kernel"]), by_group(a["gate"]["kernel"]),
        a["out"]["kernel"].reshape(hk, g * d, -1),
        k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return out


def swiglu(gate, up, down, m, mm):
    """(silu(m gate) * (m up)) down over blocks of tokens, each recomputed
    in the backward pass."""
    t = m.shape[0]
    edge = _blocks(t, TOKEN_BLOCK)
    one_block = lambda mb: mm(jax.nn.silu(mm(mb, gate)) * mm(mb, up), down)
    out = jax.lax.map(jax.checkpoint(one_block), m.reshape(t // edge, edge, -1))
    return out.reshape(t, -1)


def routed(p, m, kw, mm):
    """The held experts' part for tokens m [T, d]."""
    s = jax.nn.sigmoid(jnp.matmul(m, p["router"], precision=_HI))
    _, chosen = jax.lax.top_k(s + jax.lax.stop_gradient(p["expert_bias"]),
                              kw["experts_per_token"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    w = picked / (picked.sum(-1, keepdims=True) + 1e-20) * kw["route_scale"]

    def one_expert(index, gate, up, down):
        w_e = jnp.where(chosen == index, w, 0.0).sum(-1)
        return w_e[:, None] * swiglu(gate, up, down, m, mm)

    def step(acc, xs):
        return acc + jax.checkpoint(one_expert)(*xs), None

    index = kw["expert_offset"] + jnp.arange(p["gate"].shape[0])
    out, _ = jax.lax.scan(step, jnp.zeros_like(m),
                          (index, p["gate"], p["up"], p["down"]))
    return out


def layer(p, h, kind: str, kw: dict, mm):
    """One layer on one sequence h [T, d]."""
    eps = kw["rms_norm_eps"]
    window = kw["window"] if kind == "sliding_attention" else None
    o = attention(p["attn"], rms_norm(p["norm_in"], h, eps), window, kw, mm)
    h = h + rms_norm(p["norm_post_attn"], o, eps)
    m = rms_norm(p["norm_pre_mlp"], h, eps)
    kernels = lambda name: [p[name][n]["kernel"] for n in ("gate", "up", "down")]
    if "mlp" in p:
        y = swiglu(*kernels("mlp"), m, mm)
    else:
        y = swiglu(*kernels("shared"), m, mm) + routed(p["experts"], m, kw, mm)
    return h + rms_norm(p["norm_post_mlp"], y, eps)


def layers(p, h, kw: dict, mm):
    """Every ``layer<i>`` of ``p`` in order of i, each recomputed in the
    backward pass."""
    for i in sorted(int(name[5:]) for name in p if name.startswith("layer")):
        step = jax.checkpoint(lambda lp, y, kind=kw["layer_types"][i]:
                              layer(lp, y, kind, kw, mm))
        h = step(p[f"layer{i}"], h)
    return h


def head_loss(p, h, labels, eps, mm):
    """Mean cross-entropy of one sequence, over blocks of tokens."""
    t = h.shape[0]
    edge = _blocks(t, TOKEN_BLOCK)
    x = rms_norm(p["norm_f"], h, eps).reshape(t // edge, edge, -1)

    def one_block(xb, yb):
        logits = mm(xb, p["lm_head"])
        picked = jnp.take_along_axis(logits, yb[:, None], axis=-1)[:, 0]
        return (jax.nn.logsumexp(logits, axis=-1) - picked).sum()

    sums = jax.lax.map(lambda a: jax.checkpoint(one_block)(*a),
                       (x, labels.reshape(t // edge, edge)))
    return sums.sum() / t


def loss_fn(config: dict, precision: str):
    kw = {"rope_theta": 10000.0, "rms_norm_eps": 1e-5, **config["plan"]["kwargs"]}
    mm = common.matmul(precision)

    def one_sequence(c, s, tokens, labels):
        h = c["tok"]["embedding"][tokens] * kw["d_model"] ** 0.5
        h = layers(s, layers(c, h, kw, mm), kw, mm)
        return head_loss(s["head"], h, labels, kw["rms_norm_eps"], mm)

    def loss(client, server, tokens, labels):
        c, s = client["params"], server["params"]
        per_row = jax.lax.map(lambda a: one_sequence(c, s, *a), (tokens, labels))
        return per_row.mean()

    return loss
