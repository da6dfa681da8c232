"""The latent-attention MoE stack with its multi-token-prediction module
(JoyAI-LLM-Flash: DeepSeek-V3's layer) and its loss, plain: float32
``jax.numpy``, every matrix product through ``common.matmul(precision)``,
nothing imported from the program.  The weights come in the program's tree
layout, made by ``weights.py``; the sizes from the configuration's
``plan.kwargs`` (the published names' values for the layers kept).  The
routed layer, the SwiGLU and RMSNorm are ``reference/afmoe.py``'s: the two
models share that layer to the letter.

RMSNorm(x) = x / sqrt(mean(x^2) + eps) * scale; no biases anywhere;
pre-norm residuals: ``h += Attn(norm_attn(h))``, ``h += FFN(norm_mlp(h))``.

- embedding: ``h = E[tokens]``, no scaling, no position table.
- latent attention, ``u = norm_attn(h)``: ``c_q = norm(u W_qa)``; ``q = c_q
  W_qb``, a head is ``[q_n | q_r]``; ``u W_kva = [c_kv | k_r]``; ``c_kv =
  norm(c_kv)``; ``c_kv W_kvb``, a head is ``[k_n | v]``; rotary on
  **interleaved pairs** (lanes 2i and 2i+1 turn by ``pos * theta^(-2i /
  d_r)``) on every head's ``q_r`` and on the one ``k_r`` all heads share; ``k
  = [k_n | k_r]``; scores ``q . k * (d_n + d_r)^-0.5``, causal; softmax; ``o
  = P v``; ``concat(o) W_o``.
- FFN: the first ``dense_layers`` layers ``SwiGLU(m)`` of ``dense_width``;
  the others ``shared(m) + routed(m)`` with ``m = norm_mlp(h)``: ``s =
  sigmoid(m W_r)`` over all the router's outputs, chosen = top-k of ``s +
  expert_bias``, ``w = s[chosen] / sum * route_scale``, routed = sum over
  the chosen experts **held here** of ``w_e SwiGLU_e(m)``.
- head: ``g = norm_f(h)``, ``logits = g W_head`` over the vocabulary rows
  held.
- the module (``mtp_layers`` 1): ``u_i = [norm_e(E'[t_{i+1}]) ; norm_h(g_i)]
  W_eh`` with ``t_{i+1}`` the label at i; ``u' = Layer(u)``, an expert layer
  of its own weights at positions i; ``logits'_i = norm_s(u'_i) W_head``, the
  same head.
- loss: ``mean_i CE(logits_i, t_{i+1}) + mtp_lambda * mean_{i < T-1}
  CE(logits'_i, t_{i+2})``.

Departures from the equations, all to fit T 8192 in float32 beside the
training state, none changing a value: attention runs a group of heads at a
time (their columns of W_qb and W_kvb and rows of W_o, the partial output
projections summed) and inside that one block of queries at a time against
all keys, masked; the held experts are a scan in which every expert computes
all tokens and is weighted by ``w_e``; every SwiGLU, the head and its losses
go over blocks of tokens; every such group, block and expert, and every
layer, is recomputed in the backward pass.  The router's product is float32
at every ``precision``; the controls round every other product's operands.
``expert_bias`` is a constant under ``stop_gradient``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common
from .afmoe import QUERY_BLOCK, TOKEN_BLOCK, _blocks, rms_norm, routed, swiglu

HEAD_GROUP = 4


def rope_pairs(x, theta):
    """x [T, ..., d]: lanes 2i and 2i+1 turn by ``pos * theta^(-2i / d)``,
    position = index along axis 0."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,))
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                      a * jnp.sin(ang) + b * jnp.cos(ang)], -1).reshape(x.shape)


def causal_attention(q, k, v, mm):
    """q, k [G, T, D], v [G, T, Dv] -> [G, T, Dv]; causal.  One block of
    queries at a time against every key, masked; each block recomputed in
    the backward pass."""
    g, t, d = q.shape
    edge = _blocks(t, QUERY_BLOCK)
    cols = jnp.arange(t)[None, :]
    kt = k.transpose(0, 2, 1)

    def one_block(qb, q0):         # qb [G, edge, D]
        s = mm(qb, kt) * d ** -0.5
        ok = (q0 + jnp.arange(edge))[:, None] >= cols
        return mm(jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1), v)

    qb = q.reshape(g, t // edge, edge, d).transpose(1, 0, 2, 3)
    starts = jnp.arange(t // edge) * edge
    o = jax.lax.map(lambda a: jax.checkpoint(one_block)(*a), (qb, starts))
    return o.transpose(1, 0, 2, 3).reshape(g, t, -1)


def attention(a, u, kw, mm):
    """The attention part of a layer for one sequence, u [T, d] the normed
    input.  A scan over groups of heads: each takes its own columns of W_qb
    and W_kvb and rows of W_o; each group recomputed in the backward pass."""
    t, eps, theta = u.shape[0], kw["rms_norm_eps"], kw["rope_theta"]
    heads, d_n, d_v = kw["num_heads"], kw["qk_nope_head_dim"], kw["v_head_dim"]
    rank = kw["kv_lora_rank"]
    group = HEAD_GROUP if heads % HEAD_GROUP == 0 else heads
    c_q = rms_norm(a["q_a_norm"], mm(u, a["q_a"]["kernel"]), eps)
    kv_a = mm(u, a["kv_a"]["kernel"])
    c_kv = rms_norm(a["kv_a_norm"], kv_a[:, :rank], eps)
    k_r = rope_pairs(kv_a[:, rank:], theta)                       # [T, d_r]

    def by_group(w):               # [in, heads * x] -> [groups, in, group * x]
        return w.reshape(w.shape[0], heads // group, -1).transpose(1, 0, 2)

    def one_group(wq, wkv, wo):
        q = mm(c_q, wq).reshape(t, group, -1)
        kv = mm(c_kv, wkv).reshape(t, group, d_n + d_v)
        q = jnp.concatenate([q[..., :d_n], rope_pairs(q[..., d_n:], theta)], -1)
        k = jnp.concatenate([kv[..., :d_n], jnp.broadcast_to(
            k_r[:, None, :], (t, group, k_r.shape[-1]))], -1)
        o = causal_attention(q.transpose(1, 0, 2), k.transpose(1, 0, 2),
                             kv[..., d_n:].transpose(1, 0, 2), mm)
        return mm(o.transpose(1, 0, 2).reshape(t, group * d_v), wo)

    def step(acc, xs):
        return acc + jax.checkpoint(one_group)(*xs), None

    out, _ = jax.lax.scan(step, jnp.zeros_like(u), (
        by_group(a["q_b"]), by_group(a["kv_b"]),
        a["out"]["kernel"].reshape(heads // group, group * d_v, -1)))
    return out


def layer(p, h, kw: dict, mm):
    """One layer on one sequence h [T, d]."""
    eps = kw["rms_norm_eps"]
    h = h + attention(p["attn"], rms_norm(p["norm_attn"], h, eps), kw, mm)
    m = rms_norm(p["norm_mlp"], h, eps)
    kernels = lambda name: [p[name][n]["kernel"] for n in ("gate", "up", "down")]
    if "mlp" in p:
        return h + swiglu(*kernels("mlp"), m, mm)
    return h + swiglu(*kernels("shared"), m, mm) + routed(p["experts"], m, kw, mm)


def layers(p, h, kw: dict, mm):
    """Every ``layer<i>`` of ``p`` in order of i, each recomputed in the
    backward pass."""
    for i in sorted(int(name[5:]) for name in p if name.startswith("layer")):
        h = jax.checkpoint(lambda lp, y: layer(lp, y, kw, mm))(p[f"layer{i}"], h)
    return h


def ce_sum(lm_head, x, labels, weights, mm):
    """Sum over tokens of ``weights * CE(x W_head, labels)``, over blocks of
    tokens, each recomputed in the backward pass."""
    t = x.shape[0]
    edge = _blocks(t, TOKEN_BLOCK)

    def one_block(xb, yb, wb):
        logits = mm(xb, lm_head)
        picked = jnp.take_along_axis(logits, yb[:, None], axis=-1)[:, 0]
        return ((jax.nn.logsumexp(logits, axis=-1) - picked) * wb).sum()

    cut = lambda a: a.reshape((t // edge, edge) + a.shape[1:])
    return jax.lax.map(lambda a: jax.checkpoint(one_block)(*a),
                       (cut(x), cut(labels), cut(weights))).sum()


def head_losses(p, h, labels, kw: dict, mm):
    """The objective of one sequence: h [T, d] the last layer's output."""
    eps, t = kw["rms_norm_eps"], h.shape[0]
    g = rms_norm(p["norm_f"], h, eps)
    loss = ce_sum(p["lm_head"], g, labels, jnp.ones(t), mm) / t
    if not kw.get("mtp_layers", 1):
        return loss
    m = p["mtp"]
    joined = jnp.concatenate([rms_norm(m["norm_e"], m["tok"]["embedding"][labels], eps),
                              rms_norm(m["norm_h"], g, eps)], -1)
    u = jax.checkpoint(lambda lp, y: layer(lp, y, kw, mm))(
        m["block"], mm(joined, m["eh"]["kernel"]))
    # position i predicts the label one further on; the last has none
    second = ce_sum(p["lm_head"], rms_norm(m["norm_s"], u, eps),
                    jnp.roll(labels, -1), (jnp.arange(t) < t - 1) * 1.0, mm)
    return loss + kw["mtp_lambda"] * second / (t - 1)


def loss_fn(config: dict, precision: str):
    kw = {"rope_theta": 32e6, "rms_norm_eps": 1e-6, "mtp_lambda": 0.3,
          **config["plan"]["kwargs"]}
    mm = common.matmul(precision)

    def one_sequence(c, s, tokens, labels):
        h = layers(s, layers(c, c["tok"]["embedding"][tokens], kw, mm), kw, mm)
        return head_losses(s, h, labels, kw, mm)

    def loss(client, server, tokens, labels):
        c, s = client["params"], server["params"]
        per_row = jax.lax.map(lambda a: one_sequence(c, s, *a), (tokens, labels))
        return per_row.mean()

    return loss
