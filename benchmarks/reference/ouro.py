"""The looped language model (Ouro, arXiv:2510.25741) and its training
objective, plain: float32 ``jax.numpy``, every matrix product through
``common.matmul(precision)``, nothing imported from the program.  The weights
come in the program's tree layout, made by ``weights.py``; the sizes from the
configuration's ``plan.kwargs`` (the published names' values).  RMSNorm, the
rotate-half rotary, the blocked attention and the SwiGLU are
``reference/afmoe.py``'s; the layer, the loop, the gate and the objective are
this file's own.

RMSNorm(x) = x / sqrt(mean(x^2) + eps) * scale; no biases but the gate's.

- embedding: ``h = E[tokens]``, no scaling, no position table.
- a layer (sandwich norms): ``a = Attn(input_layernorm(h))``, ``h = h +
  input_layernorm_2(a)``, ``m = MLP(post_attention_layernorm(h))``, ``h = h +
  post_attention_layernorm_2(m)``.
- Attn, ``u`` the normed input: ``q = u Wq``, ``k = u Wk``, ``v = u Wv`` as
  [T, H, D] (as many key/value heads as the configuration gives); rotary
  positions (theta from the config, rotate-half over all of D, position =
  index) on q and k; scores times D^-0.5, causal; softmax; ``(P V) Wo``.
- MLP: ``(silu(x W_gate) * (x W_up)) W_down``.
- the model: for pass s of ``passes``: ``h = layers(h)`` (every ``layer<i>``
  in order, the same weights every pass), ``h = norm_f(h)``, ``e_s = h``: the
  final norm closes every pass, and what it gives is the pass's exit and the
  next pass's input; ``lambda_s = sigmoid(e_s . w_g + b_g)``; ``l_s =
  CE(e_s W_head, y)`` a token.
- the objective, a token: ``q_1 = lambda_1``, ``q_s = lambda_s prod_{j<s} (1 -
  lambda_j)``, the last pass takes what is left (``q_S = prod_{j<S} (1 -
  lambda_j)``); ``sum_s q_s l_s - beta H(q)``, ``H(q) = -sum_s q_s log q_s``;
  the loss is its mean over the tokens.  ``log q_s`` is summed from the
  logarithms of ``lambda`` and ``1 - lambda`` and ``q_s`` is its exponential.

Departures from the equations, all to fit T 8192 in float32 beside the
training state, none changing a value: attention runs one key/value head at a
time (its query heads' columns of Wq and rows of Wo, the partial output
projections summed) and inside that one block of queries at a time against
all keys, masked; every SwiGLU and every exit's head and cross-entropy go
over blocks of tokens; every such group and block, and every application of
a layer, is recomputed in the backward pass.  The gate's product (width 1)
is float32 at every ``precision``, as the program states it; the controls
round every other product's operands.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common
from .afmoe import TOKEN_BLOCK, _blocks, banded_attention, rms_norm, rope, swiglu

_HI = jax.lax.Precision.HIGHEST


def attention(a, u, kw, mm):
    """The attention operator for one sequence, u [T, d] the normed input.
    A scan over the key/value heads: each takes the columns of Wq and the
    rows of Wo of its own query heads; each group recomputed in the
    backward pass."""
    t, theta = u.shape[0], kw["rope_theta"]
    heads, hk, d = kw["num_heads"], kw["num_kv_heads"], kw["head_dim"]
    g = heads // hk
    k = rope(mm(u, a["k"]["kernel"]).reshape(t, hk, d), theta)
    v = mm(u, a["v"]["kernel"]).reshape(t, hk, d)

    def one_group(wq, wo, kh, vh):
        q = rope(mm(u, wq).reshape(t, g, d), theta)
        return mm(banded_attention(q, kh, vh, None, mm).reshape(t, g * d), wo)

    def step(acc, xs):
        return acc + jax.checkpoint(one_group)(*xs), None

    wq = a["q"]["kernel"]
    out, _ = jax.lax.scan(step, jnp.zeros_like(u), (
        wq.reshape(wq.shape[0], hk, g * d).transpose(1, 0, 2),
        a["out"]["kernel"].reshape(hk, g * d, -1),
        k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return out


def layer(p, h, kw: dict, mm):
    """One application of one layer on one sequence h [T, d]."""
    eps = kw["rms_norm_eps"]
    a = attention(p["self_attn"], rms_norm(p["input_layernorm"], h, eps), kw, mm)
    h = h + rms_norm(p["input_layernorm_2"], a, eps)
    m = swiglu(*(p["mlp"][n]["kernel"] for n in ("gate", "up", "down")),
               rms_norm(p["post_attention_layernorm"], h, eps), mm)
    return h + rms_norm(p["post_attention_layernorm_2"], m, eps)


def exits(p, h, kw: dict, mm):
    """``e_s`` for every pass, stacked [S, T, d]: the same layers again, then
    the final norm.  A scan over the passes (they are one computation on
    another input), every application of a layer recomputed in the backward
    pass."""
    names = sorted((n for n in p if n.startswith("layer")), key=lambda n: int(n[5:]))

    def one_pass(h, _):
        for name in names:
            h = jax.checkpoint(lambda lp, y: layer(lp, y, kw, mm))(p[name], h)
        h = rms_norm(p["norm_f"], h, kw["rms_norm_eps"])
        return h, h

    return jax.lax.scan(one_pass, h, None, length=kw["passes"])[1]


def token_losses(head, e, labels, mm):
    """The cross-entropy of every token of one sequence through the head,
    over blocks of tokens, each recomputed in the backward pass."""
    t = e.shape[0]
    edge = _blocks(t, TOKEN_BLOCK)

    def one_block(xb, yb):
        logits = mm(xb, head)
        picked = jnp.take_along_axis(logits, yb[:, None], axis=-1)[:, 0]
        return jax.nn.logsumexp(logits, axis=-1) - picked

    out = jax.lax.map(lambda a: jax.checkpoint(one_block)(*a),
                      (e.reshape(t // edge, edge, -1), labels.reshape(t // edge, edge)))
    return out.reshape(t)


def log_exit_distribution(z) -> list:
    """``log q_s`` from the passes' gate logits, ``z[s]`` of [T] each: ``log
    lambda = log_sigmoid(z)``, ``log(1 - lambda) = log_sigmoid(-z)``.  In
    logarithms because a gate that saturates in float32 makes a later ``q_s``
    exactly 0, where ``q_s log q_s`` written over ``q_s`` is not a number."""
    log_q, left = [], jnp.zeros_like(z[0])
    for gate in z[:-1]:
        log_q.append(jax.nn.log_sigmoid(gate) + left)
        left = left + jax.nn.log_sigmoid(-gate)
    return log_q + [left]


def objective(p, found, labels, kw: dict, mm):
    """The mean over one sequence's tokens of ``sum_s q_s l_s - beta H(q)``;
    ``found`` the exits [S, T, d]."""
    gate = p["early_exit_gate"]
    z = jnp.matmul(found, gate["kernel"], precision=_HI)[..., 0] + gate["bias"][0]
    log_q = log_exit_distribution(list(z))
    losses = jax.lax.map(lambda e: token_losses(p["lm_head"], e, labels, mm), found)
    expected = sum(jnp.exp(lq) * ls for lq, ls in zip(log_q, losses))
    entropy = -sum(jnp.exp(lq) * lq for lq in log_q)
    return (expected - kw["beta"] * entropy).mean()


def loss_fn(config: dict, precision: str):
    kw = {"rope_theta": 1e6, "rms_norm_eps": 1e-6, "beta": 0.1, "passes": 4,
          **config["plan"]["kwargs"]}
    mm = common.matmul(precision)

    def one_sequence(c, s, tokens, labels):
        found = exits(s, c["tok"]["embedding"][tokens], kw, mm)
        return objective(s, found, labels, kw, mm)

    def loss(client, server, tokens, labels):
        c, s = client["params"], server["params"]
        per_row = jax.lax.map(lambda a: one_sequence(c, s, *a), (tokens, labels))
        return per_row.mean()

    return loss
