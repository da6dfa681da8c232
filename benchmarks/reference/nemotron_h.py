"""The Nemotron-H layer stack (Nemotron-Labs-TwoTower-30B-A3B's tower) and
its loss, plain: float32 ``jax.numpy``, every matrix product through
``common.matmul(precision)``, nothing imported from the program.  The
weights come in the program's tree layout, made by ``weights.py``; the sizes
from the configuration's ``plan.kwargs`` (the published names' values;
``pattern`` a letter a published layer, the layers built named ``layer<i>``
by published index).  RMSNorm, the blocked attention and the head's loss are
``reference/afmoe.py``'s; the ungated expert and the recurrence are written
here.

RMSNorm(x) = x / sqrt(mean(x^2) + eps) * scale; no bias in any product; a
layer is one mixer: ``h += Mixer(norm(h))``, nothing after the branch.

- embedding: ``h = E[tokens]``, no scaling, no position table.
- ``M``, Mamba-2, ``u = norm(h)``, ``d_inner = heads x head_dim``: ``[z | xBC
  | dt] = u W_in``; ``xBC = silu(conv(xBC) + b_conv)`` per channel over
  ``conv_taps`` tokens (the last tap weighs the current token, zeros before
  the sequence's start); ``x [T, heads, head_dim]``, ``B``, ``C`` ``[T, groups,
  state]``, head n reads group n // (heads / groups); ``dt = softplus(dt +
  dt_bias)``; ``a_n = -exp(A_log_n)``; **the recurrence itself, token by
  token**: ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T`` (S ``[head_dim,
  state]`` a head, zero before the sequence), ``y_t = S_t C_t + D_n x_t``;
  ``y = RMSNorm_groups(y * silu(z))``, the gate before the norm, statistics
  over each of the ``groups`` parts of ``d_inner``, one scale of ``d_inner``;
  ``y W_out``.  The leaves ``dt_bias``, ``A_log``, ``D`` and ``conv_kernel``
  hold their distance from the published initialiser: from the quantiles, in
  head order, of ``dt`` log-uniform in ``[time_step_min, time_step_max]`` and
  of ``-a`` uniform in [1, 16] (:func:`starts`), from a skip of 1, and from
  taps uniform in ``+-conv_taps^-1/2`` (:func:`tap_starts`), as the
  configuration's ``departures`` says.
- ``*``, attention: ``q = u Wq`` as [T, H, D], ``k = u Wk``, ``v = u Wv`` as
  [T, H_kv, D]; no positions, no norm of q or k, no gate; query head n reads
  key/value head n // (H / H_kv); scores times D^-0.5, causal; softmax;
  ``(P V) Wo``.
- ``E``, ``m = norm(h)``: ``s = sigmoid(m Wr)`` over all the router's
  outputs, chosen = top-k of ``s + expert_bias``, ``w = s[chosen] / (sum +
  1e-20) * route_scale``; the sum over the chosen experts **held here** of
  ``w_e W_down,e relu(W_up,e m)^2``, plus the shared expert ``W_down,s
  relu(W_up,s m)^2``.
- head: ``norm_f``, the untied head over the vocabulary rows held, mean
  cross-entropy.

Departures from the equations, all to fit T 8192 in float32 beside the
training state, none changing a value: the recurrence runs in blocks of
``RECURRENCE_BLOCK`` tokens, each recomputed in the backward pass (every
token's state kept would be 17 GB a layer); attention runs one key/value
head at a time and inside that one block of queries at a time against all
keys, masked; the held experts are a scan in which every expert computes all
tokens and is weighted by ``w_e``; every MLP, the head and its loss go over
blocks of tokens; every such group, block and expert, and every layer, is
recomputed in the backward pass.  The router's product is float32 at every
``precision``; the controls round every other product's operands, and what
the recurrence contracts (``x``, ``B`` and ``C``, the operands of the chunked
form's four products in the program: :func:`rounded`); the convolution, the
recurrence's decays and states, the gate and the norms are no product and stay
float32.  ``expert_bias`` is a constant under ``stop_gradient``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from . import common
from .afmoe import TOKEN_BLOCK, _HI, _blocks, banded_attention, head_loss, rms_norm

RECURRENCE_BLOCK = 128


def starts(heads: int, dt_min: float, dt_max: float) -> tuple:
    """Where ``dt_bias`` and ``A_log`` start, a head: ``softplus^-1`` of the
    quantile (n + 1/2) / heads of a log-uniform step in ``[dt_min, dt_max]``,
    and the log of the same quantile of a uniform decay rate in [1, 16]."""
    q = (jnp.arange(heads, dtype=jnp.float32) + 0.5) / heads
    dt = jnp.exp(math.log(dt_min) + q * math.log(dt_max / dt_min))
    return dt + jnp.log(-jnp.expm1(-dt)), jnp.log(1.0 + 15.0 * q)


def tap_starts(taps: int, channels: int):
    """Where ``conv_kernel`` [taps, channels] starts: tap k of channel c at
    the quantile frac((c + 1) sqrt(p_k)) of a uniform in +-taps^-1/2, p_k the
    k-th prime (2, 3, 5, 7, ...); float64 until the last."""
    primes = [n for n in range(2, 4 * taps * taps + 4)
              if all(n % m for m in range(2, n))][:taps]
    c = np.arange(1, channels + 1, dtype=np.float64)
    at = np.stack([np.mod(c * math.sqrt(p), 1.0) for p in primes])
    return jnp.asarray((2.0 * at - 1.0) / math.sqrt(taps), jnp.float32)


def rounded(precision: str):
    """The controls' rounding of a tensor that is a product's operand in the
    program and none here: rounded going forward, its gradient coming back."""
    if precision == "f32":
        return lambda v: v
    q = common._rounder(precision)
    r = jax.custom_vjp(q)
    r.defvjp(lambda v: (q(v), None), lambda _, g: (q(g),))
    return r


def recurrence(x, dt, a, b, c, d_skip):
    """x [T, H, P], dt [T, H], a [H], b and c [T, G, N], d_skip [H] -> y [T,
    H, P]: one token a step, in blocks of tokens that the backward pass
    makes again."""
    t, h, p = x.shape
    g, n = b.shape[1:]
    by_group = lambda v: v.reshape(*v.shape[:-1], g, h // g)   # heads by group
    a, d_skip = by_group(a), by_group(d_skip)

    def token(state, now):          # state [G, H / G, P, N]
        x_t, dt_t, b_t, c_t = now   # [G, H / G, P], [G, H / G], [G, N] twice
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :])
        return state, (state * c_t[:, None, None, :]).sum(-1) + d_skip[..., None] * x_t

    edge = _blocks(t, RECURRENCE_BLOCK)
    fold = lambda v: v.reshape(t // edge, edge, *v.shape[1:])
    one_block = jax.checkpoint(lambda state, block: jax.lax.scan(token, state, block))
    _, y = jax.lax.scan(one_block, jnp.zeros((g, h // g, p, n), jnp.float32), (
        fold(x.reshape(t, g, h // g, p)), fold(by_group(dt)), fold(b), fold(c)))
    return y.reshape(x.shape)


def mamba2(p, u, kw, mm, operand):
    """The Mamba-2 mixer of one sequence, u [T, d] the normed input;
    ``operand`` is :func:`rounded` at the run's precision."""
    t = u.shape[0]
    h, hd = kw["mamba_heads"], kw["mamba_head_dim"]
    g, n = kw["ssm_groups"], kw["ssm_state"]
    inner = h * hd
    z, xbc, dt = jnp.split(mm(u, p["in_proj"]["kernel"]),
                           [inner, 2 * inner + 2 * g * n], axis=-1)
    taps = p["conv_kernel"] + tap_starts(*p["conv_kernel"].shape)
    past = jnp.pad(xbc, ((taps.shape[0] - 1, 0), (0, 0)))
    xbc = jax.nn.silu(p["conv_bias"] + sum(
        taps[k] * past[k:k + t] for k in range(taps.shape[0])))
    x, b, c = jnp.split(operand(xbc), [inner, inner + g * n], axis=-1)
    dt_start, a_start = starts(h, kw["time_step_min"], kw["time_step_max"])
    y = recurrence(x.reshape(t, h, hd),
                   jax.nn.softplus(dt + p["dt_bias"] + dt_start),
                   -jnp.exp(p["A_log"] + a_start),
                   b.reshape(t, g, n), c.reshape(t, g, n), 1.0 + p["D"])
    y = (y.reshape(t, inner) * jax.nn.silu(z)).reshape(t, g, inner // g)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + kw["norm_eps"])
    return mm(y.reshape(t, inner) * p["norm"]["scale"], p["out_proj"]["kernel"])


def attention(a, u, kw, mm):
    """Attention for one sequence, u [T, d] the normed input.  A scan over
    the key/value heads: each takes the columns of Wq and the rows of Wo of
    its own query heads; each group recomputed in the backward pass."""
    t = u.shape[0]
    heads, hk, d = kw["num_heads"], kw["num_kv_heads"], kw["head_dim"]
    g = heads // hk
    k = mm(u, a["k"]["kernel"]).reshape(t, hk, d)
    v = mm(u, a["v"]["kernel"]).reshape(t, hk, d)

    def one_group(wq, wo, kh, vh):
        o = banded_attention(mm(u, wq).reshape(t, g, d), kh, vh, None, mm)
        return mm(o.reshape(t, g * d), wo)

    def step(acc, xs):
        return acc + jax.checkpoint(one_group)(*xs), None

    wq = a["q"]["kernel"]
    out, _ = jax.lax.scan(step, jnp.zeros_like(u), (
        wq.reshape(wq.shape[0], hk, g * d).transpose(1, 0, 2),
        a["out"]["kernel"].reshape(hk, g * d, -1),
        k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return out


def relu2_mlp(up, down, m, mm):
    """relu(m up)^2 down over blocks of tokens, each recomputed in the
    backward pass."""
    t = m.shape[0]
    edge = _blocks(t, TOKEN_BLOCK)
    one_block = lambda mb: mm(jnp.square(jax.nn.relu(mm(mb, up))), down)
    out = jax.lax.map(jax.checkpoint(one_block), m.reshape(t // edge, edge, -1))
    return out.reshape(t, -1)


def routed(p, m, kw, mm):
    """The held experts' part for tokens m [T, d]."""
    s = jax.nn.sigmoid(jnp.matmul(m, p["router"], precision=_HI))
    _, chosen = jax.lax.top_k(s + jax.lax.stop_gradient(p["expert_bias"]),
                              kw["experts_per_token"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    w = picked / (picked.sum(-1, keepdims=True) + 1e-20) * kw["route_scale"]

    def one_expert(index, up, down):
        w_e = jnp.where(chosen == index, w, 0.0).sum(-1)
        return w_e[:, None] * relu2_mlp(up, down, m, mm)

    def step(acc, xs):
        return acc + jax.checkpoint(one_expert)(*xs), None

    index = kw["expert_offset"] + jnp.arange(p["up"].shape[0])
    out, _ = jax.lax.scan(step, jnp.zeros_like(m), (index, p["up"], p["down"]))
    return out


def layer(p, h, kw: dict, mm, operand):
    """One layer on one sequence h [T, d]; its kind is the mixer it holds."""
    u = rms_norm(p["norm"], h, kw["norm_eps"])
    if "mamba" in p:
        return h + mamba2(p["mamba"], u, kw, mm, operand)
    if "attn" in p:
        return h + attention(p["attn"], u, kw, mm)
    shared = relu2_mlp(p["shared"]["up"]["kernel"], p["shared"]["down"]["kernel"], u, mm)
    return h + shared + routed(p["experts"], u, kw, mm)


def layers(p, h, kw: dict, mm, operand):
    """Every ``layer<i>`` of ``p`` in order of i, each recomputed in the
    backward pass."""
    for i in sorted(int(name[5:]) for name in p if name.startswith("layer")):
        h = jax.checkpoint(lambda lp, y: layer(lp, y, kw, mm, operand))(p[f"layer{i}"], h)
    return h


def loss_fn(config: dict, precision: str):
    kw = {"norm_eps": 1e-5, "route_scale": 1.0, "expert_offset": 0,
          "time_step_min": 0.001, "time_step_max": 0.1, **config["plan"]["kwargs"]}
    mm, operand = common.matmul(precision), rounded(precision)

    def one_sequence(c, s, tokens, labels):
        h = layers(c, c["tok"]["embedding"][tokens], kw, mm, operand)
        h = layers(s, h, kw, mm, operand)
        return head_loss(s["head"], h, labels, kw["norm_eps"], mm)

    def loss(client, server, tokens, labels):
        c, s = client["params"], server["params"]
        per_row = jax.lax.map(lambda a: one_sequence(c, s, *a), (tokens, labels))
        return per_row.mean()

    return loss
