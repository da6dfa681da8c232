"""The SambaY layer stack (Phi-4-mini-flash) and its loss, plain: float32
``jax.numpy``, every matrix product through ``common.matmul(precision)``,
nothing imported from the program.  The weights come in the program's tree
layout, made by ``weights.py``; the sizes from the configuration's
``plan.kwargs`` (the published names' values; ``layers_kept`` are published
indices, and a layer's name, kind and ``lambda_init`` follow its index).

LN = LayerNorm with scale and bias, eps from the config.  Every layer:
``h = h + mixer(LN_1(h))``; ``h = h + (silu(g) * u) W_down`` with ``[g; u] =
LN_2(h) W_gu``.  Layer ``i`` of ``L`` published: even ``i`` a state-space
layer, odd ``i`` attention; ``i < L/2`` Mamba / window attention; ``L/2`` a
Mamba whose scan output ``m`` later layers read; ``L/2 + 1`` full attention
whose keys and values later layers read; above, even ``i`` a GMU, odd ``i``
a cross-attention.

- embedding: ``h = E[tokens]``, no scaling, no positions.
- Mamba: ``[x; z] = u W_in``; ``x'_t = silu(sum_k w_k x_{t-3+k} + b_c)``
  (causal, depthwise, 4 tokens); ``[r; B; C] = x' W_x``; ``D_t = softplus(r
  W_dt + b_dt)``; ``A = -exp(A_log)``; ``s_t = exp(D_t (x) A) s_{t-1} + (D_t
  x'_t) (x) B_t``, ``s_{-1} = 0``; ``y_t = s_t C_t + Dskip x'_t``; ``m = y``;
  ``out = (y * silu(z)) W_out``.
- GMU: ``out = (m * silu(u W_1)) W_2``.
- differential attention: ``[q; k; v] = u W_qkv + b`` (q [T, H, D], k and v
  [T, H_kv, D]); ``q1 = q[:, 0::2]``, ``q2 = q[:, 1::2]``, ``k1``, ``k2``
  likewise, ``v' = [v[:, 0::2]; v[:, 1::2]]`` side by side ([T, H_kv/2, 2D]);
  query pair n reads key/value pair ``n // (H / H_kv)``; ``a_i = softmax(
  mask(q_i k_i^T / sqrt(D))) v'``; ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2)
  + lambda_init``, ``lambda_init = 0.8 - 0.6 exp(-0.3 l)``, l the published
  index; ``o = RMSNorm_2D(a1 - lambda a2) * (1 - lambda_init)``; ``out = o
  W_o + b_o``.  Causal; on a window layer also ``0 <= i - j < window``.
- cross-attention: ``q = u W_q + b_q``; k, v the exporting layer's (after
  its bias), paired the same way; own lambdas, norm and ``W_o``.
- head: ``LN_f``, the untied head over the vocabulary rows held, mean
  cross-entropy.

Departures from the equations, all to fit T 8192 in float32 beside the
training state, none changing a value: the Mamba mixer runs one group of
channels at a time (the recurrence does not mix channels; ``r``, ``B``, ``C``
sum over all of them first and the groups' output projections are summed),
its recurrence a ``lax.scan`` over tokens inside a scan over chunks of
tokens; attention runs one key/value pair at a time (its query pairs'
columns of W_qkv or W_q and rows of W_o, the partial output projections
summed) and inside that one block of queries at a time against all keys,
masked; the GMU, every MLP, the head and its loss go over blocks of tokens;
every such group, chunk and block, and every layer, is recomputed in the
backward pass.  The controls round every product's operands; the
convolution and the recurrence are no products and stay float32.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import common

QUERY_BLOCK = 256
TOKEN_BLOCK = 1024
SCAN_CHUNK = 64
CHANNEL_GROUP = 1280


def layer_kind(i: int, published: int, mb_per_layer: int) -> str:
    half = published // 2
    ssm = i % mb_per_layer == 0
    if i >= half + 2:
        return "gmu" if ssm else "cross"
    if ssm:
        return "mamba"
    return "window" if i < half else "full"


def layer_norm(p, x, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _edge(n: int, size: int) -> int:
    """The block edge: ``size`` where it divides ``n``, else all of it."""
    return size if n % size == 0 else n


def over_token_blocks(fn, *rows):
    """``fn`` over blocks of tokens of ``rows`` ([T, ...] each), each block
    recomputed in the backward pass; ``fn`` returns [block, ...]."""
    t = rows[0].shape[0]
    edge = _edge(t, TOKEN_BLOCK)
    cut = lambda r: r.reshape((t // edge, edge) + r.shape[1:])
    out = jax.lax.map(lambda a: jax.checkpoint(fn)(*a), tuple(map(cut, rows)))
    return out.reshape((t,) + out.shape[2:])


def recurrence(x, delta, a, b, c, d_skip):
    """x, delta [T, d], a [d, n], b, c [T, n] -> y [T, d]: a scan over tokens
    inside a scan over chunks of tokens, each chunk recomputed in the
    backward pass."""
    t = x.shape[0]
    edge = _edge(t, SCAN_CHUNK)

    def token(s, row):
        x_t, d_t, b_t, c_t = row
        s = jnp.exp(d_t[:, None] * a) * s + (d_t * x_t)[:, None] * b_t[None, :]
        return s, (s * c_t[None, :]).sum(-1) + d_skip * x_t

    def chunk(s, rows):
        return jax.lax.scan(token, s, rows)

    cut = lambda r: r.reshape((t // edge, edge) + r.shape[1:])
    _, y = jax.lax.scan(jax.checkpoint(chunk), jnp.zeros_like(a),
                        tuple(map(cut, (x, delta, b, c))))
    return y.reshape(t, -1)


def mamba(p, u, kw, mm):
    """u [T, d_model] -> (out [T, d_model], y [T, d_inner])."""
    t = u.shape[0]
    inner = p["A_log"].shape[1]      # the leaf is [d_state, d_inner]
    n, rank, taps = kw["d_state"], kw["dt_rank"], p["conv_kernel"].shape[0]
    w_x, w_z = p["in_proj"]["kernel"][:, :inner], p["in_proj"]["kernel"][:, inner:]

    def conv(w_in, w_conv, b_conv):
        past = jnp.pad(mm(u, w_in), ((taps - 1, 0), (0, 0)))
        return jax.nn.silu(b_conv + sum(
            w_conv[k] * past[k:k + t] for k in range(taps)))

    proj = mm(jax.checkpoint(conv)(w_x, p["conv_kernel"], p["conv_bias"]),
              p["x_proj"])
    r, b, c = proj[:, :rank], proj[:, rank:rank + n], proj[:, rank + n:]
    group = _edge(inner, CHANNEL_GROUP)

    def one_group(w_in, w_conv, b_conv, w_dt, b_dt, a_log, d_skip, w_gate, w_out):
        x = conv(w_in, w_conv, b_conv)
        delta = jax.nn.softplus(mm(r, w_dt) + b_dt)
        y = recurrence(x, delta, -jnp.exp(a_log), b, c, d_skip)
        return mm(y * jax.nn.silu(mm(u, w_gate)), w_out), y

    def step(acc, xs):
        out, y = jax.checkpoint(one_group)(*xs)
        return acc + out, y

    cols = lambda w: w.reshape(w.shape[0], inner // group, group).transpose(1, 0, 2)
    rows = lambda w: w.reshape((inner // group, group) + w.shape[1:])
    out, y = jax.lax.scan(step, jnp.zeros_like(u), (
        cols(w_x), cols(p["conv_kernel"]), rows(p["conv_bias"]),
        cols(p["dt_proj"]), rows(p["dt_bias"]), rows(p["A_log"].T), rows(p["D"]),
        cols(w_z), rows(p["out_proj"]["kernel"])))
    return out, y.transpose(1, 0, 2).reshape(t, inner)


def gmu(p, u, memory, mm):
    one_block = lambda ub, mb: mm(
        mb * jax.nn.silu(mm(ub, p["in_proj"]["kernel"])), p["out_proj"]["kernel"])
    return over_token_blocks(one_block, u, memory)


def softmax_map(q, k, v, window, mm):
    """q [T, G, D], k [T, D], v [T, E] -> [T, G, E]: causal, banded if
    ``window``; one block of queries at a time against every key, masked,
    each block recomputed in the backward pass."""
    t, g, d = q.shape
    edge = _edge(t, QUERY_BLOCK)
    cols = jnp.arange(t)[None, :]

    def one_block(qb, q0):         # qb [G, edge, D]
        s = mm(qb, jnp.broadcast_to(k.T, (g, d, t))) * d ** -0.5
        behind = (q0 + jnp.arange(edge))[:, None] - cols
        ok = behind >= 0
        if window is not None:
            ok &= behind < window
        p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
        return mm(p, jnp.broadcast_to(v, (g,) + v.shape))

    qb = q.reshape(t // edge, edge, g, d).transpose(0, 2, 1, 3)
    starts = jnp.arange(t // edge) * edge
    o = jax.lax.map(lambda a: jax.checkpoint(one_block)(*a), (qb, starts))
    return o.transpose(0, 2, 1, 3).reshape(t, g, -1)


def rms_norm(p, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * p["scale"]


def diff_attention(p, u, kv, kind: str, index: int, kw, mm):
    """u [T, d_model] -> (out, (k, v)) with k, v [T, H_kv, D] after their
    bias: made here, or (``kind`` cross) the ones handed in."""
    t, eps = u.shape[0], kw["eps"]
    heads, hk, d = kw["num_heads"], kw["num_kv_heads"], kw["head_dim"]
    g = heads // hk                      # query pairs a key/value pair
    if kind == "cross":
        wq, bq = p["q"]["kernel"], p["q"]["bias"]
        k, v = kv
    else:
        w = p["qkv"]["kernel"]      # its bias comes as three leaves
        wq, bq = w[:, :heads * d], p["q_bias"]
        k, v = ((mm(u, w[:, lo:lo + hk * d]) + p[name]).reshape(t, hk, d)
                for lo, name in ((heads * d, "k_bias"), ((heads + hk) * d, "v_bias")))
    start = 0.8 - 0.6 * math.exp(-0.3 * index)
    lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
           - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + start)
    window = kw["window"] if kind == "window" else None

    def one_pair(wq1, bq1, wq2, bq2, wo, k1, k2, vv):
        """Key/value pair: its ``g`` query pairs' two maps, their
        difference normed, through their rows of W_o."""
        a1 = softmax_map((mm(u, wq1) + bq1).reshape(t, g, d), k1, vv, window, mm)
        a2 = softmax_map((mm(u, wq2) + bq2).reshape(t, g, d), k2, vv, window, mm)
        o = rms_norm(p["subln"], a1 - lam * a2, eps) * (1.0 - start)
        return mm(o.reshape(t, g * 2 * d), wo)

    def step(acc, xs):
        return acc + jax.checkpoint(one_pair)(*xs), None

    # query head 2n is q1 of pair n, head 2n + 1 its q2; pair n reads
    # key/value pair n // g, whose k1, k2 are heads 2p, 2p + 1
    wq = wq.reshape(-1, hk // 2, g, 2, d)
    bq = bq.reshape(hk // 2, g, 2, d)
    half = lambda x, i: x.reshape(t, hk // 2, 2, d)[:, :, i].transpose(1, 0, 2)
    out, _ = jax.lax.scan(step, jnp.zeros_like(u), (
        wq[:, :, :, 0].transpose(1, 0, 2, 3).reshape(hk // 2, -1, g * d),
        bq[:, :, 0].reshape(hk // 2, g * d),
        wq[:, :, :, 1].transpose(1, 0, 2, 3).reshape(hk // 2, -1, g * d),
        bq[:, :, 1].reshape(hk // 2, g * d),
        p["out"]["kernel"].reshape(hk // 2, g * 2 * d, -1),
        half(k, 0), half(k, 1), v.reshape(t, hk // 2, 2 * d).transpose(1, 0, 2)))
    return out + p["out"]["bias"], (k, v)


def layer(p, h, memory, kv, kind: str, index: int, kw, mm):
    """One layer on one sequence h [T, d]; returns (h, what it shares)."""
    u = layer_norm(p["ln1"], h, kw["eps"])
    if kind == "mamba":
        out, shared = mamba(p["mamba"], u, kw, mm)
    elif kind == "gmu":
        out, shared = gmu(p["gmu"], u, memory, mm), None
    else:
        out, shared = diff_attention(p["attn"], u, kv, kind, index, kw, mm)
    h = h + out
    w_gu, w_down = p["mlp"]["gate_up"]["kernel"], p["mlp"]["down"]["kernel"]
    width = w_down.shape[0]

    def mlp(xb):
        gu = mm(xb, w_gu)
        return mm(jax.nn.silu(gu[:, :width]) * gu[:, width:], w_down)

    return h + over_token_blocks(mlp, layer_norm(p["mlp"]["ln"], h, kw["eps"])), shared


def layers(p, h, kw: dict, mm):
    """Every ``layer<i>`` of ``p`` in order of i, each recomputed in the
    backward pass; the memory and the key/value set go from the layers that
    make them to the layers after."""
    published, per = kw["layers_published"], kw["mb_per_layer"]
    memory = kv = None
    for i in sorted(int(name[5:]) for name in p if name.startswith("layer")):
        kind = layer_kind(i, published, per)
        step = jax.checkpoint(lambda lp, y, m, c, kind=kind, i=i:
                              layer(lp, y, m, c, kind, i, kw, mm))
        h, shared = step(p[f"layer{i}"], h, memory, kv)
        if i == published // 2:
            memory = shared
        if i == published // 2 + 1:
            kv = shared
    return h


def head_loss(p, h, labels, eps, mm):
    """Mean cross-entropy of one sequence, over blocks of tokens."""
    def one_block(xb, yb):
        logits = mm(xb, p["lm_head"])
        picked = jnp.take_along_axis(logits, yb[:, None], axis=-1)[:, 0]
        return jax.nn.logsumexp(logits, axis=-1) - picked

    return over_token_blocks(one_block, layer_norm(p["norm_f"], h, eps),
                             labels).mean()


def loss_fn(config: dict, precision: str):
    kw = {"eps": 1e-5, "mb_per_layer": 2, "layers_published": 32,
          **config["plan"]["kwargs"]}
    mm = common.matmul(precision)

    def one_sequence(c, s, tokens, labels):
        h = c["tok"]["embedding"][tokens]
        # one tensor crosses the cut: neither stage reads what the other made
        h = layers(s, layers(c, h, kw, mm), kw, mm)
        return head_loss(s["head"], h, labels, kw["eps"], mm)

    def loss(client, server, tokens, labels):
        c, s = client["params"], server["params"]
        per_row = jax.lax.map(lambda a: one_sequence(c, s, *a), (tokens, labels))
        return per_row.mean()

    return loss
