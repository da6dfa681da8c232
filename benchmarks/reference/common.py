"""The plain reference: pre-LN transformer blocks, AdamW and the training
loop, in straightforward ``jax.numpy`` and float32.

It imports nothing of the program and is handed its weights by the harness
(``weights.py`` makes them from the seed).  ``precision`` is ``"f32"`` for
the reference proper (every matrix product at ``HIGHEST``), and ``"bf16"``
or ``"fp8"`` for the controls: there both operands of every product, in the
forward and in the backward pass, are rounded to that type (fp8 with a scale
per tensor, as fp8 training does) and the product is accumulated in float32.
Departures from the published models are those of the program, listed in
``configs/*.json``: LayerNorm epsilon 1e-6, separate q/k/v projections.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

LN_EPS = 1e-6
_HI = jax.lax.Precision.HIGHEST


def _rounder(precision: str):
    if precision == "bf16":
        return lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "fp8":
        def q(x):
            scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
            return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
        return q
    raise ValueError(f"unknown precision {precision!r}")


@functools.lru_cache(maxsize=None)
def matmul(precision: str):
    """``a @ b`` over the last axis of ``a`` and the first of a 2-D ``b``,
    or a batched product when both have the same rank."""
    def hi(a, b):
        return jnp.matmul(a, b, precision=_HI)

    if precision == "f32":
        return hi
    q = _rounder(precision)

    @jax.custom_vjp
    def mm(a, b):
        return hi(q(a), q(b))

    def fwd(a, b):
        return mm(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        qa, qb, qg = q(a), q(b), q(g)
        da = hi(qg, jnp.swapaxes(qb, -1, -2))
        if b.ndim == 2:
            db = hi(qa.reshape(-1, a.shape[-1]).T, qg.reshape(-1, g.shape[-1]))
        else:
            db = hi(jnp.swapaxes(qa, -1, -2), qg)
        return da, db

    mm.defvjp(fwd, bwd)
    return mm


def layer_norm(p, x):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def dense(p, x, mm):
    return mm(x, p["kernel"]) + p["bias"]


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x ** 3)))


def block(p, x, heads: int, causal: bool, mm):
    """x + MHA(LN(x)); then x + MLP(LN(x)).  x is [B, T, E]."""
    b, t, e = x.shape
    d = e // heads
    h = layer_norm(p["ln1"], x)
    split = lambda y: y.reshape(b, t, heads, d).transpose(0, 2, 1, 3)
    q, k, v = (split(dense(p["mha"][n], h, mm)) for n in ("q", "k", "v"))
    s = mm(q, k.transpose(0, 1, 3, 2)) * d ** -0.5
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = mm(jax.nn.softmax(s, axis=-1), v).transpose(0, 2, 1, 3).reshape(b, t, e)
    x = x + dense(p["mha"]["out"], o, mm)
    h = layer_norm(p["ln2"], x)
    return x + dense(p["down"], gelu_tanh(dense(p["up"], h, mm)), mm)


def blocks(p, x, heads: int, causal: bool, mm):
    """Every ``block<i>`` of ``p`` in order: a scan over the stacked blocks,
    each recomputed in the backward pass, so that one block's program is
    compiled once and one block's activations are held at a time."""
    count = sum(1 for k in p if k.startswith("block"))
    stacked = jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *(p[f"block{i}"] for i in range(count)))
    step = jax.checkpoint(lambda y, bp: (block(bp, y, heads, causal, mm), None))
    return jax.lax.scan(step, x, stacked)[0]


def cross_entropy(logits, labels):
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return (logz - picked).mean()


# -- AdamW as optax.adamw(lr) applies it: b1 0.9, b2 0.999, eps 1e-8 ------- #
B1, B2, EPS = 0.9, 0.999, 1e-8


@functools.partial(jax.jit, donate_argnums=(0, 1, 2), static_argnums=(5,))
def adamw_step(params, m, v, grads, count, lr: float):
    def leaf(p, m, v, g):
        m = B1 * m + (1 - B1) * g
        v = B2 * v + (1 - B2) * g * g
        mhat = m / (1 - B1 ** count)
        vhat = v / (1 - B2 ** count)
        return p - lr * mhat / (jnp.sqrt(vhat) + EPS), m, v
    out = jax.tree_util.tree_map(leaf, params, m, v, grads)
    pick = lambda i: jax.tree_util.tree_map(
        lambda t: t[i], out, is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2)


@jax.jit
def leaf_norms(tree):
    return jax.tree_util.tree_map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


@jax.jit
def leaf_delta_norms(tree, tree0):
    return jax.tree_util.tree_map(
        lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
            a.astype(jnp.float32) - b.astype(jnp.float32)))), tree, tree0)


def named(tree) -> dict:
    """Flatten a tree of scalars to ``{"a/b/c": float}``."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path):
            float(val) for path, val in flat}


def train(loss_fn, make_parties, steps: list, lr: float, row_block: int, watch=None):
    """Follow the parties through ``steps`` (per step, per client, ``(x, y)``).

    ``make_parties()`` gives ``(clients, server)``, the weights as the seed
    makes them; it is called again at the end, for the parameters' change.

    ``loss_fn(client_params, server_params, x, y)`` is the mean loss of the
    rows given.  One step is what a full coalesced group does: every client
    gets the gradient of its own rows' mean loss, the server that of the
    mean over all clients, and each party takes one AdamW step.  Rows go
    through in blocks of ``row_block`` so that a batch the program holds at
    once fits here in float32.  Returns the per-step per-client losses, the
    per-leaf norms of the first gradients and of the parameters' change.
    ``watch(count, parties, grads)``, where given, sees every step's
    parameters and gradients before the update, and the parameters once more
    after the last with no gradients (``calibrate.py``'s look).
    """
    grad = jax.jit(jax.value_and_grad(loss_fn, argnums=(0, 1)))
    add = jax.jit(lambda a, b, w: jax.tree_util.tree_map(
        lambda x, y: x + w * y, a, b), donate_argnums=(0,))
    zeros = lambda t: jax.tree_util.tree_map(jnp.zeros_like, t)

    def as_parties():
        clients, server = make_parties()
        f32 = jax.jit(lambda t: jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float32), t))
        return {**{f"client{i}": f32(p) for i, p in enumerate(clients)},
                "server": f32(server)}

    parties = as_parties()
    m = {k: zeros(p) for k, p in parties.items()}
    v = {k: zeros(p) for k, p in parties.items()}
    losses, grad_norms = [], None
    for count, batch in enumerate(steps, start=1):
        g_server, step_losses, g_clients = zeros(parties["server"]), [], []
        for i, (x, y) in enumerate(batch):
            g_client, loss, rows = zeros(parties[f"client{i}"]), 0.0, x.shape[0]
            for r in range(0, rows, row_block):
                w = min(row_block, rows - r) / rows
                part, (gc, gs) = grad(parties[f"client{i}"], parties["server"],
                                      x[r:r + row_block], y[r:r + row_block])
                loss += w * float(part)
                g_client = add(g_client, gc, w)
                g_server = add(g_server, gs, w / len(batch))
            step_losses.append(loss)
            g_clients.append(g_client)
        grads = {f"client{i}": g for i, g in enumerate(g_clients)}
        grads["server"] = g_server
        if grad_norms is None:
            grad_norms = {k: named(leaf_norms(g)) for k, g in grads.items()}
        if watch is not None:
            watch(count, parties, grads)
        for k in parties:
            parties[k], m[k], v[k] = adamw_step(
                parties[k], m[k], v[k], grads[k], float(count), lr)
        del grads, g_clients, g_server
        losses.append(step_losses)
    del m, v
    if watch is not None:
        watch(len(steps) + 1, parties, None)
    first = as_parties()
    delta = {k: named(leaf_delta_norms(parties[k], first[k])) for k in parties}
    return {"losses": losses, "grad_norms": grad_norms, "delta_norms": delta}
