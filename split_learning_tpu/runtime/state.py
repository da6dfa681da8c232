"""Functional training state.

The reference mutates module-global model/optimizer objects inside async
HTTP handlers (``src/server_part.py:14-15,47-52,83``) — a data race with >1
client (SURVEY.md §5). Here all training state is an explicit, immutable
pytree threaded through pure jitted step functions; concurrency becomes a
visible ordering decision instead of an accident.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Tuple

import jax
import optax

Params = Any


class TrainState(NamedTuple):
    params: Params
    opt_state: Any
    step: jax.Array  # int32 scalar


def sgd(lr, momentum: float = 0.0) -> optax.GradientTransformation:
    """The reference's optimizer: SGD(lr=0.01), no momentum
    (``src/client_part.py:17``, ``src/server_part.py:15``). ``lr`` may
    be a float or an optax schedule (make_lr)."""
    if momentum:
        return optax.sgd(lr, momentum=momentum)
    return optax.sgd(lr)


def make_lr(cfg) -> "float | optax.Schedule":
    """Learning-rate schedule from Config: constant by default; linear
    warmup over ``warmup_steps`` then constant; cosine decay to 0 by
    ``decay_steps`` (total, including warmup) when set. Schedules ride
    optax's internal step count, so every trainer (fused, split client,
    server, pipelined) gets them through its GradientTransformation
    with no step-threading changes."""
    if not (cfg.warmup_steps or cfg.decay_steps):
        return cfg.lr
    if cfg.decay_steps:
        return optax.warmup_cosine_decay_schedule(
            init_value=0.0, peak_value=cfg.lr,
            warmup_steps=cfg.warmup_steps,
            decay_steps=cfg.decay_steps, end_value=0.0)
    return optax.join_schedules(
        [optax.linear_schedule(0.0, cfg.lr, cfg.warmup_steps),
         optax.constant_schedule(cfg.lr)],
        [cfg.warmup_steps])


def make_tx(cfg) -> optax.GradientTransformation:
    """Optimizer factory from Config — the one construction site every
    trainer shares. ``sgd`` (+ optional L2 via weight_decay, momentum)
    preserves the reference's exact update; ``adam``/``adamw`` serve
    the transformer/causal-LM families, where decoupled weight decay
    and warmup-cosine are the standard recipe."""
    lr = make_lr(cfg)
    if cfg.optimizer == "sgd":
        tx = sgd(lr, cfg.momentum)
        if cfg.weight_decay:
            # coupled L2 for SGD: decay joins the gradient before the
            # lr scaling, the classical formulation
            tx = optax.chain(
                optax.add_decayed_weights(cfg.weight_decay), tx)
    elif cfg.optimizer == "adam":
        tx = optax.adam(lr)
    elif cfg.optimizer == "adamw":
        tx = optax.adamw(lr, weight_decay=cfg.weight_decay)
    else:
        raise ValueError(f"Unknown optimizer: {cfg.optimizer!r}")
    if cfg.grad_clip_norm:
        # clip the raw gradient before moments/decay see it. Scope note:
        # the norm is global over THIS transformation's param tree — the
        # whole model in the fused/pipeline single-program trainers, but
        # per party in the MPMD split runtimes (client and server each
        # own a make_tx over their stages; syncing norms across the wire
        # would add a round trip for a hyperparameter the reference
        # doesn't even have)
        tx = optax.chain(
            optax.clip_by_global_norm(cfg.grad_clip_norm), tx)
    return tx


def make_state(params: Params, tx: optax.GradientTransformation) -> TrainState:
    import jax.numpy as jnp
    return TrainState(params=params, opt_state=tx.init(params),
                      step=jnp.zeros((), jnp.int32))


def apply_grads(tx: optax.GradientTransformation, state: TrainState,
                grads: Params) -> TrainState:
    updates, opt_state = tx.update(grads, state.opt_state, state.params)
    params = optax.apply_updates(state.params, updates)
    return TrainState(params=params, opt_state=opt_state, step=state.step + 1)


def jit_apply_grads(tx: optax.GradientTransformation
                    ) -> Callable[[TrainState, Params], TrainState]:
    """``apply_grads`` as one XLA program a call, for the trainers whose
    gradients come out of a program of their own (the split clients,
    whose backward stays a program, and a span, of its own). Build it
    once per trainer; it traces once per state structure.

    The optimizer state and the gradients are donated: nothing else
    holds them, and the new parameters take the gradients' buffers. The
    parameters are a plain argument, because others may hold them:
    ``MultiClientSplitRunner.sync_bottoms`` hands one mean tree to every
    client (and keeps it as its compressed-sync reference), the
    pipelined client keeps them as an in-flight step's ``params_then``,
    and callers read ``client.state.params`` between steps."""
    def apply(params, opt_state, step, grads):
        return apply_grads(tx, TrainState(params, opt_state, step), grads)

    jitted = jax.jit(apply, donate_argnums=(1, 3))
    return lambda state, grads: jitted(
        state.params, state.opt_state, state.step, grads)


def compressed_sync_contribution(ef, tag, params, ref, density
                                 ) -> Tuple[Params, int, int]:
    """One party's contribution to a compressed param sync (PR 18):
    delta-from-reference through the topk8 wire codec.

    Raw params are a terrible topk8 input — most weights carry mass, so
    keeping the top 10% |x| zeroes ~90% of the model. What IS sparse is
    how far each party has drifted from the last agreed mean, so the
    wire carries ``topk8(params - ref)`` and the receiver reconstructs
    ``ref + delta'``. The EF ledger (keyed ``(tag, leaf_index)``,
    decay 1.0 — a param delta is an additive signal that must be fully
    repaid) carries the dropped drift into the next sync round, so
    repeated syncs converge on the true mean instead of systematically
    under-shooting. Returns ``(reconstruction, raw_bytes, wire_bytes)``
    — the byte pair feeds the sync_raw_bytes/sync_wire_bytes counters."""
    import numpy as np
    from split_learning_tpu.transport import codec
    leaves, treedef = jax.tree_util.tree_flatten(params)
    ref_leaves = jax.tree_util.tree_flatten(ref)[0]
    out, raw_b, wire_b = [], 0, 0
    for i, (p, r) in enumerate(zip(leaves, ref_leaves)):
        p_np = np.asarray(p, dtype=np.float32)
        r_np = np.asarray(r, dtype=np.float32)
        packed = ef.compress((tag, i), p_np - r_np, density, decay=1.0)
        rb, wb = codec.compressed_leaf_bytes(packed)
        raw_b += rb
        wire_b += wb
        out.append(r_np + codec.decompress_tree(packed))
    return jax.tree_util.tree_unflatten(treedef, out), raw_b, wire_b


def fedavg_mean(params_list, weights=None) -> Params:
    """FedAvg: leafwise mean over client param pytrees — the real
    aggregation the reference left as a TODO (src/server_part.py:81-82).
    ``weights`` (e.g. per-client example counts — the canonical FedAvg
    weighting) makes it a weighted mean; None = uniform. Shared by the
    server aggregator and client bottom-stage sync."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    if len(params_list) == 1:
        return params_list[0]
    if weights is None:
        return jax.tree_util.tree_map(
            lambda *xs: jnp.mean(jnp.stack([jnp.asarray(x) for x in xs]),
                                 axis=0),
            *params_list)
    if len(weights) != len(params_list):
        raise ValueError(f"{len(weights)} weights for "
                         f"{len(params_list)} param trees")
    w = np.asarray(weights, dtype=np.float64)
    if not (w > 0).all():
        raise ValueError(f"weights must be positive (got {weights})")
    w = w / w.sum()

    def wmean(*xs):
        # accumulate in at least f32 but never below the leaves' own
        # precision (x64 params stay x64, like the uniform path)
        acc = jnp.result_type(*[jnp.asarray(x).dtype for x in xs],
                              jnp.float32)
        return jnp.tensordot(
            jnp.asarray(w, acc),
            jnp.stack([jnp.asarray(x, acc) for x in xs]), axes=1)

    return jax.tree_util.tree_map(wmean, *params_list)
