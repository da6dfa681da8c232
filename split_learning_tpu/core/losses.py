"""Loss functions.

The reference uses ``nn.CrossEntropyLoss`` on logits (``src/server_part.py:16,49``
server-side in split mode; ``src/client_part.py:18,158`` client-side in
federated mode). Mean reduction over the batch, integer class labels.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import optax

from split_learning_tpu.core.stage import with_counters


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean softmax cross-entropy with integer labels (torch CE semantics)."""
    return optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()


def per_example_cross_entropy(logits: jax.Array,
                              labels: jax.Array) -> jax.Array:
    """Unreduced ``[batch]`` CE — the coalesced server step needs the
    per-example vector so one batched dispatch can hand each client its
    own segment-mean loss (runtime/server.py _dispatch_group)."""
    return optax.softmax_cross_entropy_with_integer_labels(logits, labels)


def final_loss(stage, params, x: jax.Array, labels: jax.Array,
               loss_op=cross_entropy, logits=None) -> jax.Array:
    """The loss of a plan's final ``stage`` on its input ``x``: the one
    function every final-stage loss goes through. ``loss_op`` on the
    stage's logits, as it always was; or, where the stage carries its
    own objective (``core/stage.Stage.objective``: its loss reads the
    labels as an input), that objective's losses, returned as they are
    for :func:`per_example_cross_entropy` and as their mean for any
    other ``loss_op``. ``logits``: the stage's output where the caller
    has made it already (an evaluation that also counts hits)."""
    if stage.objective is None:
        if logits is None:
            logits = stage.apply(params, x)
        return loss_op(logits, labels)
    losses = stage.objective(params, x, labels)
    return losses if loss_op is per_example_cross_entropy else losses.mean()


def plan_loss(plan, params, x: jax.Array, labels: jax.Array,
              loss_op=cross_entropy) -> jax.Array:
    """:func:`final_loss` of the whole ``plan``: every stage but the last
    applied to ``x``, then the last stage's loss."""
    last = plan.num_stages - 1
    return final_loss(plan.stages[last], params[last],
                      plan.apply_range(params, x, 0, last), labels, loss_op)


def plan_loss_with_counters(plan, params, x: jax.Array, labels: jax.Array,
                            loss_op=cross_entropy) -> tuple:
    """(:func:`plan_loss`, the counters the plan's stages sowed on the
    way): the same loss by the same code, every stage's ``apply`` and
    ``objective`` called through ``core/stage.with_counters``. For a
    plan that sows nothing the counters are ``{}`` and the trace is
    :func:`plan_loss`'s."""
    counters: dict = {}

    def counting(stage):
        def through(fn):
            def call(p, *args):
                out, sown = with_counters(stage, fn, p, *args)
                counters.update(sown)
                return out
            return fn and call
        return dataclasses.replace(stage, apply=through(stage.apply),
                                   objective=through(stage.objective))

    counted = dataclasses.replace(
        plan, stages=tuple(counting(s) for s in plan.stages))
    return plan_loss(counted, params, x, labels, loss_op), counters


def refuse_objective(plan, where: str) -> None:
    """Raise for a plan whose final stage carries its own objective, on
    a path that computes its loss from logits alone: such a path would
    train on the first loss and drop the rest without a word."""
    stage = plan.stages[-1]
    if stage.objective is not None:
        raise ValueError(
            f"stage {stage.name!r} carries its own objective (its loss "
            f"reads the labels as an input), which {where} cannot run: it "
            "takes a loss from logits alone")


def accuracy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    return jnp.mean((jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32))
