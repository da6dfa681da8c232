"""Stage abstraction — the core of the split-model design.

The reference hard-codes exactly two halves (`ModelPartA` / `ModelPartB`,
``src/model_def.py:5-28``) plus a hand-fused `FullModel`
(``src/model_def.py:31-46``) whose layers must be kept manually in sync.

Here a model *is* an ordered sequence of pure stages; "full model" is the
composition of the stages, so split-vs-monolithic equivalence is
by-construction (and tested, see tests/test_equivalence.py). Stages are
pure functions of (params, x) — no module-global mutable state (the
reference's server mutates a module-global model inside async handlers,
``src/server_part.py:14-15,47-52``, a data race with >1 client; purity
removes that class of bug, SURVEY.md §5 "Race detection").
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from flax import traverse_util

from split_learning_tpu.obs import spans

Params = Any  # a pytree of arrays
Array = jax.Array


@dataclasses.dataclass(frozen=True)
class Stage:
    """One pure, differentiable segment of a split model.

    ``apply(params, x) -> y`` must be jit-traceable (static shapes, no
    Python side effects) so that a stage can live inside a pjit'd pipeline
    or be jitted standalone on the client/server.

    ``objective(params, x, labels) -> losses`` is a final stage's own
    training objective, one loss a token (or a row), for a stage whose
    loss reads the labels as an input and not only as targets (a module
    that embeds the next token to predict the one after it,
    models/joyai_llm_flash.py). ``None``, as every other stage has it:
    the loss is an operator on ``apply``'s logits. Every final-stage
    loss goes through ``core/losses.final_loss``, which takes the one or
    the other; a path that cannot carry an objective refuses the stage
    (``core/losses.refuse_objective``).

    ``sows``: ``apply`` and ``objective`` take ``mutable=[collection]``
    and then return ``(result, {collection: what the modules sowed})``,
    as a flax module's ``apply`` does (:func:`from_flax` sets it; a
    hand-written stage has nothing to sow). :func:`with_counters` is the
    one caller.
    """

    name: str
    init: Callable[[jax.Array, Array], Params]  # (rng, sample_input) -> params
    apply: Callable[[Params, Array], Array]     # (params, x) -> y
    objective: Optional[Callable[[Params, Array, Array], Array]] = None
    sows: bool = False

    def out_spec(self, params: Params, x_spec: jax.ShapeDtypeStruct) -> jax.ShapeDtypeStruct:
        """Shape-infer this stage's output without running it."""
        out = jax.eval_shape(self.apply, params, x_spec)
        return jax.ShapeDtypeStruct(out.shape, out.dtype)


def stage_backward(stage: "Stage", params: Params, x: Array,
                   g_out: Array) -> Params:
    """Rematerialized backward through one stage: re-run the forward under
    ``jax.vjp`` and pull the transported cotangent ``g_out`` through it.

    This is the JAX form of the reference's manual tape splice
    (``requires_grad_(True)`` at ``src/server_part.py:45`` +
    ``activations.backward(grad)`` at ``src/client_part.py:132``): the
    cotangent crosses the party boundary as data, and the local forward is
    recomputed rather than stored — the TPU-idiomatic FLOPs-for-memory
    trade, and it keeps each side independently jittable around the
    host-side transport call.
    """
    _, vjp = jax.vjp(lambda p: stage.apply(p, x), params)
    (g_params,) = vjp(g_out)
    return g_params


def with_counters(stage: "Stage", fn: Callable, params: Params, *args):
    """``fn(params, *args)`` for ``fn`` the stage's ``apply`` or
    ``objective``, and beside it the counters its modules sowed into
    ``obs/spans.STEP_COUNTERS`` meanwhile: ``{"<stage>/<module path>":
    {name: value}}``, values still on the device; ``{}`` for a stage that
    sows nothing. This is how a value that only exists inside a jitted
    step leaves it (the routed layer's pairs and rung, models/afmoe.py);
    a caller that does not come through here never makes the collection
    mutable, and its program has none of it."""
    if not stage.sows:
        return fn(params, *args), {}
    out, sown = fn(params, *args, mutable=[spans.STEP_COUNTERS])
    counters: dict = {}
    flat = traverse_util.flatten_dict(sown.get(spans.STEP_COUNTERS, {}))
    for (*path, name), value in flat.items():
        counters.setdefault("/".join((stage.name, *path)), {})[name] = value
    return out, counters


def _checkpointed(fn: Callable) -> Callable:
    """``jax.checkpoint(fn)``; keywords (``mutable=`` of
    :func:`with_counters`) are closed over, not traced as arguments."""
    plain = jax.checkpoint(fn)
    return lambda *args, **kw: jax.checkpoint(
        functools.partial(fn, **kw))(*args) if kw else plain(*args)


def remat_plan(plan: "SplitPlan") -> "SplitPlan":
    """A plan whose stages rematerialize under reverse-mode AD.

    Wraps every stage's ``apply`` in :func:`jax.checkpoint`, so the pipeline
    backward recomputes stage forwards instead of storing activations — the
    FLOPs-for-HBM trade that lets deep plans (ResNet-18 4-stage, many
    microbatches) fit. The MPMD party trainers already rematerialize by
    construction (:func:`stage_backward`); this extends the same policy to
    the fused/pipelined single-program paths (``Config.remat``).
    """
    stages = tuple(
        dataclasses.replace(
            s, apply=_checkpointed(s.apply),
            objective=s.objective and _checkpointed(s.objective))
        for s in plan.stages)
    return dataclasses.replace(plan, stages=stages)


def from_flax(name: str, module: Any,
              objective: Optional[str] = None) -> Stage:
    """Wrap a flax.linen Module as a Stage.

    Extra keyword arguments pass through to ``module.apply`` — the
    transformer stages use this for their KV-cache decode modes
    (``cache_len=``/``decode_cache=``/``pos=``, models/transformer.py);
    plain ``apply(params, x)`` is unchanged for every other caller.
    ``objective`` names the module's method ``(x, labels) -> losses``
    that is the stage's own objective (:class:`Stage`); ``init`` then
    goes through it, with one label for each index of all but ``x``'s
    last axis, so that the weights only the objective reads are made."""
    apply = lambda params, x, **kw: module.apply(params, x, **kw)
    if objective is None:
        return Stage(name=name, apply=apply, sows=True,
                     init=lambda rng, sample: module.init(rng, sample))
    return Stage(
        name=name, apply=apply, sows=True,
        init=lambda rng, sample: module.init(
            rng, sample, jnp.zeros(jnp.shape(sample)[:-1], jnp.int32),
            method=objective),
        objective=lambda params, x, labels, **kw: module.apply(
            params, x, labels, method=objective, **kw))


@dataclasses.dataclass(frozen=True)
class SplitPlan:
    """An ordered pipeline of stages plus the ownership split.

    ``boundaries[i]`` is the party owning stage i ("client" or "server").
    The classic 2-party split (reference) is ("client", "server"); the
    U-shaped split (BASELINE.md config 5) is ("client", "server", "client")
    — the label-holding head stays on the client.
    """

    stages: Tuple[Stage, ...]
    owners: Tuple[str, ...]

    def __post_init__(self) -> None:
        if len(self.stages) != len(self.owners):
            raise ValueError("stages and owners must have equal length")
        for o in self.owners:
            if o not in ("client", "server"):
                raise ValueError(f"unknown owner {o!r}")

    @property
    def num_stages(self) -> int:
        return len(self.stages)

    def stages_of(self, owner: str) -> Tuple[int, ...]:
        return tuple(i for i, o in enumerate(self.owners) if o == owner)

    def init(self, rng: jax.Array, sample: Array) -> Tuple[Params, ...]:
        """Initialize every stage, threading a real forward through (once)."""
        params = []
        x = jnp.asarray(sample)
        for stage in self.stages:
            rng, sub = jax.random.split(rng)
            p = stage.init(sub, x)
            params.append(p)
            x = stage.apply(p, x)
        return tuple(params)

    def apply(self, params: Sequence[Params], x: Array) -> Array:
        """Monolithic forward = composition of all stages.

        This is the `FullModel` equivalent (``src/model_def.py:31-46``)
        except it can never drift from the split: same stage functions,
        same params.
        """
        if len(params) != self.num_stages:
            raise ValueError(
                f"expected {self.num_stages} per-stage param trees, got {len(params)}"
            )
        for stage, p in zip(self.stages, params):
            x = stage.apply(p, x)
        return x

    def apply_range(self, params: Sequence[Params], x: Array,
                    start: int, stop: Optional[int] = None) -> Array:
        """Run stages [start, stop) — one party's contiguous span."""
        stop = self.num_stages if stop is None else stop
        for i in range(start, stop):
            x = self.stages[i].apply(params[i], x)
        return x
