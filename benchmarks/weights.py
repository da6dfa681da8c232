"""Weights from the seed, made by the benchmark and given to both sides.

The program's parties call ``plan.init(rng, sample)`` for their weights;
:class:`SeededPlan` answers that call with the arrays made here, and the
plain reference is handed the same arrays by the harness.  So neither side
takes a weight the other has made.  One stage is one jitted call on the
device: a single draw of normals, cut into the leaves of the stage's tree,
each in the type the program stores it in.
Kernels, embeddings, positions and biases are N(0, 0.02); LayerNorm scales
are 1 + N(0, 0.02) (GPT-2's initializer range, with biases and scales moved
off their constants so that no gradient is zero by construction).
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from split_learning_tpu.core.stage import SplitPlan

STD = 0.02


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number; the driver's seeds pass 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def stage_shapes(plan: SplitPlan, sample) -> list:
    """Per stage, the tree of ShapeDtypeStructs that its ``init`` would give."""
    return _stage_shapes(plan, tuple(sample.shape), jnp.dtype(sample.dtype).name)


@functools.lru_cache(maxsize=None)
def _stage_shapes(plan: SplitPlan, shape: tuple, dtype: str) -> list:
    x = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    out = []
    for stage in plan.stages:
        shapes = jax.eval_shape(stage.init, key, x)
        out.append(shapes)
        x = jax.eval_shape(stage.apply, shapes, x)
    return out


def _leaf_name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


@functools.lru_cache(maxsize=None)
def _maker(treedef, shapes: tuple, names: tuple, dtypes: tuple):
    sizes = [math.prod(s) for s in shapes]

    def make(key):
        flat = jax.random.normal(key, (sum(sizes),), jnp.float32) * STD
        leaves, off = [], 0
        for shape, size, name, dtype in zip(shapes, sizes, names, dtypes):
            leaf = flat[off:off + size].reshape(shape)
            leaves.append((leaf + 1.0 if name == "scale" else leaf).astype(dtype))
            off += size
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(make)


def make_stage(shapes, key: jax.Array, index: int):
    """The weights of stage ``index`` for the party whose key is ``key``."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    maker = _maker(treedef, tuple(tuple(leaf.shape) for _, leaf in paths),
                   tuple(_leaf_name(p) for p, _ in paths),
                   tuple(jnp.dtype(leaf.dtype).name for _, leaf in paths))
    return maker(jax.random.fold_in(key, index))


class _LazyStages:
    """What ``plan.init`` returns: stage trees made on first use, so that a
    client that keeps only stage 0 never makes the server's 22 blocks."""

    def __init__(self, shapes, key) -> None:
        self._shapes, self._key, self._made = shapes, key, {}

    def __len__(self) -> int:
        return len(self._shapes)

    def __getitem__(self, i: int):
        i = range(len(self._shapes))[i]
        if i not in self._made:
            self._made[i] = make_stage(self._shapes[i], self._key, i)
        return self._made[i]

    def __iter__(self):
        return (self[i] for i in range(len(self)))


@dataclasses.dataclass(frozen=True)
class SeededPlan(SplitPlan):
    """The program's plan, with ``init`` answered from the seed."""

    def init(self, rng, sample):
        return _LazyStages(stage_shapes(self, jnp.asarray(sample)), rng)


def seeded(plan: SplitPlan) -> SeededPlan:
    return SeededPlan(stages=plan.stages, owners=plan.owners)


def client_key(base: jax.Array, index: int, clients: int) -> jax.Array:
    """The key ``MultiClientSplitRunner`` hands client ``index``."""
    return jax.random.fold_in(base, index) if clients > 1 else base
