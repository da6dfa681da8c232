"""A digest of the jaxpr text of every cell's step programs, at the cells' real
sizes: whether a change left a cell's program as it was.

    JAX_PLATFORMS=cpu python scripts/step_digest.py [<checkout root>]

For each cell of ``BENCHMARK.json`` in the checkout (this one, or a ``git
archive`` of another commit unpacked somewhere: the same file serves both
sides), from shapes alone, nothing runs: a fused cell's
``FusedSplitTrainer._step`` (sha256 of ``str(jax.make_jaxpr(...))``, its
outputs and equations), and the same step with every output but the state and
the loss taken off and what only those needed removed
(``state_and_loss_alone``: equal digests there say two steps differ by their
extra outputs alone); a party cell's server loss with its gradients (scalar and
per example) and its client's forward and recomputed backward.  One JSON line
a cell; ``diff`` two runs.  About 6 minutes for the ten cells.  A function's
address in the text (a ``remat`` policy prints as ``<function ... at 0x...>``)
is left out of what is hashed, so that two processes agree.
``tests/test_step_digest.py`` holds every cell's digest (``cell_digests``): a PR
that means to change a cell's program replaces that cell's line there.
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import sys

HERE = os.path.abspath(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                    os.pardir))


def sha(text: str) -> str:
    return hashlib.sha256(re.sub(r" at 0x[0-9a-f]+", "", text).encode()
                          ).hexdigest()[:16]


def cell_digests(name: str) -> dict:
    """The digests of cell ``name``, from the program and the benchmark
    files that are importable (``main`` puts a checkout's first)."""
    import jax
    import jax.numpy as jnp
    from jax.extend.core import Var
    from jax.interpreters import partial_eval as pe

    import run
    import traffic
    from split_learning_tpu.core.losses import (
        cross_entropy, final_loss, per_example_cross_entropy)
    from split_learning_tpu.core.stage import stage_backward
    from split_learning_tpu.models.factory import get_plan
    from split_learning_tpu.runtime.fused import FusedSplitTrainer

    _, cell, config = run.load_cell(name)
    job = traffic.load(cell["traffic"])
    spec = config["plan"]
    plan = get_plan(spec["model"], spec["mode"], jnp.dtype(spec["dtype"]),
                    **spec["kwargs"])
    (x, y), = traffic.batches({**job, "pool": 1, "clients": 1}, config["data"], 1)[0]
    if job["path"] == "fused":
        made = []

        def build():
            made.append(FusedSplitTrainer(plan, run.program_config(config, job),
                                          jax.random.PRNGKey(0), x))
            return made[0].state

        state = jax.eval_shape(build)     # the trainer's programs, no weights made
        step = made[0]._step
        raw = jax.make_jaxpr(step.__wrapped__)(state, x, y).jaxpr
        keep = len(jax.tree_util.tree_leaves(state)) + 1
        cut, _ = pe.dce_jaxpr(raw, [i < keep for i in range(len(raw.outvars))])
        used = {v for e in cut.eqns for v in e.invars if isinstance(v, Var)}
        cut = cut.replace(constvars=[v for v in cut.constvars
                                     if v in used or v in cut.outvars])
        return {"fused_step": sha(str(jax.make_jaxpr(step)(state, x, y))),
                "outputs": len(raw.outvars), "equations": len(raw.eqns),
                "state_and_loss_alone": sha(str(cut)),
                "equations_alone": len(cut.eqns)}
    shapes = jax.eval_shape(plan.init, jax.random.PRNGKey(0), x)
    last = plan.num_stages - 1
    acts = jax.eval_shape(lambda p: plan.apply_range(p, x, 0, last), shapes)
    stage, bottom = plan.stages[last], plan.stages[0]
    found = {"server_" + op.__name__: sha(str(jax.make_jaxpr(jax.grad(
        lambda p, a: jnp.sum(final_loss(stage, p, a, y, op)),
        argnums=(0, 1)))(shapes[last], acts)))
        for op in (cross_entropy, per_example_cross_entropy)}
    found["client_fwd"] = sha(str(jax.make_jaxpr(
        lambda p: bottom.apply(p, x))(shapes[0])))
    found["client_bwd"] = sha(str(jax.make_jaxpr(
        lambda p, g: stage_backward(bottom, p, x, g))(shapes[0], acts)))
    return found


def main() -> int:
    root = os.path.abspath(sys.argv[1]) if len(sys.argv) > 1 else HERE
    sys.path.insert(0, root)
    sys.path.insert(0, os.path.join(root, "benchmarks"))
    os.chdir(root)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        names = [w["name"] for w in json.load(f)["workloads"]]
    for name in names:
        print(json.dumps({"cell": name, **cell_digests(name)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
