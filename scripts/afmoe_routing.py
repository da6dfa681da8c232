"""How many (token, expert) pairs the routing sends to the experts held here.

    python scripts/afmoe_routing.py [--workload trinity-mini-fused-t8192] [--seed N]

(or ``--workload joyai-flash-fused-t8192``: any family whose routed layer is
``models/afmoe.py``'s.)

For the benchmark cell's check batch at the cell's sizes (on the CPU with
``JAX_PLATFORMS=cpu``: the rehearsal's), with the weights the benchmark makes
from the seed: one forward pass of the cell's plan (through the final stage's
own objective where it has one, so that a prediction module's block is
among them), and for every routed layer the
pairs per held expert (mean, max, empty experts), their share of the
worst-case buffer (tokens x experts per token rows), and the rung of the
layer's ladder of row counts (``models/afmoe.py:pair_rungs``, ``rung_of``:
the functions the model calls) that those pairs select on the device.  The
jitted step cannot hand these counts out beside the
activations (a ``Stage`` is ``(params, x) -> y``), so they are printed once,
here, for PERF.md; ``flops/afmoe.py`` counts the expected pairs under even
routing.  One JSON line a layer, then a summary.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", default="trinity-mini-fused-t8192")
    parser.add_argument("--seed", type=int, default=1)
    args = parser.parse_args()

    import run
    _, cell, config = run.load_cell(args.workload)
    jax = run.configure_jax()
    import jax.numpy as jnp
    import traffic
    import weights
    from split_learning_tpu.models import afmoe
    from split_learning_tpu.models.factory import get_plan

    found = run.find_devices(jax, cell["chips"])
    if found is None:
        return 1
    job = traffic.load(cell["traffic"])
    if found[1]:
        config, job = run.rehearsal_sizes(config, job)
    spec = config["plan"]
    kw = spec["kwargs"]
    key = weights.seed_key(args.seed)
    (x, y), = traffic.batches(job, config["data"], args.seed)[0]
    # the cell's own plan and the weights of the cell's run of this seed
    plan = get_plan(spec["model"], spec["mode"], jnp.dtype(spec["dtype"]), **kw)
    shapes = weights.stage_shapes(plan, x)
    params = [weights.make_stage(s, key, i) for i, s in enumerate(shapes)]

    def counts(m32, p):
        chosen, _ = afmoe.route(m32, p["router"], p["expert_bias"],
                                kw["experts_per_token"], kw["route_scale"])
        return afmoe.held_pairs(chosen, kw["expert_offset"], kw["experts_held"])[2]

    seen = {}
    norms = ("norm_pre_mlp", "norm_mlp")   # the routed part's input, by family
    watch = dict(capture_intermediates=lambda module, _: module.name in norms,
                 mutable=["intermediates"])

    def routed(weights, captured, path=""):
        """Every captured layer that holds experts, however deep."""
        for name, sub in captured.items():
            if "experts" in weights.get(name, {}):
                m32 = next(sub[n] for n in norms if n in sub)["__call__"][0]
                seen[path + name] = counts(m32.reshape(-1, m32.shape[-1]),
                                           weights[name]["experts"])
            elif isinstance(sub, dict):
                routed(weights.get(name, {}), sub, path + name + "/")

    def forward(stage, p, h):
        if stage.objective is not None:
            return stage.objective(p, h, jnp.asarray(y), **watch)
        return stage.apply(p, h, **watch)

    h = jnp.asarray(x)
    for stage, p in zip(plan.stages, params):
        h, state = jax.jit(forward, static_argnums=0)(stage, p, h)
        routed(p["params"], state["intermediates"])
    rows = x.size * kw["experts_per_token"]
    expected = rows * kw["experts_held"] / kw["experts_total"]
    rungs = afmoe.pair_rungs(rows, kw["experts_held"], kw["experts_total"])
    fills = []
    for name in sorted(seen):
        sizes = [int(s) for s in seen[name]]
        fills.append(sum(sizes))
        print(json.dumps({"layer": name, "pairs_here": sum(sizes),
                          "mean_per_expert": sum(sizes) / len(sizes),
                          "max_per_expert": max(sizes), "min_per_expert": min(sizes),
                          "empty_experts": sizes.count(0),
                          "buffer_rows": rows, "buffer_share": sum(sizes) / rows,
                          "rung": rungs[afmoe.rung_of(sum(sizes), rungs)]}))
    print(json.dumps({"workload": args.workload, "seed": args.seed, "tokens": int(x.size),
                      "expected_pairs_even_routing": expected,
                      "pairs_over_expected": [f / expected for f in fills],
                      "ladder": list(rungs),
                      "rungs_taken": [rungs[afmoe.rung_of(f, rungs)] for f in fills]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
