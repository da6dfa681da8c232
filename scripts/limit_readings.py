"""What a cell's limits are read from, leaf by leaf and through the
harness's own verdict, with faults planted in the program.

    chiprun -- python scripts/limit_readings.py --workload nemotronh-moe-fused-t8192
        --seeds 1,2,3 [--control-seeds 1,2] [--faults ssd_no_carry,ssd_fp8]
        [--reference-choices]

``benchmarks/calibrate.py``'s loop (a seed at a time at the cell's own sizes:
the float32 reference, then for a control seed the reference in bfloat16 and
fp8 in the program's place, then the program), and what that loop does not
say: the six worst leaves of every comparison (gap, leaf, the leaf's norm over
the median leaf's), ``benchmarks/check.py:verdict`` against the ``limits`` of
the cell's traffic file for every side, so that a control that no limit
refuses reads ``correct: true`` in its row, and for every name in ``--faults``
the program once more with that fault planted (``FAULTS``): a comparison that
is tight enough for what the cell exists for refuses each. With
``--reference-choices`` (the ``nemotron_h`` family) the sound program runs
its first step once more with every routed layer's choice of experts taken
from the float32 reference's forward pass (``reference_choices``), and the
row holds that step's ``grad_norm_gap``: what is left of the sound
program's gap when no token near a tie of router scores picks another
expert on one side than on the other. One JSON line a seed on stdout,
``{side: {"correct", "numbers", "worst"}}``; the verdicts' lines on stderr.
Needs a TPU like ``run.py``; ``JAX_PLATFORMS=cpu`` names the rehearsal at the
tiny sizes.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import statistics
import sys
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))


def ssd_no_carry(chunked):
    """The chunked recurrence without its carried state: every chunk starts
    from zeros (steps 3 and 4 of ops/ssd.py's header dropped)."""
    from split_learning_tpu.ops.common import pad_axis, round_up

    def run(x, dt, a, b, c, d_skip, chunk):
        bsz, t = x.shape[:2]
        whole = round_up(t, chunk)
        cut = lambda v: pad_axis(v, 1, whole).reshape(-1, chunk, *v.shape[2:])
        y = chunked(cut(x), cut(dt), a, cut(b), cut(c), d_skip, chunk)
        return y.reshape(bsz, whole, *y.shape[2:])[:, :t]

    return run


def ssd_fp8(chunked):
    """The recurrence's operands ``x``, ``B`` and ``C`` in float8_e4m3fn with
    a scale a tensor (the precision below the configuration's), their
    gradients passed straight through."""
    import jax
    import jax.numpy as jnp

    def q(v):
        v32 = v.astype(jnp.float32)
        scale = jnp.maximum(jnp.max(jnp.abs(v32)), 1e-30) / 448.0
        low = (v32 / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
        return (v32 + jax.lax.stop_gradient(low - v32)).astype(v.dtype)

    return lambda x, dt, a, b, c, d_skip, chunk: chunked(
        q(x), dt, a, q(b), q(c), d_skip, chunk)


FAULTS = {"ssd_no_carry": ssd_no_carry, "ssd_fp8": ssd_fp8}


def planted(name: str):
    """A context in which models/nemotron_h.py's recurrence has fault
    ``name``: build the program inside it."""
    from split_learning_tpu.models import nemotron_h
    return mock.patch.object(nemotron_h, "ssd_chunked",
                             FAULTS[name](nemotron_h.ssd_chunked))


def reference_choices(reference, kw: dict, parties, batch) -> list:
    """``chosen [rows x T, k]`` of every routed layer in order, as the
    float32 reference picks them for ``batch``: its own forward pass, a
    layer at a time."""
    import jax
    import jax.numpy as jnp
    from reference import common as ref_common
    from reference.afmoe import rms_norm
    clients, server = parties()
    mm, same = ref_common.matmul("f32"), reference.rounded("f32")
    layer = jax.jit(lambda p, h: jax.vmap(
        lambda row: reference.layer(p, row, kw, mm, same))(h))
    h = clients[0]["params"]["tok"]["embedding"][batch[0][0]]
    chosen = []
    for tree in (clients[0]["params"], server["params"]):
        for i in sorted(int(n[5:]) for n in tree if n.startswith("layer")):
            p = tree[f"layer{i}"]
            if "experts" in p:
                scores = jax.nn.sigmoid(jnp.matmul(
                    rms_norm(p["norm"], h, kw["norm_eps"]), p["experts"]["router"],
                    precision=jax.lax.Precision.HIGHEST))
                chosen.append(jax.lax.top_k(
                    scores + p["experts"]["expert_bias"],
                    kw["experts_per_token"])[1].reshape(-1, kw["experts_per_token"]))
            h = layer(p, h)
    return chosen


def choices_forced(chosen: list):
    """models/afmoe.py's ``route`` with the choice given: call ``n`` of a
    trace takes ``chosen[n mod len]`` (a trace meets the routed layers in
    order) and weighs it by the program's own scores."""
    import itertools

    import jax
    import jax.numpy as jnp
    calls = itertools.count()

    def route(m32, router_kernel, bias, per_token, route_scale):
        mine = chosen[next(calls) % len(chosen)]
        scores = jax.nn.sigmoid(jnp.dot(m32, router_kernel.astype(jnp.float32),
                                        precision=jax.lax.Precision.HIGHEST))
        picked = jnp.take_along_axis(scores, mine, axis=-1)
        return mine, picked / (picked.sum(-1, keepdims=True) + 1e-20) * route_scale

    return route


def worst_leaves(program: dict, reference: dict, n: int = 6) -> list:
    import check
    ref, got = check._flat(reference), check._flat(program)
    median = statistics.median(ref.values())
    gaps = sorted(((abs(got[k] - v) / max(v, median), k, v / median)
                   for k, v in ref.items()), reverse=True)
    return [(round(g, 5), k, round(r, 3)) for g, k, r in gaps[:n]]


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--control-seeds", default="")
    parser.add_argument("--faults", default="")
    parser.add_argument("--reference-choices", action="store_true")
    args = parser.parse_args()
    numbers_of = lambda text: [int(s) for s in text.split(",") if s]
    seeds, control_seeds = numbers_of(args.seeds), numbers_of(args.control_seeds)
    faults = [f for f in args.faults.split(",") if f]

    import run
    _, cell, config = run.load_cell(args.workload)
    jax = run.configure_jax()
    import check
    import traffic
    import weights
    from reference import common as ref_common

    found = run.find_devices(jax, cell["chips"])
    if found is None:
        return 1
    job = traffic.load(cell["traffic"])
    if found[1]:
        config, job = run.rehearsal_sizes(config, job)
    reference = importlib.import_module(f"reference.{config['family']}")
    driver_of = importlib.import_module(f"paths.{job['path']}").Driver

    for seed in sorted(set(seeds) | set(control_seeds)):
        key = weights.seed_key(seed)
        pool = traffic.batches(job, config["data"], seed)
        plan, _, parties = run.seeded_model(config, job, key, pool)
        follow = lambda precision: ref_common.train(
            reference.loss_fn(config, precision), parties, pool[:job["check_steps"]],
            config["train"]["lr"], job["reference_row_block"])

        def program(steps=job["check_steps"]):
            driver = driver_of(plan, run.program_config(config, job), key, job, pool[0][0][0])
            try:
                return run.first_steps(driver, pool, steps, parties)
            finally:
                driver.close()

        want, row = follow("f32"), {"seed": seed}

        def compare(side: str, got: dict) -> None:
            numbers = check.readings(got, want)
            ok = check.verdict(numbers, job["limits"],
                               lambda line: run.log(f"seed {seed} {side}: {line}"))
            row[side] = {"correct": ok, "numbers": {k: v[0] for k, v in numbers.items()},
                         "worst": worst_leaves(got["grad_norms"], want["grad_norms"])}

        if seed in control_seeds:
            for precision in ("bf16", "fp8"):
                compare(precision, follow(precision))
        jax.clear_caches()
        if seed in seeds:
            compare("program", program())
            for name in faults:
                jax.clear_caches()
                with planted(name):
                    compare(name, program())
            if args.reference_choices:
                from split_learning_tpu.models import afmoe
                chosen = reference_choices(reference, config["plan"]["kwargs"], parties, pool[0])
                jax.clear_caches()
                with mock.patch.object(afmoe, "route", choices_forced(chosen)):
                    got = program(steps=1)
                gap, leaf = check.worst_leaf_gap(got["grad_norms"], want["grad_norms"])
                run.log(f"seed {seed} reference_choices: grad_norm_gap = {gap:.6g} [{leaf}]")
                row["reference_choices"] = {
                    "grad_norm_gap": gap,
                    "worst": worst_leaves(got["grad_norms"], want["grad_norms"])}
            jax.clear_caches()
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
