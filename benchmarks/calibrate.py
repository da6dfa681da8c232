"""Readings that a cell's limits are set from (not part of a benchmark run).

    python benchmarks/calibrate.py --workload <cell> --seeds 1,2,... \
        --control-seeds 1,2,3 --fault-seeds 1,2,3

In one process, at the cell's own sizes: for every seed, the sound program's
three numbers against the float32 reference; for every control seed, the
reference in bfloat16 and in fp8 (the precision below the configuration's)
put in the program's place; for every fault seed, the program with a fault
planted under its step (``Planted``, ``FAULTS``: the state handed back
unchanged, the update applied twice), each side with ``check.verdict``
against the cell's own limits.  Training's readings need no measured window.
``--look <part of a leaf's name> --look-seeds ...`` follows the leaves so
named through the check steps on both sides (``Looking``, ``look_row``): each
step's gradient as the optimizer got it, its norm, the cosine between the
program's and the reference's, and for a ``lambda_q*`` / ``lambda_k*`` leaf of
a differential attention the one scalar its gradient is (``d loss / d lambda``
times the map's weight: the gradient's component along its partner leaf).
A limit goes above the sound runs' largest and below the control's and the
faults' smallest; PERF.md records both and the limit.  Needs a TPU like
``run.py``; ``JAX_PLATFORMS=cpu`` names the rehearsal at the tiny sizes.
"""

from __future__ import annotations

import argparse
import gc
import importlib
import json
import sys

import run

FAULTS = ("unchanged", "twice")


class Planted:
    """The fused driver with a fault under its step: after the sound step the
    parameters are put back as they were (``unchanged``) or moved by the
    step's change once more (``twice``); the moments stay the step's own.
    The step donates its state, so the copy is kept on the host."""

    def __init__(self, driver, fault: str) -> None:
        self._driver, self._fault = driver, fault

    def __getattr__(self, name):
        return getattr(self._driver, name)

    def step(self, batch) -> list:
        import jax
        import jax.numpy as jnp
        trainer = self._driver.trainer
        before = jax.device_get(trainer.state.params)
        losses = self._driver.step(batch)
        moved = (lambda new, old: jnp.asarray(old)) if self._fault == "unchanged" else (
            lambda new, old: new + (new - jnp.asarray(old)))
        trainer.state = trainer.state._replace(
            params=jax.tree_util.tree_map(moved, trainer.state.params, before))
        return losses


def watched(trees: dict, needle: str) -> dict:
    """``{"party/leaf/name": float32 array}`` of the leaves whose name holds
    ``needle``, over ``{party: tree}``."""
    import jax
    import numpy as np
    found = {}
    for party, tree in trees.items():
        flat, _ = jax.tree_util.tree_flatten_with_path(tree)
        for path, val in flat:
            name = party + "/" + "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                                          for k in path)
            if needle in name:
                found[name] = np.asarray(jax.device_get(val), np.float32).ravel()
    return found


class Looking:
    """The fused driver with a look at some leaves: their values before every
    step and the gradient the optimizer got in it, worked out from Adam's
    first moment before and after (``mu' = b1 mu + (1 - b1) g``)."""

    def __init__(self, driver, needle: str) -> None:
        self._driver, self._needle = driver, needle
        self.held, self.grads = [], []

    def __getattr__(self, name):
        return getattr(self._driver, name)

    def step(self, batch) -> list:
        self.held.append(watched(self._driver.params(), self._needle))
        before = watched(self._driver.first_moments(), self._needle)
        losses = self._driver.step(batch)
        after = watched(self._driver.first_moments(), self._needle)
        self.grads.append({k: (after[k] - run.ADAM_B1 * before[k]) / (1 - run.ADAM_B1)
                           for k in after})
        return losses


def look_row(sides: dict) -> dict:
    """``sides``: ``{"program" | "reference": (params per step, grads per
    step, params after)}`` -> per leaf and step the norms, the cosine between
    the two sides' gradients and the scalar along the partner leaf; and the
    two sides' changes over the steps."""
    import numpy as np
    norm = lambda v: float(np.sqrt(np.sum(np.square(v))))
    cos = lambda a, b: float(np.dot(a, b) / max(norm(a) * norm(b), 1e-30))
    out = {}
    for leaf in sorted(sides["reference"][1][0]):
        tail = leaf.rsplit("/", 1)[1]
        partner = leaf.rsplit("/", 1)[0] + "/" + (
            tail.replace("_q", "_k") if "_q" in tail else tail.replace("_k", "_q"))
        steps = []
        for k in range(len(sides["reference"][1])):
            step = {"cos": cos(sides["program"][1][k][leaf], sides["reference"][1][k][leaf])}
            for side, (params, grads, _) in sides.items():
                g = grads[k][leaf]
                step[side + "_norm"] = norm(g)
                if partner != leaf and partner in params[k]:
                    other = params[k][partner]
                    step[side + "_scalar"] = float(np.dot(g, other) / np.dot(other, other))
                    step[side + "_cos_to_partner"] = cos(g, other)
            steps.append(step)
        change = {side: after[leaf] - params[0][leaf] for side, (params, _, after) in sides.items()}
        out[leaf] = {"steps": steps, "size": int(change["reference"].size),
                     "program_change": norm(change["program"]),
                     "reference_change": norm(change["reference"]),
                     "change_cos": cos(change["program"], change["reference"])}
    return out


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--control-seeds", default="")
    parser.add_argument("--fault-seeds", default="")
    parser.add_argument("--look-seeds", default="")
    parser.add_argument("--look", default="lambda")
    args = parser.parse_args()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = [int(s) for s in args.control_seeds.split(",") if s]
    fault_seeds = [int(s) for s in args.fault_seeds.split(",") if s]
    look_seeds = [int(s) for s in args.look_seeds.split(",") if s]
    _, cell, config = run.load_cell(args.workload)

    jax = run.configure_jax()
    import check
    import traffic
    import weights
    from reference import common as ref_common

    found = run.find_devices(jax, cell["chips"])
    if found is None:
        return 1
    rehearsal = found[1]
    job = traffic.load(cell["traffic"])
    if rehearsal:
        config, job = run.rehearsal_sizes(config, job)
    reference = importlib.import_module(f"reference.{config['family']}")
    driver_of = importlib.import_module(f"paths.{job['path']}").Driver
    rows = []
    if (fault_seeds or look_seeds) and job["path"] != "fused":
        raise SystemExit("the faults and the look go under the fused path's driver")
    quiet = lambda line: None

    def program(plan, key, pool, parties, fault=None, look=None):
        driver = driver_of(plan, run.program_config(config, job), key, job, pool[0][0][0])
        if look is not None:
            driver = Looking(driver, args.look)
        try:
            got = run.first_steps(Planted(driver, fault) if fault else driver, pool,
                                  job["check_steps"], parties)
            if look is not None:    # host arrays alone: the driver's state leaves the chip
                look["program"] = (driver.held, driver.grads,
                                   watched(driver.params(), args.look))
            return got
        finally:
            driver.close()

    for seed in sorted(set(seeds) | set(control_seeds) | set(fault_seeds) | set(look_seeds)):
        key = weights.seed_key(seed)
        pool = traffic.batches(job, config["data"], seed)
        plan, _, parties = run.seeded_model(config, job, key, pool)
        follow = lambda precision, watch=None: ref_common.train(
            reference.loss_fn(config, precision), parties, pool[:job["check_steps"]],
            config["train"]["lr"], job["reference_row_block"], watch)
        seen = {"params": [], "grads": []}

        def watch(count, held, grads):
            if grads is None:
                seen["after"] = watched(held, args.look)
                return
            seen["params"].append(watched(held, args.look))
            seen["grads"].append(watched(grads, args.look))

        want = follow("f32", watch if seed in look_seeds else None)
        row = {"seed": seed}

        def read(got):
            numbers = check.readings(got, want)
            return {**{k: v[0] for k, v in numbers.items()},
                    "worst_leaf": {k: v[1] for k, v in numbers.items()},
                    "correct": check.verdict(numbers, job["limits"], quiet)}

        if seed in control_seeds:
            for precision in ("bf16", "fp8"):
                row[precision] = read(follow(precision))
        jax.clear_caches()
        if seed in look_seeds:
            look = {}
            row["program"] = read(program(plan, key, pool, parties, look=look))
            row["look"] = look_row({
                **look, "reference": (seen["params"], seen["grads"], seen["after"])})
        elif seed in seeds:
            row["program"] = read(program(plan, key, pool, parties))
        for fault in FAULTS if seed in fault_seeds else ():
            row[fault] = read(program(plan, key, pool, parties, fault))
        print(json.dumps(row), flush=True)
        rows.append(row)
        gc.collect()      # a driver's state leaves the chip before the next seed's reference
    summary = {}
    for side in ("program", "bf16", "fp8") + FAULTS:
        for number in check.NUMBERS:
            vals = [r[side][number] for r in rows if side in r]
            if vals:
                summary[f"{side}.{number}"] = {"min": min(vals), "max": max(vals), "n": len(vals)}
        verdicts = [r[side]["correct"] for r in rows if side in r]
        if verdicts:
            summary[f"{side}.correct"] = f"{sum(verdicts)} of {len(verdicts)}"
    print(json.dumps({"workload": args.workload, "summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
