"""Readings that a cell's limits are set from (not part of a benchmark run).

    python benchmarks/calibrate.py --workload <cell> --seeds 1,2,... --control-seeds 1,2,3

In one process, at the cell's own sizes: for every seed, the sound program's
three numbers against the float32 reference; for every control seed, the
reference in bfloat16 and in fp8 (the precision below the configuration's)
put in the program's place.  Training's readings need no measured window.
A limit goes above the sound runs' largest and below the control's
smallest; PERF.md records both and the limit.  Needs a TPU like ``run.py``;
``JAX_PLATFORMS=cpu`` names the rehearsal at the tiny sizes.
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys

import run


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--control-seeds", default="")
    args = parser.parse_args()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = [int(s) for s in args.control_seeds.split(",") if s]
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
    for seed in sorted(set(seeds) | set(control_seeds)):
        key = weights.seed_key(seed)
        pool = traffic.batches(job, config["data"], seed)
        plan, _, parties = run.seeded_model(config, job, key, pool)
        follow = lambda precision: ref_common.train(
            reference.loss_fn(config, precision), parties, pool[:job["check_steps"]],
            config["train"]["lr"], job["reference_row_block"])
        want = follow("f32")
        row = {"seed": seed}
        if seed in control_seeds:
            for precision in ("bf16", "fp8"):
                row[precision] = {k: v[0] for k, v in check.readings(follow(precision), want).items()}
        jax.clear_caches()
        if seed in seeds:
            driver = driver_of(plan, run.program_config(config, job), key, job, pool[0][0][0])
            try:
                got = run.first_steps(driver, pool, job["check_steps"], parties)
            finally:
                driver.close()
            numbers = check.readings(got, want)
            row["program"] = {k: v[0] for k, v in numbers.items()}
            row["worst_leaf"] = {k: v[1] for k, v in numbers.items()}
            del driver, got
        print(json.dumps(row), flush=True)
        rows.append(row)
    summary = {}
    for side in ("program", "bf16", "fp8"):
        for number in check.NUMBERS:
            vals = [r[side][number] for r in rows if side in r]
            if vals:
                summary[f"{side}.{number}"] = {"min": min(vals), "max": max(vals), "n": len(vals)}
    print(json.dumps({"workload": args.workload, "summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
