"""A cell's first steps under three ladders of rows at one seed.

    chiprun -- python scripts/ladder_numbers_chip.py joyai-flash-fused-t8192 SEED

The program's check steps (``benchmarks/run.py:first_steps``: the compiled
fused step, the cell's weights and batches from the seed) with
``models/afmoe.pair_rungs`` replaced in turn by the worst case alone (one
program, no conditional), the lowest and the top rung (PR 29's two) and the
rule as it stands; then each against the other through the harness's own
``check.readings`` (the three numbers that decide ``correct``, here program
against program) and the six leaves whose first gradient's norm differs most.
It says how far the rungs of one ladder are from one another inside a whole
step: PR 40 read, for JF, two rungs against the top alone 0.0017 of a leaf's
norm and three against two 0.0019, which is why a change of ladder does not
print the parent's check digits. Three compiles of the step: about 5 minutes
on the chip; ``JAX_PLATFORMS=cpu`` rehearses at the tiny sizes, where the
ladder has one rung and every gap is 0.
"""

from __future__ import annotations

import importlib
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
import run


def leaf_gaps(a: dict, b: dict) -> tuple:
    """(the six leaves whose norms differ most, gap first; how many differ;
    how many there are): ``check.worst_leaf_gap``'s measure, every leaf."""
    flat = lambda d: {f"{p}/{k}": v for p, leaves in d.items() for k, v in leaves.items()}
    a, b = flat(a), flat(b)
    median = statistics.median(b.values())
    gaps = sorted(((abs(a[k] - b[k]) / max(b[k], median), k) for k in b), reverse=True)
    return [[round(g, 8), k] for g, k in gaps[:6]], sum(g > 0 for g, _ in gaps), len(gaps)


def main(workload: str, seed: int) -> None:
    _, cell, config = run.load_cell(workload)
    jax = run.configure_jax()
    import check
    import traffic
    import weights
    from split_learning_tpu.models import afmoe
    found = run.find_devices(jax, cell["chips"])
    job = traffic.load(cell["traffic"])
    if found[1]:
        config, job = run.rehearsal_sizes(config, job)
    driver_of = importlib.import_module(f"paths.{job['path']}").Driver
    key = weights.seed_key(seed)
    pool = traffic.batches(job, config["data"], seed)
    rule = afmoe.pair_rungs
    ladders = {"top": lambda p, h, t: (p,),
               "two": lambda p, h, t: tuple(sorted({rule(p, h, t)[0], p})),
               "three": rule}
    got = {}
    for name, fn in ladders.items():
        afmoe.pair_rungs = fn
        plan, _, parties = run.seeded_model(config, job, key, pool)
        driver = driver_of(plan, run.program_config(config, job), key, job, pool[0][0][0])
        try:
            got[name] = run.first_steps(driver, pool, job["check_steps"], parties)
        finally:
            driver.close()
        print(json.dumps({"ladder": name, "losses": got[name]["losses"]}), flush=True)
        del driver
        jax.clear_caches()      # one loaded step at a time
        afmoe._rung.cache_clear()
    for a, b in (("two", "top"), ("three", "top"), ("three", "two")):
        numbers = check.readings(got[a], got[b])
        worst, differing, leaves = leaf_gaps(got[a]["grad_norms"], got[b]["grad_norms"])
        print(json.dumps({f"{a}_against_{b}": {k: [v[0], v[1]] for k, v in numbers.items()},
                          "first_gradient_norms_differing": [differing, leaves], "worst_six": worst}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]))
