"""The looped cell's ``remat`` forms beside one another on the chip, and a
reference that drops a pass held against the program.

    chiprun -- python scripts/ouro_forms_chip.py --seed N [--forms 4,3,2]
        [--steps 12] [--dropped-pass] [--exits 8]

For each ``remat_mlp_passes`` of ``--forms`` (``models/ouro.py``: in how many
of the passes the MLPs' ``gate`` and ``up`` products run again in the
backward pass; ``none`` is the plan without ``remat``), in the order given:
the cell's fused step (``ouro-loop-fused-t8192``'s configuration and sizes,
weights and rows from the seed) built, compiled, warmed and stepped ``--steps``
times by ``train_step``: one JSON line a form with the compile seconds, the
median step in ms, the tokens a second that is, and the process's
``peak_bytes_in_use`` so far (it only rises: give the forms from the least
memory up). A form that does not fit reads ``"error"``.  What the fit rule
(``benchmarks/configs/ouro-2.6b.json``, ``fit``) costs in rate is the
difference between its form and the next.

``--dropped-pass``: the program's first steps (``benchmarks/run.py``'s own
``first_steps``) against the float32 reference as it is and against the same
reference run with one pass fewer, through ``check.readings``: the second
must fail ``loss_gap`` by far.

``--exits N``: the cell's first N steps under the program's recorder
(``obs.enable()``), and from each step's ``counters_read`` span what the
objective sowed: ``exit_mass`` (the mean exit probability of every pass) and
``exit_loss`` (the mean cross-entropy of every pass), one JSON line a step.
On the CPU (``JAX_PLATFORMS=cpu``) it
rehearses at the configuration's tiny sizes.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

CELL = "ouro-loop-fused-t8192"


def with_kwargs(config: dict, **over) -> dict:
    plan = config["plan"]
    return {**config, "plan": {**plan, "kwargs": {**plan["kwargs"], **over}}}


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--forms", default="4,3,2")
    parser.add_argument("--steps", type=int, default=12)
    parser.add_argument("--dropped-pass", action="store_true")
    parser.add_argument("--exits", type=int, default=0)
    args = parser.parse_args()

    import run
    _, cell, config = run.load_cell(CELL)
    jax = run.configure_jax()
    import check
    import traffic
    import weights
    from reference import common as ref_common
    from reference import ouro as reference

    found = run.find_devices(jax, cell["chips"])
    if found is None:
        return 1
    devices, rehearsal = found
    job = traffic.load(cell["traffic"])
    if rehearsal:
        config, job = run.rehearsal_sizes(config, job)
    key = weights.seed_key(args.seed)
    pool = traffic.batches(job, config["data"], args.seed)
    from paths.fused import Driver

    def build(cfg):
        """(the cell's driver for ``cfg``, the function that makes its
        weights anew)."""
        plan, _, parties = run.seeded_model(cfg, job, key, pool)
        return Driver(plan, run.program_config(cfg, job), key, job,
                      pool[0][0][0]), parties

    for form in (f for f in args.forms.split(",") if f):
        over = {"remat": False, "remat_mlp_passes": 0} if form == "none" \
            else {"remat": True, "remat_mlp_passes": int(form)}
        row = {"form": form, **over}
        try:
            t0 = time.perf_counter()
            driver, _ = build(with_kwargs(config, **over))
            driver.step(pool[0])
            driver.sync()
            row["build_and_first_step_s"] = time.perf_counter() - t0
            driver.step(pool[1 % len(pool)])
            times = []
            for k in range(args.steps):
                t0 = time.perf_counter()
                driver.step(pool[k % len(pool)])
                times.append(time.perf_counter() - t0)
            driver.sync()
            row["step_ms_median"] = 1e3 * statistics.median(times)
            row["tokens_per_s"] = traffic.tokens_per_step(job) / statistics.median(times)
            del driver
        except Exception as exc:  # a form that does not fit is a reading too
            row["error"] = repr(exc)[:300]
        if not rehearsal:
            row["peak_bytes_in_use_so_far"] = max(
                d.memory_stats().get("peak_bytes_in_use", 0) for d in devices)
        print(json.dumps(row), flush=True)
        jax.clear_caches()

    if args.exits:
        from split_learning_tpu import obs
        from split_learning_tpu.obs import spans
        driver, _ = build(config)
        recorder = obs.enable()
        try:
            losses = [driver.step(pool[k % len(pool)])[0] for k in range(args.exits)]
        finally:
            obs.disable()
            driver.close()
        reads = [r["attrs"] for r in recorder.spans()
                 if r["name"] == spans.COUNTERS_READ]
        for k, (loss, read) in enumerate(zip(losses, reads), start=1):
            print(json.dumps({"step": k, "loss": loss,
                              "exit_mass": read[spans.EXIT_MASS][0],
                              "exit_loss": read[spans.EXIT_LOSS][0]}), flush=True)
        del driver
        jax.clear_caches()

    if args.dropped_pass:
        driver, parties = build(config)
        try:
            got = run.first_steps(driver, pool, job["check_steps"], parties)
        finally:
            driver.close()
        del driver
        jax.clear_caches()
        passes = config["plan"]["kwargs"]["passes"]
        for label, cfg in (("as_it_is", config),
                           ("one_pass_fewer", with_kwargs(config, passes=passes - 1))):
            want = ref_common.train(
                reference.loss_fn(cfg, "f32"), parties, pool[:job["check_steps"]],
                config["train"]["lr"], job["reference_row_block"])
            numbers = check.readings(got, want)
            print(json.dumps({
                "reference": label, "seed": args.seed,
                **{k: v[0] for k, v in numbers.items()},
                "correct": check.verdict(numbers, job["limits"],
                                         lambda *a: print(*a, file=sys.stderr))}),
                flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
