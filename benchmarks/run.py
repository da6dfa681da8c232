"""The benchmark's one command.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: reads the cell from ``BENCHMARK.json``, its configuration from
``configs/``, its job from ``traffic/`` and its driver from ``paths/``; makes
weights and data from the seed; lets the plain reference follow the first
steps; builds the program, drives it through the same steps and compares;
warms up; measures for ``--seconds``; prints the result as the last line.
It refuses to run without a TPU, unless the caller named the CPU
(``JAX_PLATFORMS=cpu``): that is the rehearsal, at the tiny sizes the files
give under ``rehearsal``, and it prints no device metric.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse
import gc
import importlib
import importlib.util
import json
import math
import os
import shutil
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

OUT_DIR = os.path.join(ROOT, ".bench_out")
TRACE_SECONDS = 4.0
HARNESS_SPANS = ("fused.train_step", "party.train_round", "party.split_step",
                 "chain.step", "data.next_batch")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
ADAM_B1 = 0.9


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def phase(name: str) -> None:
    """Where set-up's time goes, on stderr: seconds since the process began."""
    log(f"phase {name} at {time.perf_counter() - _T0:.1f} s")


def load_cell(name: str) -> tuple:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json (have {sorted(cells)})")
    cell = cells[name]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    return bench, cell, config


def metrics_of(bench: dict, cell: str, group: str) -> list:
    return [m for m in bench[group] if cell in m.get("workloads", [cell])]


def layer_reader(name: str):
    path = os.path.join(HERE, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("layer_metric_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def configure_jax():
    """The compile cache where the program puts it, holding every program."""
    from split_learning_tpu.utils import configure_compile_cache
    configure_compile_cache()
    import jax
    # JAX's 1 s floor leaves the many small programs of a run uncached
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # no eviction: a cell's programs must all still be there for its next run
    jax.config.update("jax_compilation_cache_max_size", -1)
    return jax


def find_devices(jax, chips: int):
    """(devices, rehearsal), or None where there is nothing to measure on:
    no TPU and the caller did not name the CPU, or fewer chips than asked."""
    devices = jax.devices()
    platform = devices[0].platform
    rehearsal = platform == "cpu" and os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    if platform != "tpu" and not rehearsal:
        log(f"refusing to measure on {platform!r}: no TPU (JAX_PLATFORMS=cpu names the rehearsal)")
        return None
    if len(devices) < chips:
        log(f"the cell needs {chips} chips, JAX found {len(devices)}")
        return None
    return devices, rehearsal


def rehearsal_sizes(config: dict, job: dict) -> tuple:
    """The tiny sizes that each file gives under ``rehearsal``, laid over it."""
    over = config.get("rehearsal", {})
    config = {**config, **{k: v for k, v in over.items() if k not in ("plan_kwargs", "data")}}
    config["plan"] = {**config["plan"],
                      "kwargs": {**config["plan"]["kwargs"], **over.get("plan_kwargs", {})}}
    config["data"] = {**config["data"], **over.get("data", {})}
    return config, {**job, **job.get("rehearsal", {})}


def seeded_model(config: dict, job: dict, key, pool: list) -> tuple:
    """(the program's plan with ``init`` answered from the seed, its stages'
    shapes, a function that makes every party's weights anew)."""
    import jax.numpy as jnp
    import weights
    from split_learning_tpu.models.factory import get_plan
    spec = config["plan"]
    plan = weights.seeded(get_plan(spec["model"], spec["mode"], jnp.dtype(spec["dtype"]),
                                   **spec["kwargs"]))
    shapes = weights.stage_shapes(plan, pool[0][0][0])
    keys = [weights.client_key(key, i, job["clients"]) for i in range(job["clients"])]

    def parties():
        return ([weights.make_stage(shapes[0], k, 0) for k in keys],
                weights.make_stage(shapes[1], key, 1))

    return plan, shapes, parties


def program_config(config: dict, job: dict):
    from split_learning_tpu.utils.config import Config
    spec = config["plan"]
    return Config(mode=spec["mode"], model=spec["model"], dtype=spec["dtype"],
                  optimizer=config["train"]["optimizer"], lr=config["train"]["lr"],
                  batch_size=job["clients"] * job["rows_per_client"],
                  num_clients=job["clients"])


def first_steps(driver, pool: list, steps: int, seeded_parties) -> dict:
    """Drive the program through its first steps by the window's own call
    and read what the reference is compared with: each step's losses, the
    first gradient's norm per leaf as the optimizer got it (Adam's first
    moment after one step, over 1 - b1), and the norm of each leaf's change
    after the steps (against the weights made anew from the seed)."""
    from reference import common as ref_common
    got = {"losses": []}
    driver.check_gate(True)
    for k in range(steps):
        got["losses"].append([float(x) for x in driver.step(pool[k])])
        phase(f"check step {k + 1}")
        if k == 0:
            got["grad_norms"] = {
                p: {leaf: n / (1 - ADAM_B1) for leaf, n in ref_common.named(
                    ref_common.leaf_norms(mu)).items()}
                for p, mu in driver.first_moments().items()}
    driver.sync()
    driver.check_gate(False)
    clients, server = seeded_parties()
    first = {**{f"client{i}": p for i, p in enumerate(clients)}, "server": server}
    got["delta_norms"] = {p: ref_common.named(ref_common.leaf_delta_norms(tree, first[p]))
                          for p, tree in driver.params().items()}
    return got


def end_to_end_values(tokens_per_s: float, reply_ms: list, peak_flops: float,
                      flops_per_token: float) -> dict:
    values = {"tokens_per_s": tokens_per_s,
              "mfu_pct": 100.0 * tokens_per_s * flops_per_token / peak_flops}
    if reply_ms:
        values["reply_ms_p50"] = statistics.median(reply_ms)
        values["reply_ms_p95"] = percentile(reply_ms, 0.95)
    return values


def host_spans() -> tuple:
    """The host spans that may name an idle gap: the harness's own wrappers
    and, beneath them, the program's (imported where it is used, after
    ``configure_jax``, not copied: a span the program gains is named too)."""
    from split_learning_tpu.obs.spans import ALL_SPANS
    return HARNESS_SPANS + tuple(ALL_SPANS)


def reduce_trace(trace_dir: str, workload: str, chips: int) -> dict:
    """Reduce the run's trace, and leave its table of operations and a small
    slice under ``.bench_out/`` for whoever reads the run afterwards."""
    import trace_reduce
    flat = trace_reduce.load(trace_reduce.newest_xplane(trace_dir), host_spans())
    reduced = trace_reduce.reduce(flat, devices=chips)
    with open(os.path.join(OUT_DIR, workload + ".trace_slice.json"), "w") as f:
        json.dump(trace_reduce.slice_of(flat, 300), f)
    with open(os.path.join(OUT_DIR, workload + ".trace_ops.json"), "w") as f:
        json.dump({k: reduced[k] for k in ("busy_s", "window_s", "op_seconds",
                                           "op_counts", "idle_gaps", "calls")}, f)
    return reduced


def peak_bytes_of(stats: dict) -> int:
    """The most a chip held at once, from its ``memory_stats()`` after the
    window.  On this runtime ``peak_bytes_in_use`` counts live buffers and
    loaded code, and a running program's temporaries are ``bytes_reserved``:
    room set aside at the bottom of memory when the program is loaded, and
    given up again when buffers need it (a probe on the chip, PERF.md
    section 7, item 9), so the two peaks are no one moment's sum: set-up's
    second copy of the weights and a step's temporaries never met.  What a
    step held while it ran is what the chip holds now, the state and the
    code, with the widest reservation on top."""
    return max(stats.get("peak_bytes_in_use", 0),
               stats.get("bytes_in_use", 0) + stats.get("peak_bytes_reserved", 0))


def percentile(values: list, q: float) -> float:
    ordered = sorted(values)
    return ordered[min(len(ordered) - 1, max(0, math.ceil(q * len(ordered)) - 1))]


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args()
    bench, cell, config = load_cell(args.workload)

    jax = configure_jax()
    phase("program and jax imported")
    import check
    import traffic
    import trace_reduce
    import weights
    from reference import common as ref_common

    found = find_devices(jax, cell["chips"])
    if found is None:
        return 1
    devices, rehearsal = found
    phase("devices found")
    platform = devices[0].platform
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    kind = devices[0].device_kind
    if not rehearsal and kind not in peaks:
        log(f"device_kind {kind!r} is not in peaks.json")
        return 1

    job = traffic.load(cell["traffic"])
    if rehearsal:
        config, job = rehearsal_sizes(config, job)
    family = config["family"]
    flops = importlib.import_module(f"flops.{family}")
    reference = importlib.import_module(f"reference.{family}")
    paths_driver = importlib.import_module(f"paths.{job['path']}").Driver

    compile_events = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compile_events.append((time.perf_counter(), secs))
        if name == COMPILE_EVENT else None)

    phase("imports done")
    # -- inputs and weights from the seed ---------------------------------- #
    key = weights.seed_key(args.seed)
    pool = traffic.batches(job, config["data"], args.seed)
    steps_checked = job["check_steps"]
    sample = pool[0][0][0]

    plan, _, seeded_parties = seeded_model(config, job, key, pool)

    phase("inputs made, plan built")
    # -- the program: built once, checked, warmed, then measured ------------ #
    driver = paths_driver(plan, program_config(config, job), key, job, sample)
    try:
        phase("program built")
        got = first_steps(driver, pool, steps_checked, seeded_parties)
        phase("first steps driven and read")
        driver.warm_up(pool[steps_checked % len(pool)])
        driver.step(pool[(steps_checked + 1) % len(pool)])
        driver.sync()
        phase("warmed up")

        seconds = min(args.seconds, TRACE_SECONDS) if args.trace else args.seconds
        trace_dir = os.path.join(OUT_DIR, "trace", args.workload)
        if args.trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            os.makedirs(trace_dir, exist_ok=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # no Python frames: they slow the host
            options.host_tracer_level = 1    # the benchmark's own spans and no more
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        replies_before = len(driver.reply_seconds)
        counters_before = driver.counters()
        setup_s = time.perf_counter() - _T0
        losses, failed, steps, step_ends = [], 0, 0, []
        t_window = time.perf_counter()
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            while time.perf_counter() - t_window < seconds:
                with jax.profiler.TraceAnnotation("data.next_batch"):
                    batch = pool[steps % len(pool)]
                try:
                    losses.extend(driver.step(batch))
                except Exception as exc:  # a failed unit is counted, not fatal
                    log(f"step {steps} failed: {exc!r}")
                    failed += len(batch) if driver.unit == "replies" else 1
                steps += 1
                step_ends.append(time.perf_counter())
            driver.sync()
        elapsed = time.perf_counter() - t_window
        if args.trace:
            jax.profiler.stop_trace()
        counters_after = driver.counters()
        built_in_window = sum(1 for at, _ in compile_events if at >= t_window)
        nonfinite = sum(1 for x in losses if x is None or not math.isfinite(x))
        failed += nonfinite
        attempted = len(losses) + (failed - nonfinite)
        done_tokens = (len(losses) - nonfinite) * traffic.tokens_per_step(job) / (
            job["clients"] if driver.unit == "replies" else 1)
        stats = [d.memory_stats() or {} for d in devices] if not rehearsal else []
        peak_bytes = max((peak_bytes_of(s) for s in stats), default=0)
        for d, s in zip(devices, stats):
            log(f"memory_stats after the window, {d}: "
                f"{ {k: v for k, v in sorted(s.items()) if 'bytes' in k} }")
    finally:
        driver.close()
    reply_seconds, wire_bytes = driver.reply_seconds, driver.wire_bytes
    del driver
    gc.collect()

    # -- the reference follows the same first steps, once the program's state
    # is freed: its memory and its time are then no part of what is reported
    t_ref = time.perf_counter()
    want = ref_common.train(reference.loss_fn(config, "f32"), seeded_parties,
                            pool[:steps_checked], config["train"]["lr"],
                            job["reference_row_block"])
    reference_s = time.perf_counter() - t_ref
    numbers = check.readings(got, want)
    for k, (a, b) in enumerate(zip(got["losses"], want["losses"])):
        log(f"check step {k + 1} losses program {a} reference {b}")
    numbers["nonfinite_losses"] = (float(nonfinite), "in the window")
    numbers["programs_built_in_window"] = (float(built_in_window), "compile events after window start")
    limits = {**job["limits"], "nonfinite_losses": 0, "programs_built_in_window": 0}
    check_lines = []
    correct = check.verdict(numbers, limits, check_lines.append) and failed == 0
    compiled = [s for at, s in compile_events if at < t_window]
    log(f"backend compile events before the window: {sum(compiled):.1f} s in {len(compiled)}")
    log(f"reference {reference_s:.1f} s, after the window (not in setup_s)")

    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": peak_bytes}
    result = {"correct": bool(correct), "attempted": attempted, "failed": failed}
    reply_ms = [s * 1e3 for s in reply_seconds[replies_before:]]
    if not args.trace:
        values = {"setup_s": setup_s}
        if not rehearsal:
            values.update(end_to_end_values(
                done_tokens / elapsed, reply_ms, cell["chips"] * peaks[kind]["bf16_flops_per_s"],
                flops.train_flops_per_token(config, job["tokens_per_row"])))
        wanted = metrics_of(bench, args.workload, "end_to_end")
    else:
        reduced = None
        if not rehearsal:
            reduced = reduce_trace(trace_dir, args.workload, cell["chips"])
            device["busy_s"], device["window_s"] = reduced["busy_s"], reduced["window_s"]
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
        shutil.rmtree(trace_dir, ignore_errors=True)
        run = {"config": config, "job": job, "flops": flops,
               "peak": peaks.get(kind), "trace": reduced, "peak_bytes": peak_bytes,
               "compile_s": sum(compiled), "programs_built_in_window": built_in_window,
               "counters_before": counters_before, "counters_after": counters_after,
               "wire_bytes": wire_bytes[replies_before:], "rehearsal": rehearsal}
        wanted = metrics_of(bench, args.workload, "per_layer")
        values = {m["name"]: layer_reader(m["name"])(run) for m in wanted}
    units = {m["name"]: m["unit"] for m in wanted}
    result["metrics"] = {name: {"value": value, "unit": units[name]}
                         for name, value in values.items()
                         if name in units and value is not None}
    if reply_ms:
        log(f"replies timed in the window: {len(reply_ms)}")
    # how the window's steps (rounds) spread, and what the server's counters
    # did meanwhile: whether a run that reads low had a few stalls or was slow
    # all through (PERF.md section 2)
    step_ms = sorted(1e3 * (b - a) for a, b in zip([t_window] + step_ends, step_ends))
    if len(step_ms) >= 4:
        q1, q2, q3 = statistics.quantiles(step_ms, n=4)
        log(f"window steps {len(step_ms)}: ms min {step_ms[0]:.1f} q1 {q1:.1f} median {q2:.1f} "
            f"q3 {q3:.1f} max {step_ms[-1]:.1f}")
    moved = {k: counters_after[k] - counters_before.get(k, 0) for k in counters_after
             if isinstance(counters_after[k], (int, float)) and not isinstance(counters_after[k], bool)}
    if moved:
        log(f"server counters over the window: {moved}")
    result["device"] = device
    # each number compared beside its limit: the last lines on stderr, and the
    # last key of the result's line.  Their reader is the driver's record of a
    # run that is not correct, which keeps the end of each and nothing else the
    # run printed (the ledger's ``last_line_numbers``)
    for line in check_lines:
        log(line)
    result["checks"] = {name: {"value": value if math.isfinite(value) else str(value),
                               "limit": limits[name]}
                        for name, (value, _) in numbers.items()}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
