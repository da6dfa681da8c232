#!/usr/bin/env python
"""On-chip op-level profile of a fused training step.

SURVEY.md §5 (tracing/profiling) promises jax.profiler traces; this
script turns one into a committed, reviewable artifact: run a fused
workload on the default backend under
``utils.profiling.device_trace``, parse the Perfetto trace the profiler
writes, and emit the top ops by total device time plus the traced
steps/sec. Models: the split CNN headline (default) or the bench
transformer trunk via ``SLT_PROFILE_MODEL=transformer``, configured
by the SAME env knobs AND defaults as the bench legs
(``SLT_BENCH_SEQ`` / ``SLT_BENCH_DMODEL`` / ``SLT_BENCH_ATTN`` /
``SLT_BENCH_DTYPE`` / ``SLT_BENCH_BATCH``) so profiling the leg you
just benchmarked takes the same exports. Output:
``artifacts/tpu_profile_<date>.json`` for the CNN, or
``tpu_profile_transformer_<attn>_<dtype>_T<seq>_d<width>_<date>.json``
(committed when produced on the chip), plus one stdout JSON line.

The trace file itself (MBs, binary) stays out of git — the summary is
the evidence: which XLA fusions the step spends its time in, and how
much of the wall clock is device-occupied vs dispatch gap.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

WARMUP = 20
TRACED = 50


def profile_batch() -> int:
    """The profiled batch, from the bench legs' own knob
    (``SLT_BENCH_BATCH``). ``SLT_PROFILE_BATCH`` is the knob's
    pre-unification name: honored as a deprecated fallback (with a
    warning) so old invocations keep profiling the shape they asked
    for, and refused outright when both are set and disagree — the
    silent alternative would profile a different shape than the leg it
    claims to corroborate."""
    bench = os.environ.get("SLT_BENCH_BATCH")
    legacy = os.environ.get("SLT_PROFILE_BATCH")
    if legacy is not None:
        if bench is not None and int(bench) != int(legacy):
            raise SystemExit(
                f"SLT_PROFILE_BATCH={legacy} conflicts with "
                f"SLT_BENCH_BATCH={bench}: drop the deprecated "
                "SLT_PROFILE_BATCH (the bench knob is authoritative)")
        print("[profile] SLT_PROFILE_BATCH is deprecated; use "
              "SLT_BENCH_BATCH (same default, shared with the bench "
              "legs)", file=sys.stderr)
        return int(legacy)
    return int(bench) if bench is not None else 64


def newest_trace(log_dir: str) -> str | None:
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile",
                                   "*", "*.trace.json.gz"))
    return max(paths, default=None)


def summarize_trace(path: str, top_n: int = 30) -> dict:
    # 30, not 15: a wide-model step fragments the trunk into many
    # mid-sized fusions that pushed half the mha.* kernels below a
    # 15-op cut (seen at d1024), and the mha share is exactly what
    # the artifact exists to show
    """Chrome-trace summary: per process (pid), top events by total
    duration. Device processes carry the XLA op timeline; host
    processes carry Python/runtime frames."""
    with gzip.open(path, "rt") as f:
        trace = json.load(f)
    events = trace.get("traceEvents", [])
    pid_names = {e["pid"]: e["args"]["name"] for e in events
                 if e.get("ph") == "M" and e.get("name") == "process_name"
                 and "name" in e.get("args", {})}
    per_proc: dict = {}
    for e in events:
        if e.get("ph") != "X" or "dur" not in e:
            continue
        proc = pid_names.get(e["pid"], str(e["pid"]))
        ops = per_proc.setdefault(proc, {})
        rec = ops.setdefault(e["name"], {"count": 0, "total_us": 0.0})
        rec["count"] += 1
        rec["total_us"] += float(e["dur"])
    out = {}
    for proc, ops in per_proc.items():
        top = sorted(ops.items(), key=lambda kv: -kv[1]["total_us"])[:top_n]
        out[proc] = [{"name": n, "count": r["count"],
                      "total_us": round(r["total_us"], 1),
                      "mean_us": round(r["total_us"] / r["count"], 2)}
                     for n, r in top]
    return out


def main() -> None:
    from split_learning_tpu.utils import configure_compile_cache
    configure_compile_cache()

    import numpy as np

    import jax

    from split_learning_tpu.data.datasets import synthetic
    from split_learning_tpu.models import get_plan
    from split_learning_tpu.runtime.fused import FusedSplitTrainer
    from split_learning_tpu.utils import Config
    from split_learning_tpu.utils.profiling import device_trace

    model = os.environ.get("SLT_PROFILE_MODEL", "split_cnn")
    # the bench legs' own env names AND defaults, so profiling the leg
    # you just benchmarked takes the SAME exports — a divergent knob
    # (or a divergent default on a shared name, which is worse) would
    # silently profile a different program than the leg it claims to
    # corroborate
    batch = profile_batch()
    attn = os.environ.get("SLT_BENCH_ATTN", "full")
    dtype = os.environ.get("SLT_BENCH_DTYPE", "float32")
    seq = d_model = None
    if model == "transformer":
        # the bench transformer trunk, from the one shared builder
        # (bench.transformer_trunk_kwargs): profiles WHERE the
        # flash/dense step spends its device time, complementing the
        # steps/sec legs
        from bench import _seq_len, transformer_trunk_kwargs
        from split_learning_tpu.models.transformer import transformer_plan
        tkw = transformer_trunk_kwargs("split", dtype)
        seq = _seq_len()   # the same parse the trunk builder used
        d_model = tkw["d_model"]
        plan = transformer_plan(attn=attn, **tkw)
        rs = np.random.RandomState(0)
        x = rs.randint(0, 256, (batch, seq)).astype(np.int32)
        y = rs.randint(0, 10, (batch,)).astype(np.int32)
    elif model == "split_cnn":
        ds = synthetic("mnist", n_train=batch, n_test=8, seed=0)
        x = np.asarray(ds.train.x[:batch])
        y = np.asarray(ds.train.y[:batch])
        plan = get_plan(mode="split")
    else:
        # bench.py convention: a bad knob value is refused, never
        # silently measured (and here mislabeled) as something else
        raise SystemExit(f"SLT_PROFILE_MODEL={model}: only split_cnn "
                         "and transformer are profilable")

    cfg = Config(mode="split", batch_size=batch, lr=0.01)
    trainer = FusedSplitTrainer(plan, cfg, jax.random.PRNGKey(0), x)
    device = trainer.state.step.devices().pop()

    loss = None
    for _ in range(WARMUP):
        loss = trainer.train_step_async(x, y)
    # drain warmup with a data-dependent transfer: warmup steps still
    # executing when the trace opens would pollute the traced window's
    # op counts and steps/sec
    float(loss)

    log_dir = os.environ.get("SLT_PROFILE_DIR") or os.path.join(
        "/tmp", f"slt_profile_{os.getpid()}")
    with device_trace(log_dir):
        t0 = time.perf_counter()
        loss = None
        for _ in range(TRACED):
            loss = trainer.train_step_async(x, y)
        # close with a host transfer of a data-dependent scalar: the
        # float() cannot complete until the whole donated-state chain
        # has executed (the same window close as bench.py's legs)
        float(loss)
        # ...and the clock closes BEFORE the with-block exits:
        # stop_trace serializes the whole Perfetto trace (measured
        # 70 s for a 50-step transformer trace) and must never ride
        # the steps/sec denominator
        wall = time.perf_counter() - t0

    trace_path = newest_trace(log_dir)
    summary = {
        "what": (f"jax.profiler trace summary of the fused {model} "
                 "step (top ops by total time per trace process)"),
        "date": time.strftime("%Y-%m-%d"),
        "platform": device.platform,
        "device_kind": getattr(device, "device_kind", device.platform),
        "model": model,
        "attn": attn if model == "transformer" else None,
        "dtype": dtype if model == "transformer" else None,
        "seq_len": seq,
        "d_model": d_model,
        "batch": batch,
        "traced_steps": TRACED,
        "traced_steps_per_sec": round(TRACED / wall, 2),
        "trace_file": trace_path,
        "top_ops": summarize_trace(trace_path) if trace_path else None,
    }
    stem = ("tpu_profile" if model == "split_cnn"
            else f"tpu_profile_{model}_{attn}_{dtype}_T{seq}_d{d_model}")
    out_path = os.path.join(REPO, "artifacts",
                            f"{stem}_{time.strftime('%Y-%m-%d')}.json")
    on_tpu = device.platform == "tpu"
    if on_tpu:
        with open(out_path, "w") as f:
            json.dump(summary, f, indent=1)
        print(f"[profile] wrote {out_path}", file=sys.stderr)
    else:
        print(f"[profile] platform={device.platform}: not committing a "
              f"TPU-named artifact", file=sys.stderr)
    # stdout line for the window runner (drop the bulky op table)
    print(json.dumps({k: v for k, v in summary.items() if k != "top_ops"}
                     | {"top_op_processes": list((summary["top_ops"] or {})),
                        "valid": on_tpu}))
    if not on_tpu:
        # non-zero so the window runner records an error (retried on a
        # later window) instead of marking the leg permanently done
        sys.exit(1)


if __name__ == "__main__":
    main()
