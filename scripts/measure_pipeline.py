#!/usr/bin/env python
"""Measure the GPipe pipeline overheads parallel/pipeline.py documents.

Quantifies, for the pipelined trainer (`PipelinedTrainer`):

1. **Structural facts** (exact, computed from the plan + compiled HLO):
   - flat-buffer size (`max_elems`), per-hop padding elements/bytes — the
     cost of heterogeneous stage shapes riding one ppermute buffer;
   - ticks per step T = M+S-1 and the analytic bubble fraction
     (S-1)/(M+S-1);
   - collective ops in the compiled module (collective-permute /
     all-reduce counts).
2. **Bubble scaling** (measured): steps/sec vs microbatch count M at a
   fixed microbatch size on the 8-virtual-device CPU mesh. On virtual
   devices every rank's branch executes serially on the host, so useful
   work is M*S of T*S stage executions and throughput per microbatch
   should track the GPipe efficiency M/(M+S-1) — the measurement is
   *scheduling-relative* (no real ICI; says nothing about absolute TPU
   step time, everything about the schedule's shape).

Writes ``artifacts/pipeline_measurements.json``; the structural half is
asserted by tests/test_pipeline_perf.py. Run: XLA_FLAGS=--xla_force_host_platform_device_count=8
JAX_PLATFORMS=cpu python scripts/measure_pipeline.py
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# This measurement is CPU-mesh-only (scheduling-relative pipeline
# accounting). The CPU pin must exist before the interpreter loads jax
# (the device-plugin shim registers at startup), so __main__ re-execs
# via utils.reexec_pinned_cpu — see its docstring; import stays
# side-effect-free.


def hop_stats(trainer) -> dict:
    """Padding accounting for the common flat ppermute buffer."""
    import numpy as np
    specs = trainer._specs
    itemsize = np.dtype(trainer.buf_dtype).itemsize
    hops = []
    for i in range(len(specs) - 1):
        # hop i carries stage i+1's input, padded to buf_elems
        useful = specs[i + 1].in_elems
        hops.append({
            "hop": f"stage{i}->stage{i + 1}",
            "useful_elems": useful,
            "padded_elems": trainer.buf_elems - useful,
            "bytes_per_microbatch": trainer.mb_size * trainer.buf_elems * itemsize,
            "useful_bytes_per_microbatch": trainer.mb_size * useful * itemsize,
            "padding_fraction": 1.0 - useful / trainer.buf_elems,
        })
    return {"buf_elems": trainer.buf_elems,
            "buf_dtype": str(np.dtype(trainer.buf_dtype)),
            "mb_size": trainer.mb_size, "hops": hops}


def hlo_counts(trainer, x, y) -> dict:
    """Collective ops in the compiled module. Async backends (TPU) emit
    start/done pairs; CPU emits the plain op — count whichever form the
    backend used, not both halves of a pair."""
    import jax.numpy as jnp
    lowered = trainer._step.lower(trainer.state, jnp.asarray(x), jnp.asarray(y))
    text = lowered.compile().as_text()

    def count(op: str) -> int:
        starts = text.count(f"{op}-start(")
        # sync form: " all-reduce(" follows the (possibly tuple) result
        # type; operand references look like "(%all-reduce.2)" and don't
        # match
        return starts if starts else text.count(f" {op}(")

    return {"collective_permute_ops": count("collective-permute"),
            "all_reduce_ops": count("all-reduce")}


def bench_config(model: str, S: int, mbsz: int, Ms, steps: int) -> dict:
    import jax
    import numpy as np

    from split_learning_tpu.models import get_plan
    from split_learning_tpu.parallel.mesh import make_mesh
    from split_learning_tpu.parallel.pipeline import PipelinedTrainer
    from split_learning_tpu.utils import Config

    plan = get_plan(model=model, mode="split")
    assert plan.num_stages == S, (plan.num_stages, S)
    mesh = make_mesh(num_clients=1, num_stages=S)
    shape = (28, 28, 1) if model == "split_cnn" else (32, 32, 3)

    rs = np.random.RandomState(0)
    out = {"model": model, "stages": S, "mb_size": mbsz, "sweep": []}
    for M in Ms:
        batch = M * mbsz
        x = rs.randn(batch, *shape).astype(np.float32)
        yb = rs.randint(0, 10, (batch,)).astype(np.int64)
        cfg = Config(mode="split", batch_size=batch, microbatches=M)
        trainer = PipelinedTrainer(plan, cfg, jax.random.PRNGKey(0), x, mesh,
                                   microbatches=M)
        trainer.train_step(x, yb)  # compile + warm
        t0 = time.perf_counter()
        loss = 0.0
        for _ in range(steps):
            loss = trainer.train_step(x, yb)  # float() inside = sync
        dt = time.perf_counter() - t0
        T = M + S - 1
        rec = {
            "microbatches_M": M, "ticks_T": T,
            "bubble_fraction": (S - 1) / T,
            "gpipe_efficiency": M / T,
            "step_ms": dt / steps * 1e3,
            "microbatches_per_sec": steps * M / dt,
            "loss": loss,
        }
        if M == Ms[0]:
            rec["hlo"] = hlo_counts(trainer, x, yb)
            out["hop_stats"] = hop_stats(trainer)
        out["sweep"].append(rec)
        print(f"[pipeline] {model} S={S} M={M}: {rec['step_ms']:.1f} ms/step, "
              f"{rec['microbatches_per_sec']:.1f} mb/s "
              f"(GPipe efficiency {M}/{T}={M / T:.2f})", file=sys.stderr)

    # normalized scaling vs the analytic bubble: mb/s relative to M=max,
    # predicted ratio = eff(M)/eff(M_max)
    base = out["sweep"][-1]
    for rec in out["sweep"]:
        rec["rel_throughput_measured"] = (
            rec["microbatches_per_sec"] / base["microbatches_per_sec"])
        rec["rel_throughput_predicted_by_bubble"] = (
            rec["gpipe_efficiency"] / base["gpipe_efficiency"])
    return out


def main() -> None:
    import jax
    n_dev = len(jax.devices())
    results = {
        "note": ("bubble sweep measured on a virtual CPU mesh "
                 f"({n_dev} host-platform devices): scheduling-relative — "
                 "ranks serialize on one host, so throughput tracks the "
                 "GPipe schedule's useful-work fraction M/(M+S-1), not "
                 "real ICI/stage-overlap wall time"),
        "configs": [
            bench_config("split_cnn", S=2, mbsz=64, Ms=[1, 2, 4, 8], steps=5),
            bench_config("resnet18_4stage", S=4, mbsz=4, Ms=[1, 2, 4, 8],
                         steps=3),
        ],
    }
    out_path = os.path.join(REPO, "artifacts", "pipeline_measurements.json")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(results, f, indent=2)
    print(f"[pipeline] wrote {out_path}", file=sys.stderr)


if __name__ == "__main__":
    from split_learning_tpu.utils import reexec_pinned_cpu
    reexec_pinned_cpu()
    # after the pin (jax is not imported until main): the virtual mesh
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    main()
