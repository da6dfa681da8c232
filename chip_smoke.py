#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the trainer still starts on the chip.

Drives the system's main path once, in THIS process, through the entry
point a user calls (``split_learning_tpu.launch.run.main``), over the
three execution paths the repo has — one fused XLA program, the
two-party ``ServerRuntime`` split, the K-stage ``StageRuntime`` chain —
at the full width of the models the builders last ran (depth as the plan
builders default it, weights random from the seed, synthetic data from
the seed). Nothing under ``artifacts/`` is read.

  python chip_smoke.py            # every leg this host has chips for

| leg | path | proves |
|---|---|---|
| K | ops.flash_attention vs ops.ring_attention.full_attention | the Mosaic-compiled flash forward + one-pass backward agree with the dense f32 reference at leg B's kernel shape |
| A | fused, split CNN b64 f32 | the reference workload, one XLA program |
| B | fused, transformer_lm d1024 T1024 b64 bf16, --attn flash | the Pallas kernels compile and train inside the full step |
| C | local (two-party), B's model and shape, dense attention | ``ServerRuntime`` in-process; its loss series must agree with B's |
| D | device chain, resnet18_4stage b256 bf16, 1F1B, 4 microbatches | ``StageRuntime`` x3 + ``PipelineRunner`` over the device wire |
| E | pipeline (SPMD), D's model and shape (>= 4 chips only) | ``PipelinedTrainer``, 1 data x 4 pipe, ppermute hops |

It refuses to start unless ``jax.devices()[0].platform == "tpu"``, fails
(non-zero exit) if any loss is non-finite, if the Pallas kernels would
be interpreted, if the flash one-pass preflight demotes to the two-kernel
split, if a leg builds a program after its first step, trips the
dispatch watchdog (steady-state recompile, unsanctioned D2H), or raises;
on a host with >= 4 chips leg D must also put hub and stages 1-3 on four
distinct devices. One process holds the chip: no subprocess is started.

The last line of stdout is one JSON object:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import os
import sys
import tempfile
import time
import warnings
from typing import Dict, List, Optional, Sequence

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_PREFLIGHT_WARNING = "flash one-pass backward preflight failed"


@dataclasses.dataclass(frozen=True)
class Leg:
    name: str
    what: str
    argv: Sequence[str]          # after ``train --mode split``, sans --steps
    steps: int
    chain_stages: int = 0        # > 0: an MPMD chain leg with that many stages
    min_devices: int = 1
    # the loss series must agree with this earlier leg's (same model,
    # shape, seed and data through a different execution path)
    agrees_with: Optional[str] = None
    loss_must_fall: bool = False


_LM = ["--model", "transformer_lm", "--dataset", "lm", "--d-model", "1024",
       "--num-heads", "8", "--seq-len", "1024", "--batch-size", "64",
       "--dtype", "bfloat16"]
_RESNET = ["--model", "resnet18_4stage", "--microbatches", "4",
           "--dataset", "synthetic", "--batch-size", "256",
           "--dtype", "bfloat16"]

LEGS = (
    Leg("A", "fused split CNN b64 f32 (reference workload)",
        ["--model", "split_cnn", "--transport", "fused", "--dataset",
         "synthetic", "--batch-size", "64"],
        steps=20, loss_must_fall=True),
    Leg("B", "fused transformer_lm d1024 T1024 b64 bf16, Pallas flash",
        _LM + ["--attn", "flash", "--transport", "fused"], steps=5),
    Leg("C", "two-party local transformer_lm (B's model and shape, dense)",
        _LM + ["--transport", "local"], steps=5, agrees_with="B"),
    Leg("D", "device chain resnet18_4stage b256 bf16, 1F1B x4",
        _RESNET + ["--stages", "4", "--transport", "device",
                   "--schedule", "1f1b"], steps=5, chain_stages=4),
    Leg("E", "SPMD pipeline resnet18_4stage b256 bf16, 1 data x 4 pipe",
        _RESNET + ["--transport", "pipeline", "--num-clients", "1"],
        steps=5, min_devices=4),
)

# B and C run the same bf16 model on the same batches from the same
# seed; flash vs dense attention and one program vs two parties reorder
# bf16 roundings, nothing more
_AGREE_NATS = 0.05


def _say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


@contextlib.contextmanager
def _demotion_is_error():
    """A kernel that "gives way" must be visible: at the smoke's shapes a
    one-pass backward demoted to the two-kernel split is a failure."""
    with warnings.catch_warnings():
        warnings.filterwarnings("error", message=_PREFLIGHT_WARNING,
                                category=RuntimeWarning)
        yield


def _run_leg(leg: Leg, data_dir: str, compile_log: List, n_devices: int,
             earlier: Dict[str, dict]) -> dict:
    import numpy as np

    from split_learning_tpu.launch.run import main as cli_main
    from split_learning_tpu.obs import dispatch_debug

    _say(f"leg {leg.name}: {leg.what}")
    argv = ["train", "--mode", "split", *leg.argv, "--steps", str(leg.steps),
            "--tracking", "jsonl", "--data-dir", data_dir]
    _say("  argv: " + " ".join(argv))
    tracker = dispatch_debug.tracker()
    n_violations0 = len(tracker.violations)
    d2h0 = tracker.unexpected_d2h
    n_compiles0 = len(compile_log)
    t0 = time.time()
    rc = cli_main(argv)
    if rc != 0:
        raise RuntimeError(f"leg {leg.name}: CLI returned {rc}")

    with open(os.path.join(data_dir, "metrics",
                           "Split_Learning_Sim.jsonl")) as f:
        tracked = list(map(json.loads, f))
    recs = [r for r in tracked if r.get("key") == "loss"]
    losses = [r["value"] for r in recs]
    stamps = [r["ts"] for r in recs]
    _say("  losses: " + " ".join(f"{v:.4f}" for v in losses))
    if len(losses) != leg.steps:
        raise RuntimeError(
            f"leg {leg.name}: {len(losses)} losses logged, want {leg.steps}")
    if not np.all(np.isfinite(losses)):
        raise RuntimeError(f"leg {leg.name}: non-finite loss in {losses}")
    if leg.loss_must_fall and not losses[-1] < losses[0]:
        raise RuntimeError(
            f"leg {leg.name}: loss did not fall "
            f"({losses[0]:.4f} -> {losses[-1]:.4f})")

    built = compile_log[n_compiles0:]
    late = sum(t > stamps[0] for t, _ in built)
    out = {
        "leg": leg.name,
        "losses": losses,
        # wall time from CLI entry to the first logged loss: data
        # synthesis + init + every program's compile + step 0
        "to_first_step_s": stamps[0] - t0,
        # XLA backend time for the programs this leg built (a warm
        # persistent cache turns it into retrieval time)
        "compile_s": sum(s for _, s in built),
        "programs_built": len(built),
        "steady_step_s": (float(np.median(np.diff(stamps)))
                          if len(stamps) > 1 else float("nan")),
        "programs_built_after_first_step": late,
    }
    violations = tracker.violations[n_violations0:]
    out["watchdog"] = {
        "steady_state_recompiles": sum(
            v["kind"] == "steady-state-recompile" for v in violations),
        "unexpected_d2h": tracker.unexpected_d2h - d2h0,
    }
    _say(f"  first step after {out['to_first_step_s']:.1f} s "
         f"(backend compile {out['compile_s']:.1f} s over "
         f"{out['programs_built']} programs); steady step "
         f"{out['steady_step_s'] * 1e3:.1f} ms (host clock between logged "
         "losses, median)")
    _say(f"  programs built after the first step: {late}; "
         f"watchdog: {out['watchdog']}")
    if late:
        raise RuntimeError(
            f"leg {leg.name}: {late} program(s) built after the first "
            "step (a retrace in steady state)")
    if violations:
        raise RuntimeError(
            f"leg {leg.name}: dispatch watchdog violations: "
            f"{[v['message'] for v in violations]}")

    if leg.chain_stages:
        # the CLI logs the chain's stage -> device-ids map as a params
        # record (JSON object keys arrive as strings)
        placed = {int(stage): ids for r in tracked
                  for stage, ids in r.get("params", {}).get(
                      "stage_devices", {}).items()}
        out["stage_devices"] = placed
        _say(f"  stage -> devices: {placed}")
        if sorted(placed) != list(range(leg.chain_stages)):
            raise RuntimeError(
                f"leg {leg.name}: stage -> device map {placed} does not "
                f"cover stages 0..{leg.chain_stages - 1}")
        if n_devices >= leg.chain_stages:
            ids = [tuple(placed[i]) for i in range(leg.chain_stages)]
            if len(set(ids)) != leg.chain_stages or any(
                    len(i) != 1 for i in ids):
                raise RuntimeError(
                    f"leg {leg.name}: {n_devices} devices but stages share "
                    f"one: {placed}")

    if leg.agrees_with in earlier:
        ref = earlier[leg.agrees_with]["losses"]
        gap = float(np.max(np.abs(np.asarray(losses) - np.asarray(ref))))
        out["max_abs_diff_vs_" + leg.agrees_with] = gap
        _say(f"  max |loss - leg {leg.agrees_with}'s| = {gap:.4f} nats "
             f"(bound {_AGREE_NATS})")
        if gap > _AGREE_NATS:
            raise RuntimeError(
                f"leg {leg.name}: loss series {losses} departs from leg "
                f"{leg.agrees_with}'s {ref} by {gap:.4f} nats")
    return out


def run_legs(legs: Sequence[Leg]) -> List[dict]:
    """Run each leg once through the CLI entry point in this process and
    check what came out. Raises on the first failing leg — nothing here
    lets a failure end in exit code 0. Platform-agnostic on purpose
    (tests drive it at tiny sizes on CPU); the refusal to run off-chip
    lives in :func:`main`."""
    import jax

    from split_learning_tpu.obs import dispatch_debug

    n_devices = len(jax.devices())
    compile_log: List = []   # (wall clock, seconds) per program built

    def on_event(event: str, secs: float, **_kw) -> None:
        if event == _BACKEND_COMPILE_EVENT:
            compile_log.append((time.time(), secs))

    jax.monitoring.register_event_duration_secs_listener(on_event)
    # the watchdog arms the D2H transfer guard the CPU backend cannot
    # enforce and counts steady-state recompiles by step scope
    watchdog_was_on = dispatch_debug.enabled()
    dispatch_debug.force(True)
    results: Dict[str, dict] = {}
    try:
        with _demotion_is_error(), \
                tempfile.TemporaryDirectory(prefix="slt_smoke_") as tmp:
            for leg in legs:
                if n_devices < leg.min_devices:
                    _say(f"leg {leg.name}: skipped ({n_devices} device(s), "
                         f"needs {leg.min_devices})")
                    continue
                results[leg.name] = _run_leg(
                    leg, os.path.join(tmp, leg.name), compile_log,
                    n_devices, results)
                gc.collect()   # drop the leg's device buffers before the next
    finally:
        dispatch_debug.force(False)
        if not watchdog_was_on:
            dispatch_debug.uninstall()
        jax.monitoring.unregister_event_duration_listener(on_event)
    return list(results.values())


def check_flash_kernel(t: int = 1024, d: int = 128) -> dict:
    """Leg K: the flash forward and one-pass backward, as this backend
    builds them, against the repo's dense reference in float32 at
    "highest" matmul precision — bf16 storage, leg B's kernel shape
    (block 1024, head_dim 128) at a small batch*heads."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from split_learning_tpu.ops.flash_attention import flash_attention
    from split_learning_tpu.ops.ring_attention import full_attention

    _say(f"leg K: flash fwd + bwd vs dense reference, T={t} d={d} bf16")
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, w = (jax.random.normal(key, (2, t, 2, d), jnp.float32)
                  for key in ks)

    def probe(attn, cast):
        def f(a, b, c):
            o = attn(cast(a), cast(b), cast(c), causal=True)
            return jnp.sum(o.astype(jnp.float32) * w), o
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                          has_aux=True))

    with _demotion_is_error():
        (_, o), grads = probe(
            flash_attention, lambda x: x.astype(jnp.bfloat16))(q, k, v)
    with jax.default_matmul_precision("highest"):
        (_, o_ref), grads_ref = probe(full_attention, lambda x: x)(q, k, v)
    errs = {}
    for name, got, want in zip(("o", "dq", "dk", "dv"),
                               (o,) + tuple(grads),
                               (o_ref,) + tuple(grads_ref)):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        if not np.all(np.isfinite(got)):
            raise RuntimeError(f"leg K: non-finite {name}")
        errs[name] = float(np.linalg.norm(got - want)
                           / np.linalg.norm(want))
    _say("  relative L2 error vs reference: "
         + " ".join(f"{n}={e:.4f}" for n, e in errs.items()))
    # bf16 keeps 8 significant bits: inputs and P round at 2^-9 relative,
    # products accumulate in f32 — a wrong mask, scale or block index is
    # an O(1) error, two orders above this bound
    bad = {n: e for n, e in errs.items() if e > 0.03}
    if bad:
        raise RuntimeError(
            f"leg K: flash kernel departs from the dense reference: {bad}")
    return {"leg": "K", "rel_l2_err": errs}


def main() -> int:
    from split_learning_tpu.utils import configure_compile_cache
    cache_dir = configure_compile_cache()
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu":
        print(f"chip_smoke: no accelerator — jax.devices()[0].platform is "
              f"{dev.platform!r}, not 'tpu'; refusing to run",
              file=sys.stderr)
        return 1
    from split_learning_tpu.ops.common import use_interpret
    if use_interpret():
        print("chip_smoke: ops.common.use_interpret() is true on a TPU "
              "backend — the Pallas kernels would be interpreted",
              file=sys.stderr)
        return 1
    _say(f"platform: {device['platform']}  device_kind: {device['kind']}  "
         f"devices: {device['count']}  jax {jax.__version__}")
    _say(f"compile cache: {cache_dir}")

    t0 = time.time()
    results = [check_flash_kernel()] + run_legs(LEGS)
    _say(f"summary: {json.dumps(results)}")
    _say(f"all {len(results)} legs passed in {time.time() - t0:.0f} s; "
         f"compile {sum(r.get('compile_s', 0.0) for r in results):.1f} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
