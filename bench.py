#!/usr/bin/env python
"""Headline benchmark: MNIST split-CNN training throughput (the metric
BASELINE.json names).

Prints ONE JSON line:
  {"metric": "mnist_split_cnn_steps_per_sec", "value": N,
   "unit": "steps/sec", "vs_baseline": R}

- baseline: the reference architecture — per-step HTTP round trip of the
  5.28 MiB cut-layer tensor between a client and a server process path
  (loopback, CPU, safe codec — strictly *generous* to the reference, which
  also paid pickle + k8s networking; ``src/client_part.py:110-138``).
- value: the fused TPU-native path — the whole split step (both stages,
  loss, both SGD updates, in-XLA cut-layer exchange) as one jitted program
  on the default backend (TPU when available).
- vs_baseline = value / baseline_steps_per_sec.

Detail (stderr) additionally reports FLOPs/MFU accounting (VERDICT round 1
weak #2) and, on TPU, a ResNet-18/CIFAR-10 leg (BASELINE.json configs[3]).

Run with --quick for a fast smoke (fewer timed steps).
Internal: --role {baseline,fused} runs one measurement subprocess; the
fused role is parameterized by SLT_BENCH_DTYPE / SLT_BENCH_MODEL /
SLT_BENCH_BATCH env vars. A chip belongs to one process at a time, so
the orchestrating parent never touches JAX and runs its role
subprocesses one after another; they share the persistent compile cache
(utils/backend.configure_compile_cache). A device role (DEVICE_ROLES)
that finds no chip exits non-zero before it measures anything
(_require_chip), and the run fails with it: nothing here re-runs it on
CPU or prints a number this run did not measure. The CPU is used for a
device role only when the caller asks for it by name
(JAX_PLATFORMS=cpu, as the tests do); the record then says so.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

BATCH = 64  # reference batch size (src/client_part.py:98)

# Subprocess env for the legs that are CPU measurements by design (the
# HTTP-loopback baseline, the virtual-mesh and synthetic-wire side legs):
# they must never take the chip from the device legs.
CPU_ENV = {"JAX_PLATFORMS": "cpu"}

# Roles that measure the chip; every other role is a CPU measurement by
# design and the orchestrator runs it under CPU_ENV.
DEVICE_ROLES = ("fused", "decode", "flash_micro")


def device_role_refusal(platform: str, asked: str | None) -> str | None:
    """Why a device role must not run on ``platform``, or None if it may.
    JAX resolves to the CPU without an error when the TPU runtime does
    not come up; a number taken there must never fill a device slot. The
    one way onto the CPU is the caller naming it (``asked``, the value of
    JAX_PLATFORMS, is exactly "cpu")."""
    if platform == "tpu" or (asked or "").strip().lower() == "cpu":
        return None
    return (f"device role found platform {platform!r}, not 'tpu', and "
            f"JAX_PLATFORMS={asked!r} did not ask for it: refusing to "
            "measure the wrong device")


def _require_chip(role: str) -> None:
    import jax
    refusal = device_role_refusal(jax.devices()[0].platform,
                                  os.environ.get("JAX_PLATFORMS"))
    if refusal:
        raise SystemExit(f"[bench] --role {role}: {refusal}")


SEQ_LEN = 256  # transformer bench context length (SLT_BENCH_SEQ overrides)


def _seq_len() -> int:
    return int(os.environ.get("SLT_BENCH_SEQ", str(SEQ_LEN)))


def _bench_d_model() -> int:
    """Attention-family leg width — transformer AND ViT —
    (SLT_BENCH_DMODEL, default 256). One
    parse site: the plan builder and the leg record must never read
    different values. Multiples of 128 only — heads scale with width
    so head_dim stays exactly the 128-lane tile, the shape every
    recorded flash_block was resolved for."""
    d = int(os.environ.get("SLT_BENCH_DMODEL", "256"))
    if d % 128:
        raise SystemExit(
            f"SLT_BENCH_DMODEL={d} is not a multiple of 128: heads "
            "scale with width to keep head_dim at the 128-lane tile, "
            "and a non-multiple would silently benchmark a different "
            "kernel shape than the record describes")
    return d


def transformer_trunk_kwargs(mode: str, dtype) -> dict:
    """The bench transformer trunk's plan kwargs, shared with every
    consumer that claims to build "the same trunk as the bench legs"
    (scripts/profile_fused_tpu.py): width from the one
    :func:`_bench_d_model` parse site, heads scaled so head_dim stays
    the 128-lane tile, the same max_len floor."""
    import numpy as np
    d_model = _bench_d_model()
    return dict(mode=mode, dtype=np.dtype(dtype), d_model=d_model,
                num_heads=d_model // 128,
                max_len=max(2048, _seq_len()))


RING_FLASH_BLOCK_NOTE = (
    "ring attention invokes the flash kernel per shard at t_local (and "
    "per-shard bh), not at the global T this leg is labeled with; the "
    "bench fused role builds no seq mesh, so there is no t_local to "
    "resolve a block at — recorded as None rather than a full-T edge "
    "the kernel never compiled (ADVICE round 5)")


def _active_flash_block(model: str, attn: str):
    """The block edge a flash-kernel leg actually ran with (env
    override, else _resolve_block's choice for this leg's shape) —
    None for non-flash legs, and None for ring_flash legs: the ring
    form runs the kernel per shard at t_local, so a block resolved at
    global T would mislabel the record AND _resolve_block's one-pass
    preflight would compile a full-T shape the leg never runs (the
    note rides the leg as ``flash_block_note``). Frozen into the leg
    record so later assemblers can attribute the number to the right
    kernel shape even after the picker's defaults change.
    _resolve_block, not _pick_block: the entry points can cap the edge
    to the proven split-form maximum when the one-pass backward is
    refused, and the record must carry the edge that actually
    compiled."""
    if attn != "flash":
        return None
    if model == "transformer":
        t = _seq_len()
    elif model == "vit":
        t = 64   # 32x32 / patch 4 patch tokens (see _data)
    else:
        return None
    import numpy as np
    from split_learning_tpu.ops.flash_attention import _resolve_block
    # both bench attention trunks run head_dim 128 (d_model/heads —
    # the MXU-filling shape; see the model kwargs in _fused_step_leg)
    dtype = np.dtype(os.environ.get("SLT_BENCH_DTYPE", "float32"))
    block, _ = _resolve_block(t, 128, dtype)
    return int(block)


def _data(n_steps: int, model: str):
    import numpy as np
    rs = np.random.RandomState(0)
    if model in ("resnet18", "vit"):
        # CIFAR-shaped images; for vit: 32x32 / patch 4 -> 64 tokens
        x = rs.randn(n_steps, BATCH, 32, 32, 3).astype(np.float32)
    elif model == "transformer":
        x = rs.randint(0, 256, (n_steps, BATCH, _seq_len())).astype(np.int32)
    else:
        x = rs.randn(n_steps, BATCH, 28, 28, 1).astype(np.float32)
    y = rs.randint(0, 10, (n_steps, BATCH)).astype(np.int64)
    return x, y


def _traced_phase_breakdown(run_traced_steps, export_path: str | None = None
                            ) -> dict:
    """Per-leg phase breakdown (ISSUE: every bench leg records where its
    step time goes). Enables the obs tracer, runs a few extra steps via
    the callback, and returns the per-phase summary + the north-star
    transport fraction. Always AFTER the timed window — the published
    number keeps the zero-overhead-off hot path — and safe to enable
    globally because every role owns a fresh subprocess."""
    from split_learning_tpu import obs
    tr = obs.enable()
    try:
        run_traced_steps()
    finally:
        obs.disable()
    out = {
        "phases": tr.phase_summary(),
        "transport_fraction": tr.fraction("transport"),
        "note": ("measured on a few post-window traced steps, not the "
                 "timed window (tracing stays off while timing)"),
    }
    if export_path:
        out["trace_file"] = tr.export_chrome(export_path)
    return out


def measure_baseline(quick: bool) -> dict:
    """Reference-architecture path: HTTP loopback split step on CPU."""
    import jax

    from split_learning_tpu.models import get_plan
    from split_learning_tpu.runtime import ServerRuntime, SplitClientTrainer
    from split_learning_tpu.transport.http import HttpTransport, SplitHTTPServer
    from split_learning_tpu.utils import Config

    warmup, steps = (2, 10) if quick else (5, 40)
    cfg = Config(mode="split", batch_size=BATCH)
    plan = get_plan(mode="split")
    x, y = _data(warmup + steps, "split_cnn")
    runtime = ServerRuntime(plan, cfg, jax.random.PRNGKey(0), x[0])
    server = SplitHTTPServer(runtime).start()
    transport = HttpTransport(server.url)
    client = SplitClientTrainer(plan, cfg, jax.random.PRNGKey(0), transport)
    try:
        for i in range(warmup):
            client.train_step(x[i], y[i], i)
        t0 = time.perf_counter()
        for i in range(warmup, warmup + steps):
            client.train_step(x[i], y[i], i)
        dt = time.perf_counter() - t0
        phases = _traced_phase_breakdown(lambda: [
            client.train_step(x[j % (warmup + steps)], y[j % (warmup + steps)],
                              warmup + steps + j) for j in range(3)])
    finally:
        transport.close()
        server.stop()
    return {
        "steps_per_sec": steps / dt,
        "roundtrip_p50_ms": transport.stats.percentile(50) * 1e3,
        "platform": "cpu+http-loopback",
        "phases": phases,
    }


def grow_window(window, n_chunks: int, floor_s: float = 1.0,
                cap: int = 4096) -> int:
    """Double ``n_chunks`` until ``window(n_chunks)`` takes at least
    ``floor_s`` seconds. Every timed window pays a fixed close-out cost
    (the final loss transfer to the host), and a window comparable to
    that cost fails the 2x
    linearity cross-check no matter how fast the chip is — the
    2026-07-31 quick CNN leg timed 0.07 s windows and was (correctly)
    gated out at linearity 1.37. Re-times rather than extrapolates, so
    the published number is always a directly measured window."""
    while window(n_chunks)[0] < floor_s and n_chunks < cap:
        n_chunks = min(n_chunks * 2, cap)
    return n_chunks


def validate_leg(leg: dict) -> tuple[bool, str | None]:
    """The publication gate README.md promises: a leg is INVALID (its
    number must never be published) unless
      (a) steps/sec x FLOPs/step <= chip peak (util <= 1.0) when the
          chip's peak is known;
      (b) achieved model TFLOP/s stays under a conservative 5 TFLOP/s
          bound on CPU, which has no published peak (a TPU whose
          device_kind is not in the peaks table never gets here:
          utils/flops.device_peak_flops raises);
      (c) the 2x-steps window took ~2x the time of the 1x window
          (linearity in [1.5, 2.6]) — a dispatch-only timer fails this
          because its 'window' is a fixed cost independent of work.
    Round 1 and round 2 both published dispatch-latency artifacts that
    violate (a) by 40x and 60x; this gate is why round 3 cannot."""
    util = leg.get("util_vs_bf16_peak")
    if util is not None:
        if util > 1.0:
            return False, (f"util_vs_bf16_peak={util:.3f} > 1.0: "
                           "steps/sec x FLOPs/step exceeds chip peak")
    elif leg.get("model_tflops_per_sec", 0.0) > 5.0:
        return False, (f"{leg['model_tflops_per_sec']:.1f} model TFLOP/s "
                       "with no known chip peak exceeds the conservative "
                       "5 TFLOP/s bound")
    lin = leg.get("linearity_2x")
    if lin is not None and not (1.5 <= lin <= 2.6):
        return False, (f"linearity_2x={lin:.2f} outside [1.5, 2.6]: the "
                       "timed window does not scale with work, so it "
                       "measured dispatch, not execution")
    return True, None


def measure_fused(quick: bool) -> dict:
    """TPU-native path: the whole split step is one XLA program, and steps
    are batched under lax.scan (FusedSplitTrainer.train_epoch) so host
    dispatch amortizes — the two structural wins over the reference's
    per-step pickle/HTTP round trip.

    Timing discipline (VERDICT round 2, weak #1 — this is the fix): every
    timed window is **data-dependent**: it ends with a host transfer of the
    final per-step loss, which the device cannot satisfy until the whole
    chained (donated-state) run has executed — rounds 1 and 2 published
    40x/60x-over-peak dispatch latencies as throughput from windows that
    were not. The window is a full reference workload (2,814 steps = the
    reference's 3 MNIST epochs, src/client_part.py:107) timed end-to-end,
    cross-checked by a 2x-length window (linearity), and gated on
    FLOPs/step x steps/sec <= chip peak before publication."""
    import jax
    import numpy as np

    from split_learning_tpu.models import get_plan
    from split_learning_tpu.runtime.fused import FusedSplitTrainer
    from split_learning_tpu.utils import Config
    from split_learning_tpu.utils.flops import device_peak_flops, mfu

    model = os.environ.get("SLT_BENCH_MODEL", "split_cnn")
    dtype = os.environ.get("SLT_BENCH_DTYPE", "float32")
    batch = int(os.environ.get("SLT_BENCH_BATCH", str(BATCH)))
    mode = os.environ.get("SLT_BENCH_MODE", "split")  # "u_split" = config 5
    kernels = os.environ.get("SLT_BENCH_KERNELS", "xla")  # "pallas" = ops/
    attn = os.environ.get("SLT_BENCH_ATTN", "full")  # transformer only

    # full run = the reference's complete 3-epoch workload (2,814 steps)
    chunk, n_chunks = (100, 2) if quick else (469, 6)
    if model == "resnet18":
        # ~0.95 TFLOP/step at b256: far fewer steps make a stable window,
        # and the scan input buffer must fit HBM
        chunk, n_chunks = (4, 2) if quick else (15, 4)
    elif model == "transformer":
        chunk, n_chunks = (20, 2) if quick else (100, 4)
    elif model == "vit":
        chunk, n_chunks = (50, 2) if quick else (200, 4)
    x, y = _data(chunk, model)
    if batch != BATCH:
        reps = (batch + BATCH - 1) // BATCH
        tile = (1, reps) + (1,) * (x.ndim - 2)
        x = np.tile(x, tile)[:, :batch]
        y = np.tile(y, (1, reps))[:, :batch]

    import jax.numpy as jnp
    xd, yd = jnp.asarray(x), jnp.asarray(y)

    cfg = Config(mode=mode, batch_size=batch, dtype=dtype, kernels=kernels,
                 attn=attn)
    if model == "transformer":
        # TPU-shaped dimensions: head_dim = d_model/heads = 128 fills the
        # 128-lane tile exactly — the factory default (64/4 -> D=16) pads
        # every attention matmul's lane dim 8x on both the dense and
        # flash paths, which benchmarks the padding, not the math.
        # SLT_BENCH_DMODEL scales width; heads scale with it so
        # head_dim stays 128 (d512 -> 4 heads etc.), keeping every
        # leg's attention matmuls MXU-shaped while varying bh. The
        # 128-divisibility is load-bearing (the recorded flash_block
        # is resolved for head_dim 128), so a width that breaks it is
        # refused, not silently measured wrong.
        from split_learning_tpu.models.transformer import transformer_plan
        tkw = transformer_trunk_kwargs(mode, dtype)
        plan = transformer_plan(attn=attn, **tkw)
    elif model == "vit":
        # same TPU-shaped trunk as the transformer leg (head_dim 128):
        # 32x32/patch-4 images -> 64 patch tokens; width from the same
        # SLT_BENCH_DMODEL knob (heads scale so head_dim stays 128)
        from split_learning_tpu.models.vit import vit_plan
        vd = _bench_d_model()
        vkw = dict(mode=mode, dtype=np.dtype(dtype), d_model=vd,
                   num_heads=vd // 128)
        plan = vit_plan(attn=attn, **vkw)
    else:
        plan = get_plan(model=model, mode=mode, dtype=dtype)
    trainer = FusedSplitTrainer(plan, cfg, jax.random.PRNGKey(0), x[0])
    device = trainer.state.step.devices().pop()
    platform = device.platform

    if model in ("transformer", "vit") and attn != "full":
        # the flash kernels hide their matmuls inside pallas_call, which
        # the jaxpr FLOPs counter cannot see; count a dense-attention
        # step of identical shapes instead. Trace-only on the existing
        # params — building a second trainer would run plan.init
        # *eagerly*, and the eager dense forward materializes the
        # [B,H,T,T] scores (17 GB at T=16k: an instant OOM)
        from split_learning_tpu.core.losses import cross_entropy as _ce
        from split_learning_tpu.utils.flops import jaxpr_matmul_flops
        if model == "vit":
            dense_plan = vit_plan(attn="full", **vkw)
        else:
            dense_plan = transformer_plan(attn="full", **tkw)

        def _dense_step(params, xb, yb):
            return jax.value_and_grad(
                lambda p, a, b: _ce(dense_plan.apply(p, a), b))(
                params, xb, yb)

        flops_step = jaxpr_matmul_flops(
            _dense_step, trainer.state.params, xd[0], yd[0])
    else:
        flops_step = trainer.step_flops(x[0], y[0])

    if platform == "cpu":
        # only under an explicit JAX_PLATFORMS=cpu (_require_chip). The
        # scanned epoch is a TPU idiom; XLA *CPU* executes the rolled
        # scan body far slower than eager per-step dispatch (~40x
        # measured), so a CPU run times the stepwise path
        steps = 10 if quick else 50
        xs, ys = xd[0], yd[0]

        def window(n: int) -> tuple[float, float]:
            t0 = time.perf_counter()
            for _ in range(n):
                loss = trainer.train_step_async(xs, ys)
            last = float(loss)  # host transfer: data-dependent close
            return time.perf_counter() - t0, last

        window(2)  # compile + warm
        times = sorted(window(steps)[0] for _ in range(3))
        t_med = times[1]
        t_2x, last_loss = window(2 * steps)
        step_count = steps
    else:

        def window(n: int) -> tuple[float, float]:
            """Time n chunks dispatched back-to-back, closed by a host
            transfer of the final loss series. The donated TrainState
            chains chunk k's program onto chunk k-1's, so the transfer
            cannot complete until every step has executed on-device."""
            t0 = time.perf_counter()
            for _ in range(n):
                losses = trainer.train_epoch(xd, yd)
            last = float(np.asarray(losses)[-1])
            return time.perf_counter() - t0, last

        window(1)  # compile + warm + drain
        n_chunks = grow_window(window, n_chunks)
        times = sorted(window(n_chunks)[0] for _ in range(3))
        t_med = times[1]
        t_2x, last_loss = window(2 * n_chunks)
        step_count = chunk * n_chunks

    steps_per_sec = step_count / t_med
    achieved = flops_step * steps_per_sec
    peak = device_peak_flops(device)
    leg = {
        "model": model,
        "mode": mode,
        # steps executed per device dispatch (lax.scan in train_epoch):
        # host dispatch is amortized K-fold — the residual utilization
        # gap at small batch is the on-device critical path of a tiny
        # sequential-SGD step, not host overhead
        "steps_per_dispatch": 1 if platform == "cpu" else chunk,
        "kernels": kernels,
        "attn": attn,
        "batch": batch,
        "seq_len": _seq_len() if model == "transformer" else None,
        "d_model": (_bench_d_model() if model in ("transformer", "vit")
                    else None),
        # the block edge the flash kernel actually ran with, frozen at
        # measurement time: assemblers must never re-derive it from a
        # later _pick_block (whose constant is exactly what sweep
        # results get used to change)
        "flash_block": _active_flash_block(model, attn),
        **({"flash_block_note": RING_FLASH_BLOCK_NOTE}
           if attn == "ring_flash" else {}),
        "dtype": dtype,
        "steps_per_sec": steps_per_sec,
        "step_ms": t_med / step_count * 1e3,
        "timed_steps": step_count,
        "window_s": {"best": times[0], "median": t_med, "worst": times[-1]},
        "linearity_2x": t_2x / t_med,
        "platform": platform,
        "device_kind": getattr(device, "device_kind", "") or "",
        "loss": last_loss,
        "flops_per_step": flops_step,
        "model_tflops_per_sec": achieved / 1e12,
        # denominator is always the chip's public bf16 peak; for float32
        # runs that is an upper bound on utilization (f32 matmul peak on
        # TPU is below the bf16 peak), so the <=1.0 gate stays valid and
        # the key says what was divided by what
        "util_vs_bf16_peak": mfu(achieved, peak),
        "util_note": ("true MFU (bf16 run / bf16 peak)"
                      if dtype == "bfloat16" else
                      "f32 run over bf16 peak: utilization upper bound"),
        "steps_per_sec_ceiling_at_peak": (
            peak / flops_step if peak else None),
        # one XLA program, no transport boundary: the obs span taxonomy
        # (client_fwd / wire / queue_wait / ...) has nothing to attach to
        "phases": None,
        "phases_note": ("fused step is a single jitted program; no "
                        "client/transport/server phases exist to trace"),
    }
    leg["valid"], leg["invalid_reason"] = validate_leg(leg)
    return leg


def measure_dp(quick: bool) -> dict:
    """BASELINE.json configs[2]: multi-client data parallelism. The global
    batch shards over the mesh's ``data`` axis; gradient psum over ICI
    replaces the reference's per-epoch weight shipping.

    Run on the virtual host-platform mesh (no multi-chip hardware in this
    image), so steps/sec is **scheduling-relative**: N virtual devices
    share one host core, which measures the collective schedule's
    overhead, not a speedup. The loss-parity column is exact math, not
    relative: DP-N on the same global batch must reproduce the 1-device
    loss series (psum-mean of shard gradients ≡ full-batch gradient)."""
    import jax
    import numpy as np

    from split_learning_tpu.models import get_plan
    from split_learning_tpu.parallel.mesh import make_mesh
    from split_learning_tpu.runtime.fused import FusedSplitTrainer
    from split_learning_tpu.utils import Config

    n_clients = int(os.environ.get("SLT_BENCH_DP_CLIENTS", "4"))
    global_batch = 256
    steps = 5 if quick else 20
    rs = np.random.RandomState(0)
    x = rs.randn(steps, global_batch, 28, 28, 1).astype(np.float32)
    y = rs.randint(0, 10, (steps, global_batch)).astype(np.int64)
    cfg = Config(mode="split", batch_size=global_batch)

    def run(n: int):
        mesh = make_mesh(num_clients=n) if n > 1 else None
        trainer = FusedSplitTrainer(
            get_plan(mode="split"), cfg, jax.random.PRNGKey(0), x[0],
            mesh=mesh)
        trainer.train_step(x[0], y[0])  # compile
        losses = []
        t0 = time.perf_counter()
        for i in range(steps):
            losses.append(trainer.train_step(x[i], y[i]))  # float() = sync
        return time.perf_counter() - t0, losses

    dt_1, losses_1 = run(1)
    dt_n, losses_n = run(n_clients)
    diff = float(np.max(np.abs(np.asarray(losses_1) - np.asarray(losses_n))))
    # self-policing like the fused legs: the invariant this leg exists to
    # prove is exact-math DP (psum-mean of shard grads ≡ full-batch grad);
    # a few f32 ULPs of reassociation is the honest tolerance
    parity_tol = 1e-4
    return {
        "leg": "multi_client_dp",
        "clients": n_clients,
        "global_batch": global_batch,
        "platform": jax.devices()[0].platform,
        "scheduling_relative": True,
        "steps_per_sec_1_client": steps / dt_1,
        f"steps_per_sec_{n_clients}_clients": steps / dt_n,
        "loss_max_abs_diff_vs_1_client": diff,
        "phases": None,
        "phases_note": ("fused DP step is a single jitted program; no "
                        "client/transport/server phases exist to trace"),
        "valid": diff <= parity_tol,
        "invalid_reason": None if diff <= parity_tol else (
            f"DP-{n_clients} loss series diverges from 1-client by {diff} "
            f"(> {parity_tol}): gradient psum is not reproducing full-batch "
            "math"),
    }


def measure_wire(quick: bool) -> dict:
    """The int8 wire-compression claim (VERDICT round 2, weak #5): HTTP
    cut-layer round-trip p50 with ``compress="int8"`` vs ``"none"`` on the
    same loopback server. The 4x byte reduction is implemented in C++ and
    Pallas (native/slt_codec.cc, ops/quantize.py); this measures whether
    it buys wall-clock on the wire path."""
    import jax
    import numpy as np

    from split_learning_tpu.models import get_plan
    from split_learning_tpu.runtime import ServerRuntime, SplitClientTrainer
    from split_learning_tpu.transport.http import HttpTransport, SplitHTTPServer
    from split_learning_tpu.utils import Config

    steps = 5 if quick else 25
    cfg = Config(mode="split", batch_size=BATCH)
    plan = get_plan(mode="split")
    x, y = _data(steps + 2, "split_cnn")
    out = {"leg": "http_wire_compression", "platform": "cpu+http-loopback",
           "valid": True, "invalid_reason": None}
    for compress in ("none", "int8"):
        runtime = ServerRuntime(plan, cfg, jax.random.PRNGKey(0), x[0])
        server = SplitHTTPServer(runtime).start()
        transport = HttpTransport(server.url, compress=compress)
        client = SplitClientTrainer(plan, cfg, jax.random.PRNGKey(0),
                                    transport)
        try:
            for i in range(2):
                client.train_step(x[i], y[i], i)
            from split_learning_tpu.transport.base import TransportStats
            transport.stats = TransportStats()  # drop warmup from the window
            for i in range(2, steps + 2):
                client.train_step(x[i], y[i], i)
            s = transport.stats.summary()
            out[f"p50_ms_{compress}"] = s["p50_ms"]
            out[f"bytes_per_step_{compress}"] = (
                (s["bytes_sent"] + s["bytes_received"]) / steps)
            out[f"phases_{compress}"] = _traced_phase_breakdown(lambda: [
                client.train_step(x[j % (steps + 2)], y[j % (steps + 2)],
                                  steps + 2 + j) for j in range(3)])
        finally:
            transport.close()
            server.stop()
    if out.get("bytes_per_step_int8"):
        out["byte_reduction"] = (out["bytes_per_step_none"]
                                 / out["bytes_per_step_int8"])
        out["p50_speedup"] = out["p50_ms_none"] / out["p50_ms_int8"]
    return out


def measure_topk8(quick: bool) -> dict:
    """Sparse error-feedback wire compression (transport/codec.py topk8):
    top-k magnitude selection at density 0.1 + int8 quantization of the
    survivors, with the un-shipped residual fed back into the next step's
    selection. Three runs over the same emulated wire (LocalTransport with
    compress= — real codec both directions, byte counts included) on a
    synthetic 80 ms link: dense fp32, int8, topk8. Gates: >=8x fewer
    bytes/step than fp32, >=2.5x fewer than int8, and final training loss
    within 5% of the dense run.

    Parity discipline: the server half *trains on what the wire delivers*,
    so a compressed run's model is adapted to its own wire — evaluating it
    on dense inputs measures train/serve skew, not optimization quality.
    Each run is therefore scored on its own training-loss tail (mean of
    the last 30 steps), on a stream with an irreducible plateau (clustered
    inputs + 15% label flips) so the 5% gate compares optimization
    quality, not a near-zero noise floor. The parity gate only applies to
    the full leg: 40 quick steps end mid-descent where the runs have not
    converged to the plateau yet."""
    import jax
    import numpy as np

    from split_learning_tpu.models import get_plan
    from split_learning_tpu.runtime import ServerRuntime, SplitClientTrainer
    from split_learning_tpu.transport.local import LocalTransport
    from split_learning_tpu.utils import Config

    steps = 40 if quick else 300
    tail = 8 if quick else 30
    delay = 0.005 if quick else 0.08
    density = 0.1
    plan = get_plan(mode="split")
    cfg = Config(mode="split", batch_size=BATCH, decay_steps=steps)

    # Learnable stream with a noise floor: 10 gaussian class clusters,
    # 15% label flips. All three runs see identical batches.
    centers = np.random.RandomState(7).randn(10, 28, 28, 1
                                             ).astype(np.float32) * 2
    rs = np.random.RandomState(8)
    data = []
    for _ in range(steps):
        yb = rs.randint(0, 10, BATCH)
        xb = (centers[yb]
              + 0.4 * rs.randn(BATCH, 28, 28, 1)).astype(np.float32)
        yb = np.where(rs.rand(BATCH) < 0.15, rs.randint(0, 10, BATCH), yb)
        data.append((xb, yb.astype(np.int64)))

    class _DelayedLocal:
        """Synthetic wire around the in-process hop (sleeps only)."""

        def __init__(self, inner, delay_s):
            self.inner = inner
            self.delay = delay_s
            self.stats = inner.stats

        def split_step(self, *a, **kw):
            time.sleep(self.delay)          # activations down
            res = self.inner.split_step(*a, **kw)
            time.sleep(self.delay)          # gradients back
            return res

        def aggregate(self, *a, **kw):
            return self.inner.aggregate(*a, **kw)

        def health(self):
            return self.inner.health()

        def close(self):
            self.inner.close()

    out = {"leg": "wire_topk8", "platform": "cpu+synthetic-wire",
           "density": density, "steps": steps,
           "one_way_latency_ms": delay * 1e3,
           "note": ("fixed-latency wire: bytes gates are the point; the "
                    "sleep models propagation delay, not bandwidth, so "
                    "steps/sec barely moves with payload size"),
           "valid": True, "invalid_reason": None}
    finals = {}
    # dispatch watchdog on for the whole leg (in-process force, not the
    # env gate): counts XLA compiles and flags any steady-state recompile
    from split_learning_tpu.obs import dispatch_debug
    dd = dispatch_debug.tracker()
    g0 = dd.gauges()
    dispatch_debug.force(True)
    try:
        for mode in ("none", "int8", "topk8"):
            runtime = ServerRuntime(plan, cfg, jax.random.PRNGKey(0),
                                    data[0][0])
            transport = _DelayedLocal(
                LocalTransport(runtime, compress=mode, density=density),
                delay)
            client = SplitClientTrainer(plan, cfg, jax.random.PRNGKey(0),
                                        transport)
            losses = []
            t0 = time.perf_counter()
            for i, (xb, yb) in enumerate(data):
                losses.append(client.train_step(xb, yb, i))
            dt = time.perf_counter() - t0
            s = transport.stats.summary()
            out[f"bytes_per_step_{mode}"] = (
                (s["bytes_sent"] + s["bytes_received"]) / steps)
            out[f"final_loss_{mode}"] = float(np.mean(losses[-tail:]))
            out[f"steps_per_sec_{mode}"] = steps / dt
            if mode == "topk8" and s.get("compression_ratio"):
                out["codec_compression_ratio"] = s["compression_ratio"]
            finals[mode] = out[f"final_loss_{mode}"]
            transport.close()
    finally:
        dispatch_debug.force(False)
    g1 = dd.gauges()
    out["compile_count"] = {
        "total": g1["compile_count"] - g0["compile_count"],
        "steady_state": (g1["steady_state_recompiles"]
                         - g0["steady_state_recompiles"])}

    out["bytes_per_step"] = out["bytes_per_step_topk8"]
    out["byte_reduction_vs_fp32"] = (out["bytes_per_step_none"]
                                     / out["bytes_per_step_topk8"])
    out["byte_reduction_vs_int8"] = (out["bytes_per_step_int8"]
                                     / out["bytes_per_step_topk8"])
    out["loss_parity"] = (abs(finals["topk8"] - finals["none"])
                          / max(abs(finals["none"]), 1e-12))
    problems = []
    if out["byte_reduction_vs_fp32"] < 8.0:
        problems.append(f"byte_reduction_vs_fp32="
                        f"{out['byte_reduction_vs_fp32']:.2f} < 8.0")
    if out["byte_reduction_vs_int8"] < 2.5:
        problems.append(f"byte_reduction_vs_int8="
                        f"{out['byte_reduction_vs_int8']:.2f} < 2.5")
    if not quick and out["loss_parity"] > 0.05:
        problems.append(f"loss_parity={out['loss_parity']:.4f} > 0.05: "
                        "topk8 tail loss diverges from dense")
    if out["compile_count"]["steady_state"]:
        problems.append(
            f"steady_state_recompiles="
            f"{out['compile_count']['steady_state']:.0f} != 0: the hot "
            "loop retraces after step 2")
    if problems:
        out["valid"] = False
        out["invalid_reason"] = "; ".join(problems)
    return out


def measure_chaos_soak(quick: bool) -> dict:
    """Robustness soak (transport/chaos.py + the ServerRuntime replay
    cache): train the same seeded stream twice — once on a clean wire,
    once under a seeded fault schedule with response-drops (applied
    server-side, reply lost), duplicated deliveries, and 5xx — with the
    client on the bounded-retry policy. Exactly-once delivery makes the
    chaotic run *deterministically equivalent*: a dropped response is
    recovered from the replay cache (no re-apply), a duplicate is served
    the cached original, a 5xx retried fresh never applied at all. Gates:
    zero dropped batches, replay cache actually engaged, faults actually
    injected, and final training loss within 5% of the fault-free run
    (it should be bit-near-identical — the 5% gate is the acceptance
    contract, not the expectation)."""
    import jax
    import numpy as np

    from split_learning_tpu.models import get_plan
    from split_learning_tpu.runtime import ServerRuntime, SplitClientTrainer
    from split_learning_tpu.runtime.client import FailurePolicy
    from split_learning_tpu.transport.chaos import ChaosPolicy, ChaosTransport
    from split_learning_tpu.transport.local import LocalTransport
    from split_learning_tpu.utils import Config

    steps = 40 if quick else 220
    tail = 8 if quick else 30
    spec = "drop_resp=0.10,dup=0.05,http500=0.05"
    seed = 1234
    plan = get_plan(mode="split")
    cfg = Config(mode="split", batch_size=BATCH, decay_steps=steps)

    # same learnable-stream recipe as the topk8 leg: both runs see
    # identical batches
    centers = np.random.RandomState(7).randn(10, 28, 28, 1
                                             ).astype(np.float32) * 2
    rs = np.random.RandomState(8)
    data = []
    for _ in range(steps):
        yb = rs.randint(0, 10, BATCH)
        xb = (centers[yb]
              + 0.4 * rs.randn(BATCH, 28, 28, 1)).astype(np.float32)
        yb = np.where(rs.rand(BATCH) < 0.15, rs.randint(0, 10, BATCH), yb)
        data.append((xb, yb.astype(np.int64)))

    out = {"leg": "chaos_soak", "platform": "cpu", "steps": steps,
           "chaos_spec": spec, "chaos_seed": seed,
           "valid": True, "invalid_reason": None}
    finals = {}
    losses_by_run = {}
    for run in ("clean", "chaos"):
        runtime = ServerRuntime(plan, cfg, jax.random.PRNGKey(0),
                                data[0][0])
        transport = LocalTransport(runtime)
        if run == "chaos":
            policy = ChaosPolicy(spec, seed=seed)
            transport = ChaosTransport(transport, policy)
        client = SplitClientTrainer(plan, cfg, jax.random.PRNGKey(0),
                                    transport,
                                    failure_policy=FailurePolicy.RETRY,
                                    max_retries=3, retry_backoff=0.0)
        losses = []
        t0 = time.perf_counter()
        for i, (xb, yb) in enumerate(data):
            losses.append(client.train_step(xb, yb, i))
        dt = time.perf_counter() - t0
        losses_by_run[run] = losses
        finals[run] = float(np.mean([l for l in losses[-tail:]
                                     if l is not None]))
        out[f"final_loss_{run}"] = finals[run]
        out[f"steps_per_sec_{run}"] = steps / dt
        if run == "chaos":
            out["dropped_batches"] = client.dropped_batches
            out["chaos_injected"] = dict(policy.injected)
            rc = runtime.replay.counters()
            out["replay_hits"] = rc["replay_hits"]

    out["loss_parity"] = (abs(finals["chaos"] - finals["clean"])
                          / max(abs(finals["clean"]), 1e-12))
    # step-for-step agreement: exactly-once means the fault schedule
    # changes the wire, never the math
    pairs = [(a, b) for a, b in zip(losses_by_run["clean"],
                                    losses_by_run["chaos"])
             if a is not None and b is not None]
    out["max_step_loss_diff"] = float(max(abs(a - b) for a, b in pairs))
    problems = []
    if out["dropped_batches"] != 0:
        problems.append(f"dropped_batches={out['dropped_batches']} != 0")
    if sum(out["chaos_injected"].values()) == 0:
        problems.append("no faults injected: the soak soaked nothing")
    if out["replay_hits"] == 0:
        problems.append("replay_hits=0: the cache never engaged, so "
                        "drop_resp/dup recovery went untested")
    if out["loss_parity"] > 0.05:
        problems.append(f"loss_parity={out['loss_parity']:.4f} > 0.05: "
                        "the chaotic run diverged from the clean run")
    if problems:
        out["valid"] = False
        out["invalid_reason"] = "; ".join(problems)
    return out


def measure_fleet_soak(quick: bool) -> dict:
    """Continuous batching under a bursty fleet (runtime/fleet.py +
    runtime/admission.py): the same deterministic arrival schedule is
    offered to three twin servers — fixed-window coalescing, continuous
    batching, and continuous batching on a chaos-wrapped wire — and the
    pooled queue-wait tail decides the headline. Bursty sub-critical
    load is the window flusher's worst case (every lone arrival waits
    out the timer) and the continuous batcher's best (dispatch the
    moment the previous group leaves); the leg gates continuous p99
    queue-wait strictly below window p99. Integrity gates ride along:
    every scheduled step completes (dropped_steps == 0), replay engages
    on the chaos twin and its loss stays within 5% of the clean twin,
    and warm_fleet's shape priming means the measured runs see zero XLA
    compiles (steady-state dispatch only)."""
    import jax
    import numpy as np

    from split_learning_tpu.models import get_plan
    from split_learning_tpu.obs import dispatch_debug
    from split_learning_tpu.runtime.fleet import (
        FleetConfig, run_fleet, warm_fleet)
    from split_learning_tpu.runtime.server import ServerRuntime
    from split_learning_tpu.transport.chaos import ChaosPolicy, ChaosTransport
    from split_learning_tpu.transport.local import LocalTransport
    from split_learning_tpu.utils import Config

    n_clients = 64 if quick else 1024
    tenants = 4
    steps_pc = 2
    # per-client batch 8, NOT the reference BATCH: the leg measures
    # scheduling policy, and a small step keeps the dispatcher
    # sub-critical at fleet scale on shared CPU cores
    batch = 8
    # sub-critical bursty load: pairs arrive together, aggregate rate
    # well under the dispatcher's service capacity — the regime where
    # batching policy (not saturation) sets the queue-wait tail.
    # arrival_offsets spreads first bursts over 1/rate_hz seconds, so
    # aggregate offered load is n_clients * steps_pc * rate_hz: 0.015
    # at 1024 clients (~31 steps/s) sat AT the CPU dispatcher's service
    # rate and both policies converged on queueing delay — 0.008
    # (~16 steps/s) keeps the fleet in the regime the A/B measures
    rate_hz = 0.05 if quick else 0.008
    spec = "drop_resp=0.05,dup=0.02"
    chaos_seed = 4321
    plan = get_plan(mode="split")
    cfg = Config(mode="split", batch_size=batch, num_clients=1 << 20)
    fcfg = FleetConfig(n_clients=n_clients, tenants=tenants,
                       steps_per_client=steps_pc, arrival="burst",
                       rate_hz=rate_hz, burst_size=2, seed=1,
                       workers=16, batch=batch)
    expected = n_clients * steps_pc
    dd = dispatch_debug.tracker()

    def run(batching: str, chaos: bool) -> dict:
        dispatch_debug.force(True)
        try:
            server = ServerRuntime(
                plan, cfg, jax.random.PRNGKey(0),
                np.zeros((batch, 28, 28, 1), np.float32),
                strict_steps=True, coalesce_max=4,
                coalesce_window_ms=50.0, batching=batching,
                tenants=tenants, slo_ms=250.0)
            if chaos:
                def factory(cid):
                    # per-client seed: the chaos twin offers the clean
                    # twin's exact arrivals plus a reproducible fault
                    # schedule
                    policy = ChaosPolicy(
                        spec, seed=chaos_seed * 1_000_003 + cid)
                    return ChaosTransport(LocalTransport(server), policy)
            else:
                def factory(cid):
                    return LocalTransport(server)
            try:
                warm_rounds = warm_fleet(server, factory, fcfg)
                c0 = server.health()["coalescing"]["compile_count"]
                g0 = dd.gauges()
                res = run_fleet(fcfg, factory)
                g1 = dd.gauges()
                c1 = server.health()["coalescing"]["compile_count"]
                coalescing = server.health()["coalescing"]
                replay = server.replay.counters()
            finally:
                server.close()
        finally:
            dispatch_debug.force(False)
        return {
            "batching": batching, "chaos": chaos,
            "warm_rounds": warm_rounds,
            "wall_s": res.wall_s,
            "steps_completed": int(res.counters["fleet_steps_total"]),
            "dropped_steps": int(res.counters["fleet_dropped_steps"]),
            "backpressure_total": int(
                res.counters.get("fleet_backpressure_total", 0)),
            "retries_total": int(
                res.counters.get("fleet_retries_total", 0)),
            "mean_loss": res.mean_loss,
            "compiles_in_run": c1 - c0,
            "steady_state_recompiles": (g1["steady_state_recompiles"]
                                        - g0["steady_state_recompiles"]),
            "mean_occupancy": (
                coalescing["requests_coalesced"]
                / max(coalescing["groups_flushed"], 1)),
            "overall": res.overall,
            "per_tenant": {str(t): row
                           for t, row in res.per_tenant.items()},
            "replay": replay,
        }

    window = run("window", chaos=False)
    continuous = run("continuous", chaos=False)
    chaos_twin = run("continuous", chaos=True)

    qw_window = window["overall"].get("queue_wait_p99_ms")
    qw_continuous = continuous["overall"].get("queue_wait_p99_ms")
    # ABSOLUTE gap in nats, not a ratio: both twins converge to mean
    # loss ~0.1 on this task, so a relative bound divides ~0.01 nats of
    # apply-order noise by a near-zero denominator and flaps. Scale
    # reference: initial loss is ln(10) ~= 2.3.
    loss_parity = abs(chaos_twin["mean_loss"] - continuous["mean_loss"])
    out = {
        "leg": "fleet_soak", "platform": "cpu+local-loopback",
        "host_cores": os.cpu_count(),
        "clients": n_clients, "tenants": tenants,
        "steps_per_client": steps_pc, "per_client_batch": batch,
        "arrival": "burst", "rate_hz": rate_hz, "burst_size": 2,
        "coalesce_max": 4, "window_ms": 50.0,
        "chaos_spec": spec, "chaos_seed": chaos_seed,
        "note": ("three twins over one seeded arrival schedule; "
                 "queue-wait is the server-side enqueue->group-pickup "
                 "span pooled across tenants, the number continuous "
                 "batching exists to shrink"),
        "window": window, "continuous": continuous,
        "chaos_twin": chaos_twin,
        "queue_wait_p99_ms_window": qw_window,
        "queue_wait_p99_ms_continuous": qw_continuous,
        "loss_parity": loss_parity,
        "valid": True, "invalid_reason": None,
    }
    problems = []
    for rec in (window, continuous, chaos_twin):
        tag = ("chaos" if rec["chaos"] else rec["batching"])
        if rec["steps_completed"] != expected:
            problems.append(f"{tag}: steps_completed="
                            f"{rec['steps_completed']} != {expected}")
        if rec["dropped_steps"] != 0:
            problems.append(
                f"{tag}: dropped_steps={rec['dropped_steps']} != 0")
        if rec["compiles_in_run"] != 0:
            problems.append(
                f"{tag}: compiles_in_run={rec['compiles_in_run']} != 0: "
                "warm_fleet's shape priming missed a pow2 bucket, the "
                "queue-wait tail is compile-polluted")
        if rec["steady_state_recompiles"] != 0:
            problems.append(
                f"{tag}: steady_state_recompiles="
                f"{rec['steady_state_recompiles']} != 0")
    if qw_window is None or qw_continuous is None:
        problems.append("missing pooled queue-wait histograms")
    elif not qw_continuous < qw_window:
        problems.append(
            f"continuous p99 queue-wait {qw_continuous:.1f} ms not below "
            f"window {qw_window:.1f} ms: the continuous batcher bought "
            "nothing in its best-case regime")
    if chaos_twin["replay"]["replay_hits"] == 0:
        problems.append("chaos twin replay_hits=0: the cache never "
                        "engaged, exactly-once went untested")
    # drop/dup faults reshuffle WHICH requests share a group and in
    # what order they apply, so the twins' loss trajectories differ by
    # grouping noise (~0.01 nats at 2k steps) — exactly-once delivery
    # is gated separately (steps_completed, dropped_steps, replay_hits)
    # and this bound only needs to catch corruption-scale divergence
    if loss_parity > 0.05:
        problems.append(f"loss_parity={loss_parity:.4f} > 0.05 nats: "
                        "the chaos twin diverged from its clean twin")
    if problems:
        out["valid"] = False
        out["invalid_reason"] = "; ".join(problems)
    return out


def measure_replica_failover(quick: bool) -> dict:
    """Horizontal replication under a mid-run chaos kill
    (runtime/replica.py): the same seeded bursty fleet is offered to
    two 3-replica twin groups — one untouched, one whose busiest
    replica is breaker-killed halfway through — and the leg gates that
    the sticky router's exactly-once handoff keeps the killed twin
    whole: every scheduled step completes, zero dropped, the handoff
    counters actually engaged (death, migration, reroutes), zero
    steady-state recompiles, and the killed twin's mean loss within an
    ABSOLUTE nats bound of the clean twin (same rationale as
    fleet_soak: both converge low, a ratio would flap). A serial
    bit-identity pin rides along: ``maybe_replicate(n=1)`` must be the
    plain runtime, loss-for-loss."""
    import jax
    import numpy as np

    from split_learning_tpu.models import get_plan
    from split_learning_tpu.obs import dispatch_debug
    from split_learning_tpu.runtime.fleet import (
        FleetConfig, run_fleet, warm_fleet)
    from split_learning_tpu.runtime.replica import maybe_replicate
    from split_learning_tpu.runtime.server import ServerRuntime
    from split_learning_tpu.transport.local import LocalTransport
    from split_learning_tpu.utils import Config

    n_clients = 24 if quick else 96
    steps_pc = 2
    batch = 8
    # sub-critical bursty load (the fleet_soak regime): policy, not
    # saturation, sets the tail — and the kill lands mid-queue, not
    # mid-collapse
    rate_hz = 0.05 if quick else 0.008
    n_replicas = 3
    expected = n_clients * steps_pc
    kill_at = expected // 2
    plan = get_plan(mode="split")
    cfg = Config(mode="split", batch_size=batch, num_clients=1 << 20)
    sample = np.zeros((batch, 28, 28, 1), np.float32)
    dd = dispatch_debug.tracker()

    def make_replica(_idx: int) -> ServerRuntime:
        # shared init (same plan/cfg/key): the group is statistically
        # one model
        return ServerRuntime(plan, cfg, jax.random.PRNGKey(0), sample,
                             strict_steps=True, coalesce_max=4,
                             coalesce_window_ms=50.0,
                             batching="continuous")

    def group_compiles(group) -> int:
        # sum over ALL replicas: the group's own health() sums live
        # ones only, so a kill would make the delta go negative
        total = 0
        for r in group.replicas:
            try:
                total += r.health().get("coalescing", {}).get(
                    "compile_count", 0)
            except Exception:
                pass
        return total

    def run(kill: bool) -> dict:
        fcfg = FleetConfig(n_clients=n_clients, tenants=1,
                           steps_per_client=steps_pc, arrival="burst",
                           rate_hz=rate_hz, burst_size=2, seed=1,
                           workers=16, batch=batch,
                           kill_replica_at=(kill_at if kill else 0))
        dispatch_debug.force(True)
        try:
            group = maybe_replicate(make_replica, n_replicas)

            def factory(cid):
                return LocalTransport(group)
            try:
                warm_rounds = warm_fleet(group, factory, fcfg)
                c0 = group_compiles(group)
                g0 = dd.gauges()
                res = run_fleet(fcfg, factory, group=group)
                g1 = dd.gauges()
                c1 = group_compiles(group)
                counters = group.counters()
                live = group.live_replicas()
            finally:
                group.close()
        finally:
            dispatch_debug.force(False)
        return {
            "killed": kill, "warm_rounds": warm_rounds,
            "wall_s": res.wall_s,
            "steps_completed": int(res.counters["fleet_steps_total"]),
            "dropped_steps": int(res.counters["fleet_dropped_steps"]),
            "kills": int(res.counters.get("fleet_replica_kills", 0)),
            "mean_loss": res.mean_loss,
            "compiles_in_run": c1 - c0,
            "steady_state_recompiles": (g1["steady_state_recompiles"]
                                        - g0["steady_state_recompiles"]),
            "live_replicas": live,
            "replica_handoffs": int(counters["replica_handoffs"]),
            "replica_deaths": int(counters["replica_deaths"]),
            "replica_reroutes": int(counters["replica_reroutes"]),
            "handoff_replay_entries": int(
                counters["handoff_replay_entries"]),
            "overall": res.overall,
        }

    # serial bit-identity pin: --replicas 1 IS the plain runtime. The
    # fleet's concurrent apply order is timing-dependent, so the pin
    # runs serially where loss equality is exact, not approximate.
    plain = make_replica(0)
    solo = maybe_replicate(make_replica, 1)
    rs = np.random.RandomState(7)
    solo_match = True
    try:
        for step in range(1, 4):
            acts = rs.randn(batch, 26, 26, 32).astype(np.float32)
            labels = rs.randint(0, 10, (batch,)).astype(np.int64)
            _, lp = plain.split_step(acts, labels, step, 0)
            _, ls = solo.split_step(acts, labels, step, 0)
            if lp != ls:
                solo_match = False
    finally:
        plain.close()
        solo.close()

    clean = run(kill=False)
    killed = run(kill=True)
    loss_parity = abs(killed["mean_loss"] - clean["mean_loss"])
    out = {
        "leg": "replica_failover", "platform": "cpu+local-loopback",
        "host_cores": os.cpu_count(),
        "clients": n_clients, "steps_per_client": steps_pc,
        "per_client_batch": batch, "replicas": n_replicas,
        "kill_replica_at": kill_at,
        "arrival": "burst", "rate_hz": rate_hz, "burst_size": 2,
        "note": ("twin 3-replica groups over one seeded arrival "
                 "schedule; the killed twin loses its busiest replica "
                 "mid-run and must finish whole through the "
                 "exactly-once handoff"),
        "clean": clean, "killed": killed,
        "loss_parity": loss_parity,
        "replicas_one_bit_identical": solo_match,
        "valid": True, "invalid_reason": None,
    }
    problems = []
    for rec in (clean, killed):
        tag = "killed" if rec["killed"] else "clean"
        if rec["steps_completed"] != expected:
            problems.append(f"{tag}: steps_completed="
                            f"{rec['steps_completed']} != {expected}")
        if rec["dropped_steps"] != 0:
            problems.append(
                f"{tag}: dropped_steps={rec['dropped_steps']} != 0")
        if rec["steady_state_recompiles"] != 0:
            problems.append(
                f"{tag}: steady_state_recompiles="
                f"{rec['steady_state_recompiles']} != 0")
    if clean["replica_deaths"] != 0 or clean["kills"] != 0:
        problems.append("clean twin saw a death/kill it should not have")
    if killed["kills"] != 1 or killed["replica_deaths"] != 1 or \
            killed["replica_handoffs"] != 1:
        problems.append(
            f"killed twin handoff counters off: kills={killed['kills']} "
            f"deaths={killed['replica_deaths']} "
            f"handoffs={killed['replica_handoffs']} (want 1/1/1)")
    if killed["handoff_replay_entries"] == 0:
        problems.append("handoff migrated 0 replay entries: the "
                        "exactly-once merge went untested")
    if killed["replica_reroutes"] == 0:
        problems.append("0 reroutes after the kill: the victim owned "
                        "no clients, the failover went untested")
    if len(killed["live_replicas"]) != n_replicas - 1:
        problems.append(f"killed twin ended with live replicas "
                        f"{killed['live_replicas']}")
    if not solo_match:
        problems.append("maybe_replicate(n=1) diverged from the plain "
                        "runtime: the zero-overhead-off pin broke")
    # the killed twin's migrated clients finish their remaining steps
    # on successors whose params drifted from the victim's (replicas
    # train independently between syncs), so the trajectories differ
    # by migration noise — ~0.1 nats at this scale with a third of the
    # fleet rerouted after one step. The absolute bound is sized to
    # catch corruption-scale divergence (a double-apply or lost merge
    # shows up as whole nats), not to forbid the migration itself.
    if loss_parity > 0.25:
        problems.append(f"loss_parity={loss_parity:.4f} > 0.25 nats: "
                        "the killed twin diverged from its clean twin")
    if problems:
        out["valid"] = False
        out["invalid_reason"] = "; ".join(problems)
    return out


def measure_autoscale_diurnal(quick: bool) -> dict:
    """Elastic autoscaling under a diurnal arrival cycle (PR 19,
    runtime/autoscale.py): the same seeded sinusoidally-modulated fleet
    is offered to two arms — a STATIC arm provisioned at the peak (3
    replicas, no policy) and an ELASTIC arm starting at 1 replica with
    the telemetry-driven autoscaler free to scale between 1 and 3.
    The leg gates that elasticity is not a trade of correctness or
    latency for cost: both arms complete every scheduled step with
    zero drops; the elastic arm's policy actually engaged (>= 1
    scale-up); its settled p99 (the best of the final three non-null
    points of the policy-seen trajectory) holds under the SLO; and it
    spends
    STRICTLY fewer replica-seconds than the static-peak arm — the
    whole point of scaling down through the exactly-once handoff
    instead of provisioning for the peak."""
    import jax
    import numpy as np

    from split_learning_tpu.models import get_plan
    from split_learning_tpu.obs import telemetry as obs_telemetry
    from split_learning_tpu.obs import trace as obs_trace
    from split_learning_tpu.runtime.autoscale import (
        Autoscaler, AutoscalePolicy)
    from split_learning_tpu.runtime.fleet import (
        FleetConfig, run_fleet, warm_fleet)
    from split_learning_tpu.runtime.replica import ReplicaGroup
    from split_learning_tpu.runtime.server import ServerRuntime
    from split_learning_tpu.transport.local import LocalTransport
    from split_learning_tpu.utils import Config

    n_clients = 12 if quick else 24
    steps_pc = 3
    batch = 8
    coalesce_max = 4
    rate_hz = 0.6            # diurnal-modulated poisson, busy/idle phases
    period_s = 3.0
    peak_replicas = 3
    interval_s = 0.25
    # bucket-aligned: the ring's histogram edges jump 25ms -> 50ms, so
    # 50 is the tightest SLO the p99 estimate can actually adjudicate
    # (a window in the 25-50 bucket reports ~49.75; one past the edge
    # reports ~99.5)
    slo_ms = 50.0
    expected = n_clients * steps_pc
    plan = get_plan(mode="split")
    cfg = Config(mode="split", batch_size=batch, num_clients=1 << 20)
    sample = np.zeros((batch, 28, 28, 1), np.float32)
    had_tracer = obs_trace.get_tracer() is not None

    def make_replica(_idx: int) -> ServerRuntime:
        return ServerRuntime(plan, cfg, jax.random.PRNGKey(0), sample,
                             strict_steps=True, coalesce_max=coalesce_max,
                             coalesce_window_ms=50.0,
                             batching="continuous")

    fcfg = FleetConfig(n_clients=n_clients, tenants=1,
                       steps_per_client=steps_pc, arrival="diurnal",
                       rate_hz=rate_hz, diurnal_period_s=period_s,
                       seed=3, workers=16, batch=batch)

    def run(elastic: bool) -> dict:
        n0 = 1 if elastic else peak_replicas
        group = ReplicaGroup([make_replica(i) for i in range(n0)])

        def factory(cid):
            return LocalTransport(group)
        ring = None
        autoscaler = None
        if obs_trace.get_tracer() is None:
            obs_trace.enable()  # the ring's p99 is tracer-gated
        try:
            warm_rounds = warm_fleet(group, factory, fcfg)
            if elastic:
                ring = obs_telemetry.TelemetryRing(
                    group.metrics, party="server",
                    interval_s=interval_s, capacity=600)
                ring.start_sampler()
                policy = AutoscalePolicy(
                    min_replicas=1, max_replicas=peak_replicas,
                    cooldown_up_s=0.2, cooldown_down_s=0.4)
                autoscaler = Autoscaler(group, make_replica, policy,
                                        ring, coalesce_max=coalesce_max,
                                        slo_ms=slo_ms)
                autoscaler.start(interval_s)
            res = run_fleet(fcfg, factory, group=group,
                            autoscaler=autoscaler)
            if autoscaler is not None:
                autoscaler.close()  # settle before reading summaries
            summ = (autoscaler.summary() if autoscaler is not None
                    else {"scale_ups": 0, "scale_downs": 0,
                          "decisions": 0, "events": [],
                          "p99_ms_trajectory": []})
            seconds = group.replica_seconds()
        finally:
            if autoscaler is not None:
                autoscaler.close()
            if ring is not None:
                ring.close()
            group.close()
            if not had_tracer and obs_trace.get_tracer() is not None:
                obs_trace.disable()
        # "settled" = best of the final three non-null windows: a lone
        # late window that swallowed a scale transient (replica
        # construction compiles on CPU) must not mask the state the
        # loop actually converged to — but a recent window still has to
        # clear the SLO on its own
        p99s = [p for p in summ["p99_ms_trajectory"] if p is not None]
        settled = min(p99s[-3:]) if p99s else None
        return {
            "elastic": elastic, "warm_rounds": warm_rounds,
            "wall_s": res.wall_s,
            "steps_completed": int(res.counters["fleet_steps_total"]),
            "dropped_steps": int(res.counters["fleet_dropped_steps"]),
            "mean_loss": res.mean_loss,
            "replica_seconds": round(sum(seconds.values()), 3),
            "final_replicas": len(seconds),
            "scale_ups": int(summ["scale_ups"]),
            "scale_downs": int(summ["scale_downs"]),
            "decisions": int(summ["decisions"]),
            "p99_ms_trajectory": summ["p99_ms_trajectory"],
            "settled_p99_ms": settled,
            "overall": res.overall,
        }

    static = run(elastic=False)
    elastic = run(elastic=True)
    out = {
        "leg": "autoscale_diurnal", "platform": "cpu+local-loopback",
        "host_cores": os.cpu_count(),
        "clients": n_clients, "steps_per_client": steps_pc,
        "per_client_batch": batch,
        "arrival": "diurnal", "rate_hz": rate_hz,
        "diurnal_period_s": period_s,
        "peak_replicas": peak_replicas, "slo_ms": slo_ms,
        "note": ("twin arms over one seeded diurnal schedule: static "
                 "peak provisioning vs policy-driven elasticity "
                 "(1..3 replicas); elasticity must cost strictly "
                 "fewer replica-seconds at held SLO and zero drops"),
        "static": static, "elastic": elastic,
        "replica_seconds_saved": round(
            static["replica_seconds"] - elastic["replica_seconds"], 3),
        "valid": True, "invalid_reason": None,
    }
    problems = []
    for rec in (static, elastic):
        tag = "elastic" if rec["elastic"] else "static"
        if rec["steps_completed"] != expected:
            problems.append(f"{tag}: steps_completed="
                            f"{rec['steps_completed']} != {expected}")
        if rec["dropped_steps"] != 0:
            problems.append(
                f"{tag}: dropped_steps={rec['dropped_steps']} != 0")
    if static["scale_ups"] or static["scale_downs"]:
        problems.append("static arm scaled: no policy should exist there")
    if elastic["scale_ups"] < 1:
        problems.append("elastic arm never scaled up: the diurnal peak "
                        "went unnoticed, the leg tested nothing")
    if elastic["settled_p99_ms"] is None:
        problems.append("elastic arm has no p99 trajectory: the policy "
                        "flew blind")
    elif elastic["settled_p99_ms"] > slo_ms:
        problems.append(
            f"elastic settled p99 {elastic['settled_p99_ms']:.1f} ms > "
            f"SLO {slo_ms:.0f} ms: elasticity traded latency for cost")
    if elastic["replica_seconds"] >= static["replica_seconds"]:
        problems.append(
            f"elastic replica-seconds {elastic['replica_seconds']} >= "
            f"static {static['replica_seconds']}: elasticity saved "
            "nothing over peak provisioning")
    if problems:
        out["valid"] = False
        out["invalid_reason"] = "; ".join(problems)
    return out


def measure_pipelined(quick: bool) -> dict:
    """The PiPar-style in-flight window (runtime/pipelined_client.py) vs
    the reference's lock-step loop, both over HTTP loopback: steady-state
    throughput approaches 1/max(server_step, wire) instead of
    1/(client_fwd + round_trip + client_bwd)."""
    import jax

    from split_learning_tpu.models import get_plan
    from split_learning_tpu.runtime import (
        PipelinedSplitClientTrainer, ServerRuntime, SplitClientTrainer)
    from split_learning_tpu.transport.http import HttpTransport, SplitHTTPServer
    from split_learning_tpu.utils import Config

    steps = 8 if quick else 30
    depth = 4
    cfg = Config(mode="split", batch_size=BATCH)
    plan = get_plan(mode="split")
    x, y = _data(steps + 2, "split_cnn")
    batches = list(zip(x, y))
    out = {"leg": "pipelined_http", "depth": depth,
           "platform": "cpu+http-loopback",
           "host_cores": os.cpu_count(),
           # overlap buys nothing when both parties convoy on shared
           # cores (total CPU work per step is constant); the win this
           # design targets appears when client and server own separate
           # CPUs (the reference's actual two-pod topology) — or with
           # real wire latency to hide (the synthetic_wire scenario)
           "note": ("loopback on shared cores measures convoying, not "
                    "the wire/compute overlap the window exists for"),
           "valid": True, "invalid_reason": None}

    def run_pair(wrap, n_steps):
        """(lock-step steps/s, depth-W steps/s) with ``wrap`` applied to
        every transport lane — one measurement recipe for both the
        loopback and synthetic-wire scenarios."""
        runtime = ServerRuntime(plan, cfg, jax.random.PRNGKey(0), x[0])
        server = SplitHTTPServer(runtime).start()
        transport = wrap(HttpTransport(server.url))
        client = SplitClientTrainer(plan, cfg, jax.random.PRNGKey(0),
                                    transport)
        try:
            for i in range(2):
                client.train_step(x[i], y[i], i)
            t0 = time.perf_counter()
            for i in range(2, n_steps + 2):
                client.train_step(x[i], y[i], i)
            sync = n_steps / (time.perf_counter() - t0)
        finally:
            transport.close()
            server.stop()

        # depth-W window (async SGD, delay < W; server strict_steps off)
        runtime = ServerRuntime(plan, cfg, jax.random.PRNGKey(0), x[0],
                                strict_steps=False)
        server = SplitHTTPServer(runtime).start()
        lane0 = wrap(HttpTransport(server.url))
        piped = PipelinedSplitClientTrainer(
            plan, cfg, jax.random.PRNGKey(0), lane0, depth=depth,
            transport_factory=lambda: wrap(HttpTransport(server.url)))
        try:
            piped.train(lambda: iter(batches[:2]), epochs=1)  # warm lanes
            t0 = time.perf_counter()
            piped.train(lambda: iter(batches[2:n_steps + 2]), epochs=1,
                        start_step=2)
            depth_w = n_steps / (time.perf_counter() - t0)
        finally:
            piped.close()
            lane0.close()
            server.stop()
        return sync, depth_w

    sync, depth_w = run_pair(lambda t: t, steps)
    out["steps_per_sec_sync"] = sync
    out[f"steps_per_sec_depth{depth}"] = depth_w
    out["pipelining_speedup"] = depth_w / sync

    def _traced_pipelined():
        runtime = ServerRuntime(plan, cfg, jax.random.PRNGKey(0), x[0],
                                strict_steps=False)
        server = SplitHTTPServer(runtime).start()
        lane0 = HttpTransport(server.url)
        piped = PipelinedSplitClientTrainer(
            plan, cfg, jax.random.PRNGKey(0), lane0, depth=depth,
            transport_factory=lambda: HttpTransport(server.url))
        try:
            piped.train(lambda: iter(batches[:4]), epochs=1)
        finally:
            piped.close()
            lane0.close()
            server.stop()

    out["phases"] = _traced_phase_breakdown(_traced_pipelined)

    # --- injected-wire-latency scenario -------------------------------
    # Loopback has no wire, so the scenario above cannot show the
    # overlap the window exists for. Model the reference's real k8s
    # network with explicit sleeps around each round trip: sleeping
    # threads burn no CPU, so even on one shared core the lock-step
    # loop pays the full wire per step while the depth-W window hides
    # it behind compute — honestly labeled synthetic.
    class _DelayedTransport:
        def __init__(self, inner, delay_s):
            self.inner = inner
            self.delay = delay_s
            self.stats = inner.stats

        def split_step(self, *a, **kw):
            time.sleep(self.delay)          # activations down
            res = self.inner.split_step(*a, **kw)
            time.sleep(self.delay)          # gradients back
            return res

        def close(self):
            self.inner.close()

    delay = 0.08
    wire_steps = 6 if quick else 20
    sync, depth_w = run_pair(lambda t: _DelayedTransport(t, delay),
                             wire_steps)
    out["synthetic_wire"] = {
        "one_way_latency_ms": delay * 1e3, "steps": wire_steps,
        "note": "synthetic wire: sleeps model network latency the "
                "loopback lacks; overlap hides them behind compute",
        "steps_per_sec_sync": sync,
        f"steps_per_sec_depth{depth}": depth_w,
        "pipelining_speedup": depth_w / sync,
    }

    # --- async-dispatch overlap scenario (PR 5) -----------------------
    # The depth-W window keeps W steps in flight, so an off-lock D2H on
    # the server genuinely overlaps the NEXT lane's dispatch — the
    # pipelined client is the cleanest consumer of async dispatch.
    # d2h_delay_s is the same honestly-synthetic sleep as the wire
    # above; no wire delay here, the transfer is the thing measured.
    d2h = 0.02

    def run_depth_overlap(overlap: bool, n_steps: int) -> float:
        runtime = ServerRuntime(plan, cfg, jax.random.PRNGKey(0), x[0],
                                strict_steps=False, overlap=overlap,
                                d2h_delay_s=d2h)
        server = SplitHTTPServer(runtime).start()
        lane0 = HttpTransport(server.url)
        piped = PipelinedSplitClientTrainer(
            plan, cfg, jax.random.PRNGKey(0), lane0, depth=depth,
            transport_factory=lambda: HttpTransport(server.url))
        try:
            piped.train(lambda: iter(batches[:2]), epochs=1)  # warm lanes
            t0 = time.perf_counter()
            piped.train(lambda: iter(batches[2:n_steps + 2]), epochs=1,
                        start_step=2)
            return n_steps / (time.perf_counter() - t0)
        finally:
            piped.close()
            lane0.close()
            server.stop()

    ov_steps = 6 if quick else 16
    ov_on = run_depth_overlap(True, ov_steps)
    ov_off = run_depth_overlap(False, ov_steps)
    out["overlap"] = {
        "d2h_delay_ms": d2h * 1e3, "steps": ov_steps,
        "note": ("synthetic d2h: sleeps model the host transfer CPU JAX "
                 "lacks; with overlap off it serializes the lanes behind "
                 "the server lock, with overlap on (async dispatch, the "
                 "default) it runs off-lock while the next lane "
                 "dispatches. The hard gate lives in the "
                 "multi_client_coalesced leg"),
        "steps_per_sec_overlap_on": ov_on,
        "steps_per_sec_overlap_off": ov_off,
        "overlap_speedup": ov_on / ov_off,
    }
    return out


def measure_coalesced(quick: bool) -> dict:
    """Server-side request coalescing (runtime/coalesce.py): N concurrent
    clients vs the serialized round-robin relay, on CPU loopback. The
    headline pair injects synthetic wire latency around each round trip
    (the measure_pipelined idiom: sleeps model the reference's k8s
    network, burn no CPU, and let the scheduling win show on a shared
    core — round-robin pays the full wire per step, concurrent clients
    sleep in parallel while the server folds their steps into one
    batched dispatch). Raw loopback numbers ride along with the
    convoying caveat. Self-policing like multi_client_dp: the parity
    invariant (a coalescing server whose every group has one member must
    reproduce the serialized loss series) and a minimum group occupancy
    gate ``valid``."""
    import jax
    import numpy as np

    from split_learning_tpu.models import get_plan
    from split_learning_tpu.runtime import ServerRuntime
    from split_learning_tpu.runtime.client import SplitClientTrainer
    from split_learning_tpu.runtime.multi_client import (
        MultiClientSplitRunner)
    from split_learning_tpu.transport.local import LocalTransport
    from split_learning_tpu.utils import Config

    n_clients = int(os.environ.get("SLT_BENCH_COALESCE_CLIENTS", "4"))
    per_client_batch = 4   # the serving regime coalescing exists for:
    # many small requests, per-dispatch overhead >> per-request compute
    rounds = 6 if quick else 12
    warm = 2
    delay = 0.04
    plan = get_plan(mode="split")
    cfg = Config(mode="split", batch_size=per_client_batch,
                 num_clients=n_clients)
    rs = np.random.RandomState(0)
    x = rs.randn(rounds, n_clients, per_client_batch, 28, 28, 1
                 ).astype(np.float32)
    y = rs.randint(0, 10, (rounds, n_clients, per_client_batch)
                   ).astype(np.int64)

    class _DelayedLocal:
        """Synthetic wire around the in-process hop (sleeps only)."""

        def __init__(self, inner, delay_s):
            self.inner = inner
            self.delay = delay_s
            self.stats = inner.stats

        def split_step(self, *a, **kw):
            time.sleep(self.delay)          # activations down
            res = self.inner.split_step(*a, **kw)
            time.sleep(self.delay)          # gradients back
            return res

        def health(self):
            return self.inner.health()

        def close(self):
            self.inner.close()

    # dispatch watchdog on for every timed run (in-process force, not
    # the env gate): counts XLA compiles, flags steady-state recompiles
    from split_learning_tpu.obs import dispatch_debug
    dd = dispatch_debug.tracker()

    def run(coalesce_max: int, concurrent: bool, wire_delay: float,
            overlap: bool = True, d2h_delay: float = 0.0):
        dispatch_debug.force(True)
        try:
            server = ServerRuntime(
                plan, cfg, jax.random.PRNGKey(0), x[0, 0],
                coalesce_max=coalesce_max,
                overlap=overlap, d2h_delay_s=d2h_delay,
                # generous window: the group should close full when the
                # clients really are concurrent, not on the timer
                coalesce_window_ms=max(2 * wire_delay * 1e3, 5.0))
            runner = MultiClientSplitRunner(
                plan, cfg, jax.random.PRNGKey(1),
                lambda i: _DelayedLocal(LocalTransport(server), wire_delay)
                if wire_delay else LocalTransport(server),
                num_clients=n_clients, concurrent=concurrent)
            try:
                for r in range(warm):
                    runner.train_round(list(zip(x[r], y[r])))
                t0 = time.perf_counter()
                for r in range(warm, rounds):
                    runner.train_round(list(zip(x[r], y[r])))
                dt = time.perf_counter() - t0
                health = server.health()
            finally:
                runner.close()
                server.close()
        finally:
            dispatch_debug.force(False)
        return (rounds - warm) * n_clients / dt, health.get("coalescing")

    # headline pair: synthetic wire, serialized relay vs concurrent +
    # coalescing server
    g0 = dd.gauges()
    sps_serialized, _ = run(1, False, delay)
    sps_coalesced, co = run(n_clients, True, delay)
    # raw loopback pair: no wire to hide, shared cores convoy — reported
    # for honesty, never the headline
    raw_serialized, _ = run(1, False, 0.0)
    raw_coalesced, _ = run(n_clients, True, 0.0)

    # --- async-dispatch overlap pair (PR 5) ---------------------------
    # N concurrent clients against a NON-coalescing server (every step
    # its own lock acquisition — the regime where lock-hold time is the
    # bottleneck). d2h_delay_s models the host transfer CPU JAX lacks
    # (the same honestly-synthetic sleep idiom as the wire): with
    # overlap off the transfer serializes every peer behind the lock,
    # with overlap on it runs on the waiter's thread while the next
    # client's step dispatches.
    d2h_delay = 0.03
    sps_overlap_on, _ = run(1, True, delay, overlap=True,
                            d2h_delay=d2h_delay)
    sps_overlap_off, _ = run(1, True, delay, overlap=False,
                             d2h_delay=d2h_delay)
    overlap_speedup = sps_overlap_on / sps_overlap_off
    g1 = dd.gauges()
    compile_count = {
        "total": g1["compile_count"] - g0["compile_count"],
        "steady_state": (g1["steady_state_recompiles"]
                         - g0["steady_state_recompiles"])}

    # parity guard (exact math, no sleeps): a single client against a
    # coalescing server makes every group a window flush of one, which
    # must reproduce the serialized loss series within f32 tolerance
    parity_steps = 6 if quick else 12
    px = rs.randn(parity_steps, 8, 28, 28, 1).astype(np.float32)
    py = rs.randint(0, 10, (parity_steps, 8)).astype(np.int64)
    pcfg = Config(mode="split", batch_size=8)

    def loss_series(coalesce_max: int):
        server = ServerRuntime(plan, pcfg, jax.random.PRNGKey(0), px[0],
                               coalesce_max=coalesce_max,
                               coalesce_window_ms=1.0)
        client = SplitClientTrainer(plan, pcfg, jax.random.PRNGKey(1),
                                    LocalTransport(server))
        try:
            return [client.train_step(px[i], py[i], i)
                    for i in range(parity_steps)]
        finally:
            server.close()

    diff = float(np.max(np.abs(
        np.asarray(loss_series(1)) - np.asarray(loss_series(n_clients)))))
    parity_tol = 1e-4

    # overlap parity: moving the D2H off the lock cannot change numerics
    # (same jitted program, same application order), so the gate is
    # BIT-identity, not a tolerance — measured on a deterministic
    # single-client sequential run (under concurrency the application
    # order is a thread race in both modes, so only the sequential pair
    # can demand bit-identity)
    def overlap_loss_series(overlap: bool):
        server = ServerRuntime(plan, pcfg, jax.random.PRNGKey(0), px[0],
                               overlap=overlap)
        client = SplitClientTrainer(plan, pcfg, jax.random.PRNGKey(1),
                                    LocalTransport(server))
        try:
            return [client.train_step(px[i], py[i], i)
                    for i in range(parity_steps)]
        finally:
            server.close()

    overlap_loss_diff = float(np.max(np.abs(
        np.asarray(overlap_loss_series(True))
        - np.asarray(overlap_loss_series(False)))))

    # lock-hold accounting: with overlap on, the p50 of the lock-held
    # window (slt_lock_hold_seconds) must sit BELOW the p50 of the
    # overlap-off dispatch span (old taxonomy: dispatch reabsorbs the
    # materialization) — the direct measurement that the D2H left the
    # lock. Histograms populate only while tracing, so this runs as a
    # short traced pair outside every timed window.
    from split_learning_tpu import obs
    from split_learning_tpu.obs.metrics import histogram_percentile

    def traced_metrics(overlap: bool):
        obs.enable()
        try:
            server = ServerRuntime(plan, cfg, jax.random.PRNGKey(0),
                                   x[0, 0], overlap=overlap,
                                   d2h_delay_s=d2h_delay)
            runner = MultiClientSplitRunner(
                plan, cfg, jax.random.PRNGKey(1),
                lambda i: LocalTransport(server),
                num_clients=n_clients, concurrent=True)
            try:
                for r in range(2):
                    runner.train_round(list(zip(x[r], y[r])))
                return server.metrics()
            finally:
                runner.close()
                server.close()
        finally:
            obs.disable()

    hists_on = traced_metrics(True)["histograms"]
    hists_off = traced_metrics(False)["histograms"]
    lock_hold_p50 = histogram_percentile(hists_on.get("lock_hold", {}), 50)
    dispatch_off_p50 = histogram_percentile(hists_off.get("dispatch", {}), 50)

    def _traced_coalesced():
        server = ServerRuntime(plan, cfg, jax.random.PRNGKey(0), x[0, 0],
                               coalesce_max=n_clients,
                               coalesce_window_ms=5.0)
        runner = MultiClientSplitRunner(
            plan, cfg, jax.random.PRNGKey(1),
            lambda i: LocalTransport(server),
            num_clients=n_clients, concurrent=True)
        try:
            for r in range(2):
                runner.train_round(list(zip(x[r], y[r])))
        finally:
            runner.close()
            server.close()

    # SLT_TRACE=path additionally exports the traced steps as a
    # Perfetto-loadable Chrome trace (scripts/trace_report.py reads it)
    phases = _traced_phase_breakdown(_traced_coalesced,
                                     export_path=os.environ.get("SLT_TRACE"))

    occupancy = (co["requests_coalesced"] / co["groups_flushed"]
                 if co and co.get("groups_flushed") else 0.0)
    speedup = sps_coalesced / sps_serialized
    invalid_reason = None
    if diff > parity_tol:
        invalid_reason = (
            f"single-member-group loss series diverges from serialized by "
            f"{diff} (> {parity_tol}): the coalesced step is not "
            "reproducing the serialized math")
    elif occupancy < 2.0:
        invalid_reason = (
            f"mean group occupancy {occupancy:.2f} < 2: the concurrent "
            "clients never actually coalesced, so the speedup column "
            "measures nothing")
    elif overlap_speedup < 1.3:
        invalid_reason = (
            f"overlap speedup {overlap_speedup:.2f} < 1.3 at "
            f"{n_clients} concurrent clients: taking the D2H off the "
            "lock bought nothing, the async-dispatch leg is broken")
    elif overlap_loss_diff != 0.0:
        invalid_reason = (
            f"overlap on-vs-off loss series differ by {overlap_loss_diff} "
            "(must be bit-identical: the D2H's placement cannot change "
            "numerics)")
    elif int(hists_on.get("lock_hold", {}).get("count", 0)) == 0:
        invalid_reason = ("traced overlap-on run recorded no lock_hold "
                          "samples: slt_lock_hold_seconds never populated")
    elif not lock_hold_p50 < dispatch_off_p50:
        invalid_reason = (
            f"lock_hold p50 {lock_hold_p50 * 1e3:.2f} ms is not below "
            f"the no-overlap dispatch p50 {dispatch_off_p50 * 1e3:.2f} ms: "
            "the lock is still covering the materialization")
    elif compile_count["steady_state"]:
        invalid_reason = (
            f"steady_state_recompiles={compile_count['steady_state']:.0f}"
            " != 0: the coalesced/serialized hot loops retrace after "
            "step 2 (the pow2-pad signature set is not holding)")
    return {
        "leg": "multi_client_coalesced",
        "clients": n_clients,
        "per_client_batch": per_client_batch,
        "platform": "cpu+local-loopback",
        "host_cores": os.cpu_count(),
        "one_way_latency_ms": delay * 1e3,
        "note": ("synthetic wire (the measure_pipelined idiom): sleeps "
                 "model the network the loopback lacks; the serialized "
                 "relay pays the full wire per step while concurrent "
                 "clients overlap it and the server batches their steps "
                 "into one dispatch. Semantics: ONE group-mean server "
                 "update per group, not N sequential updates — see "
                 "README 'Request coalescing'"),
        "steps_per_sec_serialized": sps_serialized,
        "steps_per_sec_coalesced": sps_coalesced,
        "speedup_vs_serialized": speedup,
        "compile_count": compile_count,
        "phases": phases,
        "coalescing": co,
        "mean_occupancy": occupancy,
        "loopback_raw": {
            "note": ("no wire to hide on shared cores: convoying, not "
                     "the serving win the coalescer exists for"),
            "steps_per_sec_serialized": raw_serialized,
            "steps_per_sec_coalesced": raw_coalesced,
        },
        "overlap": {
            "note": ("async dispatch (PR 5): N concurrent clients, "
                     "non-coalescing server, synthetic d2h_delay_s "
                     "modeling the host transfer CPU JAX lacks; overlap "
                     "off serializes every client's transfer behind the "
                     "lock, overlap on runs it off-lock on the waiter's "
                     "thread. Loss parity is measured bit-identical on "
                     "a deterministic sequential pair; p50s come from a "
                     "short traced pair outside the timed windows"),
            "d2h_delay_ms": d2h_delay * 1e3,
            "steps_per_sec_overlap_on": sps_overlap_on,
            "steps_per_sec_overlap_off": sps_overlap_off,
            "overlap_speedup": overlap_speedup,
            "loss_max_abs_diff_on_vs_off": overlap_loss_diff,
            "lock_hold_p50_ms": lock_hold_p50 * 1e3,
            "dispatch_p50_ms_no_overlap": dispatch_off_p50 * 1e3,
        },
        "loss_max_abs_diff_vs_serialized": diff,
        "parity_tol": parity_tol,
        "valid": invalid_reason is None,
        "invalid_reason": invalid_reason,
    }


def measure_reply_latency_2bp(quick: bool) -> dict:
    """Decoupled backward / 2BP (PR 10): 4 concurrent clients over the
    synthetic wire against a serialized (non-coalescing) server, coupled
    vs ``--decouple-bwd --apply-lag 2``. The measured quantity is the
    server-visible reply window — wall clock around the in-process
    ``split_step`` hop, wire sleeps excluded — which is what the split
    moves: the coupled server materializes the cut-layer gradient only
    when the fused forward+both-grads+opt program finishes, while the
    decoupled server materializes it after the reply program alone
    (forward + grad-of-acts) and drains the weight updates into the
    clients' wire windows (PiPar's idle-window accounting).

    Workload: the split LM transformer with a wide-vocab server-held
    head (the regime 2BP targets — the weight gradient + optimizer
    apply over the vocab*d_model head dominates the fused step, while
    the reply needs only fwd + the d_model-wide dX chain). The
    reference CNN's conv top half is the opposite regime: its
    transposed-conv dX is the expensive leg, so reply ~ 0.72x fused
    there and decoupling buys little — that asymmetry is the point of
    reporting this leg on the head-heavy shape. Gates (ISSUE 10):
    decoupled reply p50 <= 0.7x coupled; lag=0 loss series
    bit-identical to the coupled path; lag=2 parity within the stated
    nats budget on a converging regime; steady-state recompiles == 0
    across both decoupled programs."""
    import statistics
    import threading

    import jax
    import numpy as np

    from split_learning_tpu.models import get_plan
    from split_learning_tpu.runtime import ServerRuntime
    from split_learning_tpu.runtime.client import SplitClientTrainer
    from split_learning_tpu.transport.local import LocalTransport
    from split_learning_tpu.utils import Config

    n_clients = 4
    per_client_batch = 4
    seq_len = 16
    vocab, d_model = 32768, 128
    rounds = 10 if quick else 16
    warm = 2
    # heterogeneous one-way wires: free-running clients with distinct
    # delays drift out of phase, so arrivals stagger instead of
    # convoying in lockstep bursts — the regime a real fleet sits in.
    # The wires are long enough to keep single-core utilization well
    # under saturation: the deferred applies (and the clients' own
    # backward/opt work — same core) drain inside the sleep windows,
    # so the median decoupled reply is the clean fwd+grad-of-acts
    # program rather than a queue behind earlier device work (device
    # programs are FIFO)
    delays = [0.4 * (1 + 0.4 * i) for i in range(n_clients)]
    lag = 2
    plan = get_plan(model="transformer", mode="split", vocab=vocab,
                    d_model=d_model, num_heads=4, client_depth=1,
                    server_depth=1, lm=True)
    cfg = Config(mode="split", model="transformer",
                 batch_size=per_client_batch, num_clients=n_clients)
    rs = np.random.RandomState(0)
    x = rs.randint(0, vocab, (rounds, n_clients, per_client_batch,
                              seq_len)).astype(np.int32)
    y = rs.randint(0, vocab, (rounds, n_clients, per_client_batch,
                              seq_len)).astype(np.int32)

    class _DelayedLocal:
        """Synthetic wire around the in-process hop; times the hop
        itself (the server-visible reply window) into ``sink``."""

        def __init__(self, inner, delay_s, sink):
            self.inner = inner
            self.delay = delay_s
            self.sink = sink
            self.stats = inner.stats

        def split_step(self, *a, **kw):
            time.sleep(self.delay)          # activations down
            t0 = time.perf_counter()
            res = self.inner.split_step(*a, **kw)
            self.sink.append(time.perf_counter() - t0)
            time.sleep(self.delay)          # gradients back
            return res

        def health(self):
            return self.inner.health()

        def close(self):
            self.inner.close()

    from split_learning_tpu.obs import dispatch_debug
    dd = dispatch_debug.tracker()

    def run(decouple: bool):
        sinks: list = [[] for _ in range(n_clients)]
        dispatch_debug.force(True)
        try:
            server = ServerRuntime(
                plan, cfg, jax.random.PRNGKey(0), x[0, 0],
                decouple_bwd=decouple, apply_lag=lag if decouple else 0)
            clients = [
                SplitClientTrainer(
                    plan, cfg, jax.random.PRNGKey(1 + i),
                    _DelayedLocal(LocalTransport(server), delays[i],
                                  sinks[i]),
                    client_id=i)
                for i in range(n_clients)]
            errs: list = []

            def worker(i: int) -> None:
                try:
                    for r in range(rounds):
                        clients[i].train_step(x[r, i], y[r, i], r)
                except Exception as e:  # surfaced after join
                    errs.append(e)

            try:
                t0 = time.perf_counter()
                threads = [threading.Thread(target=worker, args=(i,))
                           for i in range(n_clients)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                dt = time.perf_counter() - t0
                if errs:
                    raise errs[0]
                health = server.health()
            finally:
                server.close()
        finally:
            dispatch_debug.force(False)
        timed = [s for sink in sinks for s in sink[warm:]]
        sps = (rounds - warm) * n_clients / dt
        return timed, sps, health

    g0 = dd.gauges()
    coupled_lats, sps_coupled, _ = run(False)
    dec_lats, sps_dec, dec_health = run(True)
    g1 = dd.gauges()
    compile_count = {
        "total": g1["compile_count"] - g0["compile_count"],
        "steady_state": (g1["steady_state_recompiles"]
                         - g0["steady_state_recompiles"])}
    reply_p50_coupled = statistics.median(coupled_lats)
    reply_p50_dec = statistics.median(dec_lats)
    reply_ratio = reply_p50_dec / reply_p50_coupled

    # --- numerics: lag=0 bit-identity + lag=2 staleness budget --------
    # a converging regime (4 fixed batches cycled — the loss actually
    # descends) rather than fresh noise every step: staleness on a
    # never-repeating random stream just random-walks the comparison,
    # while the budget below is a statement about trajectories that are
    # going somewhere
    parity_steps = 16
    px = rs.randint(0, vocab, (4, per_client_batch, seq_len)
                    ).astype(np.int32)
    py = rs.randint(0, vocab, (4, per_client_batch, seq_len)
                    ).astype(np.int32)
    pcfg = Config(mode="split", model="transformer",
                  batch_size=per_client_batch)

    def loss_series(decouple: bool, apply_lag: int):
        server = ServerRuntime(plan, pcfg, jax.random.PRNGKey(0), px[0],
                               decouple_bwd=decouple, apply_lag=apply_lag)
        client = SplitClientTrainer(plan, pcfg, jax.random.PRNGKey(1),
                                    LocalTransport(server))
        try:
            return [client.train_step(px[i % 4], py[i % 4], i)
                    for i in range(parity_steps)]
        finally:
            server.close()

    coupled_series = loss_series(False, 0)
    lag0_diff = float(np.max(np.abs(
        np.asarray(coupled_series) - np.asarray(loss_series(True, 0)))))
    lag2_series = loss_series(True, lag)
    # the staleness budget is on where the trajectories END (mean of the
    # last cycle), not the peak pointwise gap mid-descent
    staleness_nats = abs(float(np.mean(lag2_series[-4:]))
                         - float(np.mean(coupled_series[-4:])))
    nats_budget = 0.35

    invalid_reason = None
    if len(dec_lats) != (rounds - warm) * n_clients:
        invalid_reason = (
            f"decoupled run recorded {len(dec_lats)} reply latencies, "
            f"expected {(rounds - warm) * n_clients}")
    elif reply_ratio > 0.7:
        invalid_reason = (
            f"decoupled reply p50 is {reply_ratio:.2f}x coupled "
            f"(> 0.7): the reply program is not materially cheaper than "
            "the fused step, the decoupling bought nothing")
    elif lag0_diff != 0.0:
        invalid_reason = (
            f"lag=0 loss series differs from coupled by {lag0_diff} "
            "(must be bit-identical: same math, same order)")
    elif staleness_nats > nats_budget:
        invalid_reason = (
            f"lag={lag} end-of-run loss is {staleness_nats:.3f} nats "
            f"from coupled (> budget {nats_budget}): staleness is "
            "derailing the trajectory, not perturbing it")
    elif compile_count["steady_state"]:
        invalid_reason = (
            f"steady_state_recompiles={compile_count['steady_state']:.0f}"
            " != 0: reply_grad/deferred_apply retrace after step 2")
    return {
        "leg": "reply_latency_2bp",
        "clients": n_clients,
        "per_client_batch": per_client_batch,
        "model": {"family": "transformer", "lm": True, "vocab": vocab,
                  "d_model": d_model, "seq_len": seq_len,
                  "server_depth": 1},
        "platform": "cpu+local-loopback",
        "host_cores": os.cpu_count(),
        "one_way_latency_ms": [d * 1e3 for d in delays],
        "apply_lag": lag,
        "note": ("2BP reply-first decoupling: reply window = wall clock "
                 "around the in-process split_step hop (wire sleeps "
                 "excluded), 4 concurrent clients, serialized server. "
                 "Coupled replies wait for the fused fwd+grads+opt "
                 "program; decoupled replies wait for fwd+grad-of-acts "
                 "only, the weight updates drain into the wire windows "
                 "(<= apply_lag queued). Workload is the wide-vocab "
                 "LM-head split (weight-update-dominant server half); "
                 "the conv reference model is dX-dominant and would "
                 "show reply ~ 0.72x fused. Staleness semantics: step "
                 "t forwards on weights from step t-k, k <= apply_lag"),
        "reply_p50_ms_coupled": reply_p50_coupled * 1e3,
        "reply_p50_ms_decoupled": reply_p50_dec * 1e3,
        "reply_p50_ratio": reply_ratio,
        "reply_p90_ms_coupled": float(np.percentile(coupled_lats, 90))
        * 1e3,
        "reply_p90_ms_decoupled": float(np.percentile(dec_lats, 90)) * 1e3,
        "steps_per_sec_coupled": sps_coupled,
        "steps_per_sec_decoupled": sps_dec,
        "decoupled_counters": dec_health.get("decoupled_bwd"),
        "compile_count": compile_count,
        "loss_lag0_max_abs_diff": lag0_diff,
        "loss_lag2_staleness_nats": staleness_nats,
        "nats_budget": nats_budget,
        "parity_steps": parity_steps,
        "valid": invalid_reason is None,
        "invalid_reason": invalid_reason,
    }


def measure_mpmd_pipeline(quick: bool) -> dict:
    """K-stage MPMD split pipeline (PR 14): a 3-stage chain
    (client part_a -> stage1 trunk_b -> stage2 head_c, runtime/stage.py
    + runtime/pipeline_runner.py) over synthetic heterogeneous wires,
    GPipe-microbatched M=4 vs the same chain run M=1.

    The wires sleep per direction, scaled by rows/batch (a microbatch
    pays 1/M of the full-batch transfer), so M=1 and M=4 move the same
    byte-seconds — the speedup is pure overlap: the runner keeps one
    forward and one backward worker per wire (full duplex), so with
    M=4 the four microbatch round trips interleave across both hops
    while M=1 serializes fwd1 -> loss2 -> bwd1 end to end. The
    theoretical wire-only ceiling is (4*d1 + 2*d2) / (2*d1) (wire 1
    carries two transfers per microbatch but on independent workers);
    at the chosen 150/100 ms one-way delays the M=4 pipeline lands
    ~1.7x, against a 1.5x gate (ISSUE 14).

    Gates: (a) M=4 steps/sec >= 1.5x the M=1 chain; (b) end-of-run
    loss of the undelayed M=4 lag=1 chain within 0.35 nats of the
    1-cut ServerRuntime split on the same converging 4-batch cycle
    (chain3 re-partitions the exact reference CNN arithmetic, so the
    trajectories must agree); (c) steady-state recompiles == 0 across
    every stage program and the runner's client programs under the
    dispatch watchdog; (d) every hop was delivered: per-stage hop
    counters equal rounds x M exactly (exactly-once, no retry leaks)."""
    import jax
    import numpy as np

    from split_learning_tpu.models import get_plan
    from split_learning_tpu.runtime import ServerRuntime
    from split_learning_tpu.runtime.client import SplitClientTrainer
    from split_learning_tpu.runtime.pipeline_runner import (
        PipelineRunner, bubble_fraction)
    from split_learning_tpu.runtime.stage import StageRuntime
    from split_learning_tpu.transport.local import LocalTransport
    from split_learning_tpu.utils import Config

    batch = 32
    microbatches = 4
    delays = [0.15, 0.10]   # one-way seconds per full batch, hop 1 / hop 2
    rounds = 6 if quick else 10
    warm = 2
    rs = np.random.RandomState(0)
    px = rs.rand(4, batch, 28, 28, 1).astype(np.float32)
    py = rs.randint(0, 10, (4, batch)).astype(np.int32)
    plan3 = get_plan(model="split_cnn_chain3", mode="split")

    class _DelayedHopWire:
        """Synthetic one-way-delay wire around the in-process hop calls;
        sleep scales with rows so a 1/M microbatch pays 1/M the wire."""

        def __init__(self, inner, one_way_s):
            self.inner = inner
            self.d = one_way_s
            self.stats = inner.stats

        def _nap(self, rows):
            if self.d:
                time.sleep(self.d * rows / batch)

        def hop_forward(self, x, step, mb, client_id=0):
            self._nap(len(x))
            r = self.inner.hop_forward(x, step, mb, client_id)
            self._nap(len(x))
            return r

        def hop_backward(self, g, step, mb, client_id=0):
            self._nap(len(g))
            r = self.inner.hop_backward(g, step, mb, client_id)
            self._nap(len(g))
            return r

        def hop_loss(self, x, labels, step, mb, client_id=0):
            self._nap(len(x))
            r = self.inner.hop_loss(x, labels, step, mb, client_id)
            self._nap(len(x))
            return r

        def health(self):
            return self.inner.health()

        def close(self):
            self.inner.close()

    from split_learning_tpu.obs import dispatch_debug
    dd = dispatch_debug.tracker()

    def chain_run(m, lag, n_rounds, wire_delays, timed_from=0):
        """One fresh 3-stage chain; returns (losses, steps/sec over the
        timed window, per-stage reports, per-stage hop counters)."""
        cfg = Config(mode="split", model="split_cnn_chain3",
                     batch_size=batch, num_stages=3, microbatches=m)
        dispatch_debug.force(True)
        try:
            stages = [StageRuntime(plan3, i, cfg, jax.random.PRNGKey(0),
                                   px[0], microbatches=m, apply_lag=lag)
                      for i in (1, 2)]
            ts = [_DelayedHopWire(LocalTransport(s), d)
                  for s, d in zip(stages, wire_delays)]
            runner = PipelineRunner(plan3, cfg, jax.random.PRNGKey(0),
                                    px[0], ts, microbatches=m)
            losses = []
            try:
                for r in range(timed_from):
                    losses.append(runner.step(px[r % 4], py[r % 4], r))
                t0 = time.perf_counter()
                for r in range(timed_from, n_rounds):
                    losses.append(runner.step(px[r % 4], py[r % 4], r))
                dt = time.perf_counter() - t0
                reports = runner.stage_report()
                counters = [s.counters() for s in stages]
            finally:
                runner.close()
                for s in stages:
                    s.close()
        finally:
            dispatch_debug.force(False)
        sps = (n_rounds - timed_from) / dt if dt > 0 else float("inf")
        return losses, sps, reports, counters

    g0 = dd.gauges()
    _, sps_m1, _, _ = chain_run(1, 0, rounds, delays, timed_from=warm)
    _, sps_m4, reports_m4, counters_m4 = chain_run(
        microbatches, 1, rounds, delays, timed_from=warm)
    speedup = sps_m4 / sps_m1

    # --- parity: undelayed chain vs the 1-cut split on a converging
    # regime (4 fixed batches cycled — same rationale as the 2BP leg:
    # the budget is a statement about trajectories going somewhere)
    parity_steps = 16
    chain_series, _, _, _ = chain_run(microbatches, 1, parity_steps, [0, 0])
    plan1 = get_plan(model="split_cnn", mode="split")
    pcfg = Config(mode="split", model="split_cnn", batch_size=batch)
    server = ServerRuntime(plan1, pcfg, jax.random.PRNGKey(0), px[0])
    client = SplitClientTrainer(plan1, pcfg, jax.random.PRNGKey(1),
                                LocalTransport(server))
    try:
        onecut_series = [client.train_step(px[i % 4], py[i % 4], i)
                         for i in range(parity_steps)]
    finally:
        server.close()
    g1 = dd.gauges()
    compile_count = {
        "total": g1["compile_count"] - g0["compile_count"],
        "steady_state": (g1["steady_state_recompiles"]
                         - g0["steady_state_recompiles"])}
    parity_nats = abs(float(np.mean(chain_series[-4:]))
                      - float(np.mean(onecut_series[-4:])))
    nats_budget = 0.35

    # exactly-once bookkeeping: the timed M=4 run made rounds*M forward
    # and backward hops at stage 1 and rounds*M loss hops at stage 2
    want = rounds * microbatches
    hop_tally = {
        "stage1_fwd": counters_m4[0].get("hop_fwd"),
        "stage1_bwd": counters_m4[0].get("hop_bwd"),
        "stage2_loss": counters_m4[1].get("hop_loss"),
    }

    invalid_reason = None
    if speedup < 1.5:
        invalid_reason = (
            f"M={microbatches} pipeline is {speedup:.2f}x the M=1 chain "
            "(< 1.5): microbatch overlap is not hiding the wire")
    elif parity_nats > nats_budget:
        invalid_reason = (
            f"chain end-of-run loss is {parity_nats:.3f} nats from the "
            f"1-cut split (> budget {nats_budget}): the multi-cut path "
            "is not optimizing the same trajectory")
    elif compile_count["steady_state"]:
        invalid_reason = (
            f"steady_state_recompiles={compile_count['steady_state']:.0f}"
            " != 0: a stage or runner program retraces per step")
    elif any(v != want for v in hop_tally.values()):
        invalid_reason = (
            f"hop tally {hop_tally} != {want} per stage/direction: "
            "hops were lost or double-delivered on the clean wire")
    return {
        "leg": "mpmd_pipeline",
        "stages": 3,
        "microbatches": microbatches,
        "batch": batch,
        "model": {"family": "split_cnn_chain3",
                  "partition": ["part_a", "trunk_b", "head_c"]},
        "platform": "cpu+local-loopback",
        "host_cores": os.cpu_count(),
        "one_way_latency_ms": [d * 1e3 for d in delays],
        "apply_lag": 1,
        "note": ("GPipe microbatching over two synthetic wires: per-"
                 "direction sleeps scale with rows so both runs move "
                 "the same byte-seconds and the speedup is pure "
                 "overlap (full-duplex fwd/bwd workers per wire). "
                 "Parity leg runs undelayed against the 1-cut "
                 "ServerRuntime split of the same CNN arithmetic."),
        "steps_per_sec_m1": sps_m1,
        "steps_per_sec_m4": sps_m4,
        "pipeline_speedup": speedup,
        "bubble_fraction_theoretical": bubble_fraction(microbatches, 3),
        "stage_reports_m4": reports_m4,
        "hop_tally": hop_tally,
        "compile_count": compile_count,
        "loss_parity_nats": parity_nats,
        "nats_budget": nats_budget,
        "parity_steps": parity_steps,
        "valid": invalid_reason is None,
        "invalid_reason": invalid_reason,
    }


def measure_mpmd_colocated(quick: bool) -> dict:
    """Device-native co-located chain + 1F1B schedule (PR 16): the same
    3-stage chain as the mpmd_pipeline leg, but driver and StageRuntimes
    share the process and every hop is a DeviceTransport relay — device
    buffers end to end, no codec, no np.asarray — under the 1F1B
    injection schedule (warmup min(S, M), then one forward per drained
    cotangent).

    Measured bubble: jax dispatches stage programs asynchronously, so
    per-wire busy time all drains at the chain's ONE sync point — the
    loss edge, where hop_loss floats the scalar. That worker's busy
    fraction therefore measures whole-chain occupancy over the warm
    window, and its complement is the pipeline's real idle fraction;
    that is the number gated against the GPipe ideal (S-1)/(M+S-1).

    Gates: (a) co-located 1F1B throughput >= 0.25x the fused
    single-program trainer on the same arithmetic (measured ~0.5x on
    the CPU image — the chain pays thread handoffs and per-microbatch
    dispatch that lax.scan fuses away; the budget states how much of
    that overhead is acceptable before the co-located path stops being
    worth offering); (b) warm-window loss-edge bubble strictly below
    the GPipe ideal (S-1)/(M+S-1); (c) hop-path host copies == 0 by
    the explicit ``hop_host_copies`` counter (the CPU transfer guard
    cannot see D2H — same-process views — so the counter is the pin),
    while the HTTP twin counts 2 per hop; (d) the M=1 device chain's
    loss series is bit-identical to an M=1 chain over REAL
    SplitHTTPServer loopback wires (zero-copy relay adds no
    arithmetic); (e) zero steady-state recompiles under the dispatch
    watchdog."""
    import jax
    import numpy as np

    from split_learning_tpu.models import get_plan
    from split_learning_tpu.obs import dispatch_debug, spans
    from split_learning_tpu.runtime.fused import FusedSplitTrainer
    from split_learning_tpu.runtime.pipeline_runner import (
        PipelineRunner, bubble_fraction, onefb_warmup)
    from split_learning_tpu.runtime.stage import StageRuntime
    from split_learning_tpu.transport.device import DeviceTransport
    from split_learning_tpu.transport.http import (
        HttpTransport, SplitHTTPServer)
    from split_learning_tpu.utils import Config

    batch = 32
    microbatches = 4
    rounds = 8 if quick else 14
    warm = 3
    rs = np.random.RandomState(0)
    px = rs.rand(4, batch, 28, 28, 1).astype(np.float32)
    py = rs.randint(0, 10, (4, batch)).astype(np.int32)
    plan3 = get_plan(model="split_cnn_chain3", mode="split")
    dd = dispatch_debug.tracker()

    def chain_run(m, schedule, kind, n_rounds, timed_from):
        """One fresh co-located chain (device or real HTTP-loopback
        wires); returns (losses, steps/sec over the warm window, the
        loss-edge warm bubble, summed hop_host_copies)."""
        cfg = Config(mode="split", model="split_cnn_chain3",
                     batch_size=batch, num_stages=3, microbatches=m,
                     schedule=schedule)
        stages = [StageRuntime(plan3, i, cfg, jax.random.PRNGKey(0),
                               px[0], microbatches=m,
                               apply_lag=1 if m > 1 else 0)
                  for i in (1, 2)]
        servers, ts = [], []
        for s in stages:
            if kind == "device":
                ts.append(DeviceTransport(s))
            else:
                srv = SplitHTTPServer(s).start()
                servers.append(srv)
                ts.append(HttpTransport(srv.url))
        runner = PipelineRunner(plan3, cfg, jax.random.PRNGKey(0),
                                px[0], ts, microbatches=m,
                                schedule=schedule)
        losses = []
        try:
            for r in range(timed_from):
                losses.append(runner.step(px[r % 4], py[r % 4], r))
            # warm-window accounting: busy/wall deltas exclude compile
            loss_edge = runner._fwd_workers[-1]
            busy0, wall0 = loss_edge.busy_s, runner._wall_s
            t0 = time.perf_counter()
            for r in range(timed_from, n_rounds):
                losses.append(runner.step(px[r % 4], py[r % 4], r))
            dt = time.perf_counter() - t0
            d_wall = runner._wall_s - wall0
            edge_bubble = (1.0 - (loss_edge.busy_s - busy0) / d_wall
                           if d_wall > 0 else None)
        finally:
            runner.close()
            for s in stages:
                s.close()
            for srv in servers:
                srv.stop()
        sps = (n_rounds - timed_from) / dt if dt > 0 else float("inf")
        copies = sum(t.stats.counters.get(spans.HOP_HOST_COPIES, 0)
                     for t in ts)
        return losses, sps, edge_bubble, copies

    dispatch_debug.force(True)
    try:
        g0 = dd.gauges()
        _, sps_dev, edge_bubble, dev_copies = chain_run(
            microbatches, "1f1b", "device", rounds, warm)
        g1 = dd.gauges()
    finally:
        dispatch_debug.force(False)
    steady = g1["steady_state_recompiles"] - g0["steady_state_recompiles"]

    # fused single-program twin: the same chain3 arithmetic as ONE jit
    fused = FusedSplitTrainer(plan3, Config(
        mode="split", model="split_cnn_chain3", batch_size=batch,
        num_stages=3), jax.random.PRNGKey(0), px[0])
    for r in range(warm):
        fused.train_step(px[r % 4], py[r % 4])
    t0 = time.perf_counter()
    for r in range(warm, rounds):
        fused.train_step(px[r % 4], py[r % 4])
    sps_fused = (rounds - warm) / (time.perf_counter() - t0)
    fused_ratio = sps_dev / sps_fused
    fused_budget = 0.25

    # M=1 bit-identity: device relay vs REAL HTTP loopback wires
    id_steps = 6
    dev_series, _, _, m1_copies = chain_run(1, "gpipe", "device",
                                            id_steps, 0)
    http_series, _, _, http_copies = chain_run(1, "gpipe", "http",
                                               id_steps, 0)
    # the HTTP twin materializes exactly 2 host buffers per hop
    # (payload out, reply in) x 3 hops x id_steps — the contrast metric
    want_http = 2 * 3 * id_steps

    theo = bubble_fraction(microbatches, 3)
    invalid_reason = None
    if fused_ratio < fused_budget:
        invalid_reason = (
            f"co-located 1F1B chain is {fused_ratio:.2f}x the fused "
            f"single-program trainer (< {fused_budget}): the MPMD "
            "overhead ate the co-location win")
    elif edge_bubble is None or edge_bubble >= theo:
        invalid_reason = (
            f"warm loss-edge bubble {edge_bubble} is not strictly "
            f"below the GPipe ideal {theo:.3f}: the 1F1B chain is "
            "bubble-bound")
    elif dev_copies or m1_copies:
        invalid_reason = (
            f"hop_host_copies={dev_copies + m1_copies} != 0 on the "
            "device path: a hop payload or reply materialized on host")
    elif dev_series != http_series:
        invalid_reason = (
            "M=1 device chain loss series differs from the HTTP "
            "loopback chain: the zero-copy relay changed arithmetic")
    elif http_copies != want_http:
        invalid_reason = (
            f"HTTP twin counted {http_copies} host copies (want "
            f"{want_http}): the contrast accounting drifted")
    elif steady:
        invalid_reason = (
            f"steady_state_recompiles={steady:.0f} != 0: a stage or "
            "shuttle program retraces per step")
    return {
        "leg": "mpmd_colocated",
        "stages": 3,
        "microbatches": microbatches,
        "batch": batch,
        "schedule": "1f1b",
        "warmup_depth": onefb_warmup(microbatches, 3),
        "model": {"family": "split_cnn_chain3",
                  "partition": ["part_a", "trunk_b", "head_c"]},
        "platform": "cpu+in-process",
        "host_cores": os.cpu_count(),
        "note": ("Device-native DeviceTransport relay, 1F1B schedule. "
                 "Bubble is measured at the loss edge — the chain's "
                 "one sync point under async dispatch — over the warm "
                 "window only. The fused-trainer budget states the "
                 "acceptable MPMD overhead on one host; the HTTP twin "
                 "pins the copy contrast (0 vs 2/hop) and the M=1 "
                 "bit-identity."),
        "steps_per_sec_1f1b": sps_dev,
        "steps_per_sec_fused": sps_fused,
        "fused_ratio": fused_ratio,
        "fused_budget": fused_budget,
        "bubble_measured_loss_edge": edge_bubble,
        "bubble_theoretical_gpipe": theo,
        "hop_host_copies_device": dev_copies + m1_copies,
        "hop_host_copies_http_twin": http_copies,
        "m1_bit_identical_vs_http": dev_series == http_series,
        "steady_state_recompiles": steady,
        "valid": invalid_reason is None,
        "invalid_reason": invalid_reason,
    }


def measure_mpmd_compressed(quick: bool) -> dict:
    """Compressed hop wires on the K-stage chain (PR 18): the same
    3-stage split_cnn_chain3 over REAL SplitHTTPServer loopback wires,
    M=4, run dense ("none") vs topk8 vs clapping at density 0.25 on a
    converging 4-batch cycle. Every run drives its own fresh chain —
    parity is measured through each run's own wire, end loss against
    the dense run's. Density 0.25 is the measured knee: it still
    clears 10x on the wire (values + bitmap overhead) while holding
    end-loss inside the nats budget; per-step loss on the 4-batch
    cycle is ~0.4-nat noisy, so end loss averages the last 8 steps.

    Gates: (a) topk8 AND clapping hop bytes (request+reply, the
    transports' own byte counters) >= 10x below the dense chain's over
    the same step count; (b) each compressed run's end-of-run loss
    within the absolute-nats budget of the dense run's (error feedback
    — persistent ledger or Clapping's storage-free fold — must keep
    the sparsified trajectory converging with the dense one); (c) zero
    steady-state recompiles under the dispatch watchdog (packed/dense
    payload shapes are stable per wire); (d) Clapping stages export NO
    wire-EF ledger in their runtime extras while topk8 stages do —
    the storage-free contract, measured not asserted."""
    import jax
    import numpy as np

    from split_learning_tpu.models import get_plan
    from split_learning_tpu.obs import dispatch_debug
    from split_learning_tpu.runtime.pipeline_runner import PipelineRunner
    from split_learning_tpu.runtime.stage import StageRuntime
    from split_learning_tpu.transport.http import (
        HttpTransport, SplitHTTPServer)
    from split_learning_tpu.utils import Config

    batch = 32
    microbatches = 4
    density = 0.25
    steps = 16 if quick else 24
    rs = np.random.RandomState(0)
    px = rs.rand(4, batch, 28, 28, 1).astype(np.float32)
    py = rs.randint(0, 10, (4, batch)).astype(np.int32)
    plan3 = get_plan(model="split_cnn_chain3", mode="split")
    dd = dispatch_debug.tracker()

    def chain_run(compress):
        """One fresh HTTP chain; returns (losses, total hop wire bytes
        across both hops and directions, per-stage extras sidecars)."""
        cfg = Config(mode="split", model="split_cnn_chain3",
                     batch_size=batch, num_stages=3,
                     microbatches=microbatches)
        ef_mode = "clapping" if compress == "clapping" else "topk8"
        stages = [StageRuntime(plan3, i, cfg, jax.random.PRNGKey(0),
                               px[0], microbatches=microbatches,
                               apply_lag=1, ef_mode=ef_mode)
                  for i in (1, 2)]
        servers, ts = [], []
        for s in stages:
            srv = SplitHTTPServer(s, compress=compress,
                                  density=density).start()
            servers.append(srv)
            ts.append(HttpTransport(srv.url, compress=compress,
                                    density=density))
        runner = PipelineRunner(plan3, cfg, jax.random.PRNGKey(0),
                                px[0], ts, microbatches=microbatches)
        losses = []
        try:
            for r in range(steps):
                losses.append(runner.step(px[r % 4], py[r % 4], r))
            extras = [s.export_runtime_extras(steps) for s in stages]
        finally:
            runner.close()
            for s in stages:
                s.close()
            for srv in servers:
                srv.stop()
        wire_bytes = sum(t.stats.bytes_sent + t.stats.bytes_received
                         for t in ts)
        return losses, wire_bytes, extras

    dispatch_debug.force(True)
    try:
        g0 = dd.gauges()
        dense_series, dense_bytes, _ = chain_run("none")
        topk8_series, topk8_bytes, topk8_extras = chain_run("topk8")
        clap_series, clap_bytes, clap_extras = chain_run("clapping")
        g1 = dd.gauges()
    finally:
        dispatch_debug.force(False)
    steady = g1["steady_state_recompiles"] - g0["steady_state_recompiles"]

    def end_loss(series):
        return float(np.mean(series[-8:]))

    nats_budget = 0.35
    parity = {
        "topk8": abs(end_loss(topk8_series) - end_loss(dense_series)),
        "clapping": abs(end_loss(clap_series) - end_loss(dense_series)),
    }
    reduction = {
        "topk8": dense_bytes / topk8_bytes if topk8_bytes else None,
        "clapping": dense_bytes / clap_bytes if clap_bytes else None,
    }
    # the storage-free contract: a clapping stage's extras sidecar
    # carries no wire_ef entry at all, a topk8 stage's does
    topk8_ledger = all("wire_ef" in e for e in topk8_extras)
    clap_ledger_free = all("wire_ef" not in e for e in clap_extras)

    invalid_reason = None
    low = [k for k, v in reduction.items() if not v or v < 10.0]
    drift = [k for k, v in parity.items() if v > nats_budget]
    if low:
        invalid_reason = (
            f"hop byte reduction below 10x for {low} "
            f"(got {reduction}): the compressed chain is not "
            "an order of magnitude lighter on the wire")
    elif drift:
        invalid_reason = (
            f"end-loss parity above the {nats_budget}-nat budget for "
            f"{drift} (got {parity}): error feedback is not keeping "
            "the sparsified trajectory with the dense one")
    elif steady:
        invalid_reason = (
            f"steady_state_recompiles={steady:.0f} != 0: a packed "
            "payload shape is unstable and retraces per step")
    elif not topk8_ledger or not clap_ledger_free:
        invalid_reason = (
            f"EF ledger contract broken (topk8 exports ledger: "
            f"{topk8_ledger}, clapping ledger-free: {clap_ledger_free})")
    return {
        "leg": "mpmd_compressed",
        "stages": 3,
        "microbatches": microbatches,
        "batch": batch,
        "density": density,
        "steps": steps,
        "model": {"family": "split_cnn_chain3",
                  "partition": ["part_a", "trunk_b", "head_c"]},
        "platform": "cpu+http-loopback",
        "host_cores": os.cpu_count(),
        "note": ("Dense vs topk8 vs clapping over real HTTP loopback "
                 "hop wires, each run through its own chain. Bytes are "
                 "the transports' request+reply body counters; parity "
                 "is absolute nats against the dense run's end loss."),
        "hop_wire_bytes": {"dense": dense_bytes, "topk8": topk8_bytes,
                           "clapping": clap_bytes},
        "hop_byte_reduction": reduction,
        "loss_parity_nats": parity,
        "nats_budget": nats_budget,
        "clapping_extras_ledger_free": clap_ledger_free,
        "topk8_extras_carry_ledger": topk8_ledger,
        "steady_state_recompiles": steady,
        "valid": invalid_reason is None,
        "invalid_reason": invalid_reason,
    }


def measure_fleet_telemetry(quick: bool) -> dict:
    """Fleet telemetry plane (PR 17): three sub-measurements over the
    obs/telemetry.py ring and obs/federate.py collector.

    (a) OVERHEAD — the mpmd_colocated chain arithmetic (3-stage
    co-located device chain, 1F1B, M=4) run with telemetry off and on
    (hub registry + three per-party rings + 2x-interval sampler
    threads), best-of-two each, gated at <= 2% steps/sec overhead: the
    plane is scrape-time-only, so turning it on must not tax the step
    path beyond one None-check per hop/step.

    (b) ATTRIBUTION — the same chain with stage 1's forward compute
    synthetically slowed (a sleep inside the stage's measured dispatch
    window, so the slowdown is genuinely *compute* from every party's
    view; big enough to dominate the chain's real compute, which async
    dispatch drains at the hub's loss edge and books as wire);
    per-party ring dumps are merged by FleetCollector and the
    per-window critical path must name stage1 in >= 90% of the warm
    attributed windows (the compile-heavy warmup flush window is
    excluded and says so).

    (c) BURN — a 3-replica ReplicaGroup fleet under an unattainable
    0.5 ms latency SLO: the multi-window burn-rate pair must fire, the
    windowed dispatch-p99 trajectory must be non-empty, and the group
    scrape must render per-replica ``{replica="i"}`` labeled series."""
    import jax
    import numpy as np

    from split_learning_tpu.models import get_plan
    from split_learning_tpu.obs import spans
    from split_learning_tpu.obs import telemetry as obs_telemetry
    from split_learning_tpu.obs import trace as obs_trace
    from split_learning_tpu.obs.federate import FleetCollector
    from split_learning_tpu.obs.metrics import (
        Registry, render_prometheus)
    from split_learning_tpu.runtime.fleet import FleetConfig, run_fleet
    from split_learning_tpu.runtime.pipeline_runner import PipelineRunner
    from split_learning_tpu.runtime.replica import maybe_replicate
    from split_learning_tpu.runtime.server import ServerRuntime
    from split_learning_tpu.runtime.stage import StageRuntime
    from split_learning_tpu.transport.device import DeviceTransport
    from split_learning_tpu.transport.local import LocalTransport
    from split_learning_tpu.utils import Config

    batch = 32
    microbatches = 4
    rounds = 10 if quick else 14
    warm = 3
    interval_s = 0.2
    rs = np.random.RandomState(0)
    px = rs.rand(4, batch, 28, 28, 1).astype(np.float32)
    py = rs.randint(0, 10, (4, batch)).astype(np.int32)
    plan3 = get_plan(model="split_cnn_chain3", mode="split")
    had_tracer = obs_trace.get_tracer() is not None

    def build_chain(slow_stage_ms=0.0):
        cfg = Config(mode="split", model="split_cnn_chain3",
                     batch_size=batch, num_stages=3,
                     microbatches=microbatches, schedule="1f1b")
        stages = [StageRuntime(plan3, i, cfg, jax.random.PRNGKey(0),
                               px[0], microbatches=microbatches,
                               apply_lag=1)
                  for i in (1, 2)]
        if slow_stage_ms > 0:
            # the synthetic-slow party is the MIDDLE stage: the last
            # stage's training forward runs inside hop_loss, so only
            # stage 1's _fwd sits on the hop_forward dispatch window.
            # The sleep runs inside that measured window — compute, not
            # wire, from every party's view. It must also dominate the
            # chain's real compute, which async dispatch drains at the
            # hub's loss edge and the model honestly books as wire.
            orig_fwd = stages[0]._fwd

            def slow_fwd(params, x, _orig=orig_fwd):
                time.sleep(slow_stage_ms / 1e3)
                return _orig(params, x)
            stages[0]._fwd = slow_fwd
        ts = [DeviceTransport(s) for s in stages]
        runner = PipelineRunner(plan3, cfg, jax.random.PRNGKey(0),
                                px[0], ts, microbatches=microbatches,
                                schedule="1f1b")
        return runner, stages

    def make_rings(runner, stages):
        """Hub registry + three per-party rings (created back to back so
        their window grids align by index — the federation contract)."""
        hub_reg = Registry()
        runner.telemetry_registry = hub_reg
        rings = [obs_telemetry.TelemetryRing(
            hub_reg.snapshot, party="hub", interval_s=interval_s,
            capacity=600)]
        for s in stages:
            rings.append(obs_telemetry.TelemetryRing(
                s.metrics, party=f"stage{s.stage_index}",
                interval_s=interval_s, capacity=600))
        return rings

    # -- (a) overhead: off -> on -> off phases on ONE warm chain ------- #
    # one chain instance (one set of compiled programs) measures all
    # three phases, so the on-vs-off delta is the telemetry plane alone
    # — rebuilding the chain per arm was dominated by compile/thermal
    # variance several times the 2% budget
    runner, stages = build_chain()
    step_no = 0
    rings = []

    def timed_rounds(n: int) -> float:
        nonlocal step_no
        t0 = time.perf_counter()
        for _ in range(n):
            runner.step(px[step_no % 4], py[step_no % 4], step_no)
            step_no += 1
        dt = time.perf_counter() - t0
        return n / dt if dt > 0 else float("inf")

    try:
        for _ in range(warm):
            runner.step(px[step_no % 4], py[step_no % 4], step_no)
            step_no += 1
        sps_on_arm = []
        sps_off_arm = [timed_rounds(rounds)]
        for _ in range(2):      # off->on->off->on: best-of-two each arm
            if obs_trace.get_tracer() is None:
                obs_trace.enable()
            rings = make_rings(runner, stages)
            for ring in rings:
                ring.start_sampler()
            sps_on_arm.append(timed_rounds(rounds))
            for ring in rings:
                ring.close()
            rings = []
            runner.telemetry_registry = None
            if not had_tracer:
                obs_trace.disable()
            sps_off_arm.append(timed_rounds(rounds))
    finally:
        for ring in rings:
            ring.close()
        runner.close()
        for s in stages:
            s.close()
        if not had_tracer and obs_trace.get_tracer() is not None:
            obs_trace.disable()
    sps_off = max(sps_off_arm)
    sps_on = max(sps_on_arm)
    overhead = 1.0 - sps_on / sps_off if sps_off > 0 else None
    overhead_budget = 0.02

    # -- (b) attribution: slow stage1, federate, critical path --------- #
    slow_ms = 80.0
    if obs_trace.get_tracer() is None:
        obs_trace.enable()
    runner, stages = build_chain(slow_stage_ms=slow_ms)
    try:
        rings = make_rings(runner, stages)
        for r in range(2):      # warmup (compiles) ...
            runner.step(px[r % 4], py[r % 4], r)
        for ring in rings:      # ... flushed into one excluded window
            ring.advance(force=True)
        warm_idx = rings[0]._next_index
        for r in range(2, 2 + rounds):
            runner.step(px[r % 4], py[r % 4], r)
            for ring in rings:
                ring.advance()
        for ring in rings:
            ring.advance(force=True)
        parties = [{"role": "hub", "stage": None, "replica": None,
                    "dump": rings[0].dump()}]
        for s, ring in zip(stages, rings[1:]):
            parties.append({"role": "stage", "stage": s.stage_index,
                            "replica": None, "dump": ring.dump()})
    finally:
        runner.close()
        for s in stages:
            s.close()
        if not had_tracer:
            obs_trace.disable()
    view = FleetCollector(parties).collect()
    cp = [e for e in (view.get("critical_path") or [])
          if e["index"] >= warm_idx]
    hits = sum(1 for e in cp if e["bottleneck"]["party"] == "stage1")
    accuracy = hits / len(cp) if cp else 0.0
    accuracy_floor = 0.9
    bottlenecks: dict = {}
    for e in cp:
        p = e["bottleneck"]["party"]
        bottlenecks[p] = bottlenecks.get(p, 0) + 1

    # -- (c) burn: 3-replica group under an unattainable SLO ----------- #
    n_clients = 12 if quick else 24
    steps_pc = 2
    fbatch = 8
    plan = get_plan(mode="split")
    fcfg_model = Config(mode="split", batch_size=fbatch,
                        num_clients=1 << 20)
    sample = np.zeros((fbatch, 28, 28, 1), np.float32)

    def make_replica(_idx: int) -> ServerRuntime:
        return ServerRuntime(plan, fcfg_model, jax.random.PRNGKey(0),
                             sample, strict_steps=True, coalesce_max=4,
                             coalesce_window_ms=50.0,
                             batching="continuous")

    if obs_trace.get_tracer() is None:
        obs_trace.enable()
    group = maybe_replicate(make_replica, 3)

    def group_snapshot():
        """Group counters/gauges/labeled + the live replicas' cumulative
        histograms merged bucket-wise, so the latency SLO objective sees
        the fleet's dispatch distribution in one window stream."""
        snap = group.metrics()
        hists: dict = {}
        for rep in group.replicas:
            for name, h in rep.metrics().get("histograms", {}).items():
                cur = hists.get(name)
                if cur is None:
                    hists[name] = {
                        "buckets": h["buckets"],
                        "cumulative": list(h["cumulative"]),
                        "sum": h["sum"], "count": h["count"]}
                else:
                    cur["cumulative"] = [
                        a + b for a, b in zip(cur["cumulative"],
                                              h["cumulative"])]
                    cur["sum"] += h["sum"]
                    cur["count"] += h["count"]
        snap["histograms"] = hists
        return snap

    tracker = obs_telemetry.tracker_from_config(
        {"slo_ms": 0.5, "burn_threshold": 1.0})
    ring = obs_telemetry.TelemetryRing(
        group_snapshot, party="server", interval_s=0.25, capacity=600,
        slo=tracker)
    try:
        ring.start_sampler()
        fcfg = FleetConfig(n_clients=n_clients, tenants=1,
                           steps_per_client=steps_pc, arrival="burst",
                           rate_hz=0.05, burst_size=2, seed=1,
                           workers=16, batch=fbatch)
        res = run_fleet(fcfg, lambda cid: LocalTransport(group),
                        group=group)
        ring.advance(force=True)
        labeled_series = len(group_snapshot().get("labeled") or [])
        exposition = render_prometheus(group_snapshot())
    finally:
        ring.close()
        group.close()
        if not had_tracer:
            obs_trace.disable()
    windows = ring.windows()
    p99s = [w["percentiles"][spans.DISPATCH]["p99"]
            for w in windows
            if spans.DISPATCH in w.get("percentiles", {})]
    burn_peak = None
    for w in windows:
        for name, v in w.get("gauges", {}).items():
            if name.startswith(spans.SLO_BURN_FAST):
                burn_peak = v if burn_peak is None else max(burn_peak, v)
    alerts = tracker.alerts()
    fired = any(a["state"] == "firing" for a in alerts)
    fleet_completed = int(res.counters.get("fleet_steps_total", 0))

    invalid_reason = None
    if overhead is None or overhead > overhead_budget:
        invalid_reason = (
            f"telemetry-on chain is {overhead} slower than off "
            f"(> {overhead_budget:.0%} budget): the plane leaked onto "
            "the step path")
    elif not cp:
        invalid_reason = ("critical path attributed zero warm windows: "
                          "the federated view never saw a hub step")
    elif accuracy < accuracy_floor:
        invalid_reason = (
            f"attribution named the synthetic-slow stage1 in only "
            f"{accuracy:.0%} of {len(cp)} warm windows "
            f"(floor {accuracy_floor:.0%}); histogram={bottlenecks}")
    elif not fired:
        invalid_reason = ("burn-rate pair never fired under an "
                          "unattainable 0.5 ms SLO")
    elif not p99s:
        invalid_reason = ("no windowed dispatch p99 was recorded for "
                          "the replica fleet")
    elif labeled_series == 0 or 'replica="' not in exposition:
        invalid_reason = ("group scrape rendered no per-replica "
                          "labeled series")
    elif fleet_completed != n_clients * steps_pc:
        invalid_reason = (
            f"burn fleet completed {fleet_completed}/"
            f"{n_clients * steps_pc} steps")
    return {
        "leg": "fleet_telemetry",
        "stages": 3,
        "replicas": 3,
        "microbatches": microbatches,
        "batch": batch,
        "interval_s": interval_s,
        "platform": "cpu+in-process",
        "host_cores": os.cpu_count(),
        "note": ("Scrape-time telemetry plane: (a) on-vs-off steps/sec "
                 "on the co-located 3-stage chain, best-of-two each; "
                 "(b) per-window critical path over federated per-party "
                 "rings with stage1's forward compute slowed inside its "
                 "measured dispatch window, warmup flush excluded; "
                 "(c) 3-replica group under an unattainable SLO — the "
                 "burn pair must fire and the scrape must carry "
                 "per-replica labels."),
        "telemetry_overhead": {
            "steps_per_sec_off": sps_off,
            "steps_per_sec_on": sps_on,
            "overhead_frac": overhead,
            "budget_frac": overhead_budget,
        },
        "attribution": {
            "slow_party": "stage1",
            "slow_ms_per_fwd": slow_ms,
            "windows_attributed": len(cp),
            "accuracy": accuracy,
            "accuracy_floor": accuracy_floor,
            "bottleneck_histogram": bottlenecks,
        },
        "slo_burn": {
            "windows": len(windows),
            "slo_ms": 0.5,
            "threshold": 1.0,
            "p99_ms_windows": len(p99s),
            "p99_ms_last": p99s[-1] if p99s else None,
            "burn_peak": burn_peak,
            "fired": fired,
            "alerts": alerts,
        },
        "per_replica_labeled_series": labeled_series,
        "valid": invalid_reason is None,
        "invalid_reason": invalid_reason,
    }


def measure_sharded_server(quick: bool) -> dict:
    """Sharded server runtime (PR 11): the server half pjit-compiled
    over the virtual host mesh, with mesh-aware coalesced dispatch.
    Runs on the forced 8-device CPU host topology
    (XLA_FLAGS=--xla_force_host_platform_device_count=8).

    The throughput pair is BATCH-CEILING-RELATIVE, and says so: a real
    multi-chip mesh wins by computing shards in parallel, which N
    virtual devices on one core cannot show (a data-sharded program
    here is marginally SLOWER per row than its single-device twin —
    partitioning overhead, same core). What one core CAN honestly show
    is the serving-side consequence of sharding: at a fixed per-DEVICE
    row ceiling, a data=2 server admits groups twice the size, so the
    same request stream drains in half the dispatches and the fixed
    per-dispatch cost (lock window, host transfer — modeled by the
    d2h_delay_s sleep, the measure_coalesced idiom) is amortized twice
    as far. Both runs use the same total requests and the same
    per-device rows per group (coalesce_max=C at data=1 vs 2C at
    data=2). Self-policing gates: data=2 throughput strictly above
    data=1; mesh=1 loss series BIT-identical to the unsharded server;
    data=2 parity within float tolerance; data=2 groups actually bigger
    (occupancy); steady-state recompiles == 0; mesh shape + per-program
    flops accounting present in trace_metadata (MFU itself is honestly
    None on CPU — no published peak)."""
    # must precede the first jax import: the virtual topology is fixed
    # at backend init
    from split_learning_tpu.parallel.mesh import ensure_host_device_count
    ensure_host_device_count(8)
    import jax
    import numpy as np

    from split_learning_tpu.models import get_plan
    from split_learning_tpu.parallel.mesh import make_host_mesh
    from split_learning_tpu.runtime import ServerRuntime
    from split_learning_tpu.runtime.client import SplitClientTrainer
    from split_learning_tpu.runtime.multi_client import (
        MultiClientSplitRunner)
    from split_learning_tpu.transport.local import LocalTransport
    from split_learning_tpu.utils import Config

    if jax.device_count() < 2:
        return {
            "leg": "sharded_server",
            "platform": "cpu+local-loopback",
            "valid": False,
            "invalid_reason": (
                f"host topology has {jax.device_count()} device(s); the "
                "leg needs XLA_FLAGS=--xla_force_host_platform_device_"
                "count=8 (or SLT_HOST_DEVICES=8) set before jax "
                "initializes"),
        }

    n_clients = 8
    per_client_batch = 4
    base_cmax = 4          # data=1 ceiling: 4 requests x 4 rows / device
    rounds = 8 if quick else 14
    warm = 2
    # short wire, expensive dispatch: the leg's claim is per-dispatch
    # fixed-cost amortization, so the synthetic per-dispatch transfer
    # (d2h_delay_s — the measure_coalesced idiom, here with
    # d2h_single_channel=True so concurrent groups queue on one
    # simulated DMA channel instead of overlapping their sleeps) is
    # sized to dominate the wire. data=1 pays it twice per round (two
    # ceiling-bound groups), data=2 once — but the second group's
    # COMPUTE hides under the first group's transfer, so the per-round
    # margin is only D - C/2 (D = d2h_delay, C ~ 0.2 s per-round
    # compute on this model/batch): D must sit well above C/2 or the
    # gate measures thread phasing instead of amortization.
    delay = 0.02
    d2h_delay = 0.2        # synthetic per-dispatch host-transfer cost
    plan = get_plan(mode="split")
    cfg = Config(mode="split", batch_size=per_client_batch,
                 num_clients=n_clients)
    rs = np.random.RandomState(0)
    x = rs.randn(rounds, n_clients, per_client_batch, 28, 28, 1
                 ).astype(np.float32)
    y = rs.randint(0, 10, (rounds, n_clients, per_client_batch)
                   ).astype(np.int64)

    class _DelayedLocal:
        """Synthetic wire around the in-process hop (sleeps only)."""

        def __init__(self, inner, delay_s):
            self.inner = inner
            self.delay = delay_s
            self.stats = inner.stats

        def split_step(self, *a, **kw):
            time.sleep(self.delay)          # activations down
            res = self.inner.split_step(*a, **kw)
            time.sleep(self.delay)          # gradients back
            return res

        def health(self):
            return self.inner.health()

        def close(self):
            self.inner.close()

    from split_learning_tpu.obs import dispatch_debug
    dd = dispatch_debug.tracker()

    def run(mesh, coalesce_max):
        dispatch_debug.force(True)
        try:
            server = ServerRuntime(
                plan, cfg, jax.random.PRNGKey(0), x[0, 0], mesh=mesh,
                coalesce_max=coalesce_max, d2h_delay_s=d2h_delay,
                d2h_single_channel=True,
                coalesce_window_ms=max(2 * delay * 1e3, 5.0))
            runner = MultiClientSplitRunner(
                plan, cfg, jax.random.PRNGKey(1),
                lambda i: _DelayedLocal(LocalTransport(server), delay),
                num_clients=n_clients, concurrent=True)
            try:
                for r in range(warm):
                    runner.train_round(list(zip(x[r], y[r])))
                t0 = time.perf_counter()
                for r in range(warm, rounds):
                    runner.train_round(list(zip(x[r], y[r])))
                dt = time.perf_counter() - t0
                health = server.health()
            finally:
                runner.close()
                server.close()
        finally:
            dispatch_debug.force(False)
        return (rounds - warm) * n_clients / dt, health.get("coalescing")

    g0 = dd.gauges()
    sps_d1, co1 = run(None, base_cmax)
    sps_d2, co2 = run(make_host_mesh(data=2), 2 * base_cmax)
    g1 = dd.gauges()
    compile_count = {
        "total": g1["compile_count"] - g0["compile_count"],
        "steady_state": (g1["steady_state_recompiles"]
                         - g0["steady_state_recompiles"])}

    def occupancy(co):
        return (co["requests_coalesced"] / co["groups_flushed"]
                if co and co.get("groups_flushed") else 0.0)

    occ_d1, occ_d2 = occupancy(co1), occupancy(co2)
    speedup = sps_d2 / sps_d1 if sps_d1 else 0.0

    # --- numerics: mesh=1 bit-identity + data=2 float parity ----------
    # serialized single client, exact math, no sleeps; batch of 8 rows
    # tiles the data axis without the coalescer's padding in the loop
    parity_steps = 6 if quick else 12
    px = rs.randn(parity_steps, 8, 28, 28, 1).astype(np.float32)
    py = rs.randint(0, 10, (parity_steps, 8)).astype(np.int64)
    pcfg = Config(mode="split", batch_size=8)

    def loss_series(mesh):
        server = ServerRuntime(plan, pcfg, jax.random.PRNGKey(0), px[0],
                               mesh=mesh)
        client = SplitClientTrainer(plan, pcfg, jax.random.PRNGKey(1),
                                    LocalTransport(server))
        try:
            return [client.train_step(px[i], py[i], i)
                    for i in range(parity_steps)]
        finally:
            server.close()

    base_series = loss_series(None)
    m1_diff = float(np.max(np.abs(
        np.asarray(base_series)
        - np.asarray(loss_series(make_host_mesh(data=1))))))
    d2_diff = float(np.max(np.abs(
        np.asarray(base_series)
        - np.asarray(loss_series(make_host_mesh(data=2))))))
    parity_tol = 5e-4

    # --- traced metadata run: mesh shape + per-program flops ----------
    # (MFU accounting is tr-gated, so it needs its own short traced run
    # outside every timed window)
    from split_learning_tpu import obs
    obs.enable()
    try:
        server = ServerRuntime(
            plan, cfg, jax.random.PRNGKey(0), x[0, 0],
            mesh=make_host_mesh(data=2), coalesce_max=2 * base_cmax,
            coalesce_window_ms=5.0)
        runner = MultiClientSplitRunner(
            plan, cfg, jax.random.PRNGKey(1),
            lambda i: LocalTransport(server),
            num_clients=n_clients, concurrent=True)
        try:
            for r in range(2):
                runner.train_round(list(zip(x[r], y[r])))
            meta = server.trace_metadata()
        finally:
            runner.close()
            server.close()
    finally:
        tr = obs.disable()
    trace_path = os.environ.get("SLT_TRACE")
    if tr is not None and trace_path:
        tr.export_chrome(trace_path, metadata=meta)

    invalid_reason = None
    if m1_diff != 0.0:
        invalid_reason = (
            f"mesh=1 loss series differs from unsharded by {m1_diff} "
            "(must be bit-identical: a size-1 mesh compiles the legacy "
            "programs)")
    elif d2_diff > parity_tol:
        invalid_reason = (
            f"data=2 loss series diverges from unsharded by {d2_diff} "
            f"(> {parity_tol}): the sharded programs are not reproducing "
            "the single-device math")
    elif not occ_d2 > occ_d1:
        invalid_reason = (
            f"data=2 mean occupancy {occ_d2:.2f} <= data=1 {occ_d1:.2f}: "
            "the widened ceiling never admitted bigger groups, the "
            "throughput column measures nothing")
    elif not sps_d2 > sps_d1:
        invalid_reason = (
            f"data=2 throughput {sps_d2:.2f} <= data=1 {sps_d1:.2f} "
            "steps/s at the same per-device row ceiling: halving the "
            "dispatch count bought nothing")
    elif compile_count["steady_state"]:
        invalid_reason = (
            f"steady_state_recompiles={compile_count['steady_state']:.0f}"
            " != 0: the sharded hot loops retrace after step 2")
    elif meta.get("mesh", {}).get("data") != 2 or not meta.get("programs"):
        invalid_reason = (
            "trace_metadata is missing the mesh shape or the per-program "
            "flops accounting — the MFU/mesh export is broken")
    return {
        "leg": "sharded_server",
        "clients": n_clients,
        "per_client_batch": per_client_batch,
        "coalesce_max": {"data1": base_cmax, "data2": 2 * base_cmax},
        "mesh": meta.get("mesh"),
        "platform": "cpu+local-loopback",
        "host_cores": os.cpu_count(),
        "one_way_latency_ms": delay * 1e3,
        "d2h_delay_ms": d2h_delay * 1e3,
        "batch_ceiling_relative": True,
        "note": ("batch-ceiling-relative: N virtual devices share one "
                 "core, so the device-parallel compute win cannot show "
                 "here (a sharded program is marginally slower per row). "
                 "The gated claim is the serving consequence: at a fixed "
                 "per-device row ceiling a data=2 server admits "
                 "double-size groups, draining the same request stream "
                 "in half the dispatches and amortizing the fixed "
                 "per-dispatch cost (lock window + synthetic d2h sleep) "
                 "twice as far. MFU is None on CPU (no published peak) "
                 "by design — never 0"),
        "steps_per_sec_data1": sps_d1,
        "steps_per_sec_data2": sps_d2,
        "speedup_data2_vs_data1": speedup,
        "mean_occupancy_data1": occ_d1,
        "mean_occupancy_data2": occ_d2,
        "compile_count": compile_count,
        "loss_mesh1_max_abs_diff": m1_diff,
        "loss_data2_max_abs_diff": d2_diff,
        "parity_tol": parity_tol,
        "gather_bytes": meta.get("gather_bytes"),
        "peak_flops_per_device": meta.get("peak_flops_per_device"),
        "programs": meta.get("programs"),
        "valid": invalid_reason is None,
        "invalid_reason": invalid_reason,
    }


def measure_composed_topology(quick: bool) -> dict:
    """Composable party runtime (ISSUE 20): a 3-stage MPMD chain whose
    MIDDLE stage runs per-stage pjit over the virtual host mesh, plus
    the replicated x sharded x K-stage composition. Runs on the forced
    8-device CPU host topology.

    The throughput pair is BATCH-CEILING-RELATIVE like the
    sharded_server leg, and says so: one core cannot show the
    device-parallel compute win (a data-sharded stage program is
    marginally SLOWER per row — partitioning overhead, same core).
    What one core CAN honestly show is the pipeline consequence of a
    wider stage: at a fixed per-DEVICE rows-per-microbatch ceiling on
    the sharded stage, a data=2 middle stage admits microbatches twice
    the size, so the same step's rows drain in half the microbatches —
    half the hop round-trips — and the fixed per-hop wire cost (the
    synthetic sleep, measure_sharded_server's d2h idiom moved onto the
    wire) is amortized twice as far. Both runs move the same total rows
    per step at the same per-device rows per microbatch (M=4 x B rows
    at data=1 vs M=2 x 2B at data=2). Self-policing gates: data=2
    throughput strictly above data=1; mesh=1 chain loss series
    BIT-identical to the meshless chain (size-1 mesh compiles the
    legacy programs); data=2 parity within float tolerance; the
    replicated (N=2) x sharded x 3-stage run completes every step with
    zero drops across a mid-run kill of the sharded stage's primary
    (exactly-once handoff); steady-state recompiles == 0; the
    stage_report mesh column actually says data=2."""
    # must precede the first jax import: the virtual topology is fixed
    # at backend init
    from split_learning_tpu.parallel.mesh import ensure_host_device_count
    ensure_host_device_count(8)
    import jax
    import numpy as np

    from split_learning_tpu.models import get_plan
    from split_learning_tpu.parallel.mesh import make_host_mesh
    from split_learning_tpu.runtime.pipeline_runner import PipelineRunner
    from split_learning_tpu.runtime.replica import maybe_replicate
    from split_learning_tpu.runtime.stage import StageRuntime
    from split_learning_tpu.transport.local import LocalTransport
    from split_learning_tpu.utils import Config

    if jax.device_count() < 2:
        return {
            "leg": "composed_topology",
            "platform": "cpu+local-loopback",
            "valid": False,
            "invalid_reason": (
                f"host topology has {jax.device_count()} device(s); the "
                "leg needs XLA_FLAGS=--xla_force_host_platform_device_"
                "count=8 (or SLT_HOST_DEVICES=8) set before jax "
                "initializes"),
        }

    batch = 16
    seed = 2
    steps = 8 if quick else 14
    warm = 2
    # short wire, fixed per-hop cost: the leg's claim is per-hop
    # fixed-cost amortization, so the synthetic per-direction sleep is
    # sized so halving the microbatch count (24 -> 12 sleeps/step)
    # clearly dominates the sharded program's per-row slowdown
    delay = 0.02
    plan = get_plan(model="split_cnn_chain3", mode="split")
    sample = np.zeros((batch, 28, 28, 1), np.float32)
    rs = np.random.RandomState(0)
    xs = rs.randn(steps, batch, 28, 28, 1).astype(np.float32)
    ys = rs.randint(0, 10, (steps, batch)).astype(np.int64)

    class _DelayedHops:
        """Synthetic wire around the in-process hop (sleeps only)."""

        def __init__(self, inner, delay_s):
            self.inner = inner
            self.delay = delay_s
            self.stats = inner.stats

        def hop_forward(self, *a, **kw):
            time.sleep(self.delay)          # activations down
            res = self.inner.hop_forward(*a, **kw)
            time.sleep(self.delay)          # reply back
            return res

        def hop_backward(self, *a, **kw):
            time.sleep(self.delay)
            res = self.inner.hop_backward(*a, **kw)
            time.sleep(self.delay)
            return res

        def hop_loss(self, *a, **kw):
            time.sleep(self.delay)
            res = self.inner.hop_loss(*a, **kw)
            time.sleep(self.delay)
            return res

        def health(self):
            return self.inner.health()

        def close(self):
            self.inner.close()

        def __getattr__(self, name):
            return getattr(self.inner, name)

    from split_learning_tpu.obs import dispatch_debug
    dd = dispatch_debug.tracker()

    def make_chain(mesh_mid, microbatches, delay_s=0.0, replicas=1):
        cfg = Config(mode="split", model="split_cnn_chain3",
                     batch_size=batch, num_stages=3,
                     microbatches=microbatches, seed=seed)

        def factory(i, mesh):
            def make(_ridx=0):
                return StageRuntime(
                    plan, i, cfg, jax.random.PRNGKey(seed), sample,
                    microbatches=microbatches, mesh=mesh)
            return make

        parties = [maybe_replicate(factory(1, mesh_mid), replicas),
                   maybe_replicate(factory(2, None), replicas)]
        wires = [LocalTransport(p) for p in parties]
        if delay_s:
            wires = [_DelayedHops(w, delay_s) for w in wires]
        runner = PipelineRunner(plan, cfg, jax.random.PRNGKey(seed),
                                sample, wires,
                                microbatches=microbatches)
        return runner, parties

    def timed_run(mesh_mid, microbatches):
        """Same total rows per step, same per-device rows per
        microbatch on the sharded stage: the pair differs only in how
        many hop round-trips drain one step."""
        dispatch_debug.force(True)
        try:
            runner, parties = make_chain(mesh_mid, microbatches,
                                         delay_s=delay)
            try:
                for s in range(warm):
                    runner.step(xs[s], ys[s], step=s)
                t0 = time.perf_counter()
                for s in range(warm, steps):
                    runner.step(xs[s], ys[s], step=s)
                dt = time.perf_counter() - t0
                report = runner.stage_report()
            finally:
                runner.close()
                for p in parties:
                    p.close()
        finally:
            dispatch_debug.force(False)
        return (steps - warm) / dt, report

    g0 = dd.gauges()
    # data=1 twin: M=4 x 4 rows/mb = 4 rows/device on its one device
    sps_d1, rep_d1 = timed_run(None, 4)
    # data=2: M=2 x 8 rows/mb = 4 rows/device across the stage mesh
    sps_d2, rep_d2 = timed_run(make_host_mesh(data=2), 2)
    g1 = dd.gauges()
    compile_count = {
        "total": g1["compile_count"] - g0["compile_count"],
        "steady_state": (g1["steady_state_recompiles"]
                         - g0["steady_state_recompiles"])}
    speedup = sps_d2 / sps_d1 if sps_d1 else 0.0
    mesh_col = (rep_d2[0].get("mesh") or {}) if rep_d2 else {}

    # --- numerics: mesh=1 bit-identity + data=2 float parity ----------
    # serialized chain, exact math, no sleeps
    parity_steps = 4 if quick else 8

    def loss_series(mesh_mid):
        runner, parties = make_chain(mesh_mid, 2)
        try:
            return [runner.step(xs[i], ys[i], step=i)
                    for i in range(parity_steps)]
        finally:
            runner.close()
            for p in parties:
                p.close()

    base_series = loss_series(None)
    m1_diff = float(np.max(np.abs(
        np.asarray(base_series)
        - np.asarray(loss_series(make_host_mesh(data=1))))))
    d2_diff = float(np.max(np.abs(
        np.asarray(base_series)
        - np.asarray(loss_series(make_host_mesh(data=2))))))
    parity_tol = 5e-4

    # --- replicated x sharded x 3-stage with a mid-run kill -----------
    repl_steps = 8
    kill_at = repl_steps // 2
    runner, parties = make_chain(make_host_mesh(data=2), 2, replicas=2)
    try:
        repl_losses = []
        for s in range(repl_steps):
            if s == kill_at:
                parties[0].kill(0)  # the sharded stage's primary
            repl_losses.append(runner.step(xs[s], ys[s], step=s))
        repl_health = parties[0].health()
    finally:
        runner.close()
        for p in parties:
            p.close()
    repl_complete = (len(repl_losses) == repl_steps
                     and bool(np.all(np.isfinite(repl_losses))))
    handoffs = int(repl_health.get("replicas", {})
                   .get("replica_handoffs", 0))

    invalid_reason = None
    if m1_diff != 0.0:
        invalid_reason = (
            f"mesh=1 chain loss series differs from meshless by "
            f"{m1_diff} (must be bit-identical: a size-1 stage mesh "
            "compiles the legacy programs)")
    elif d2_diff > parity_tol:
        invalid_reason = (
            f"data=2 chain loss series diverges from meshless by "
            f"{d2_diff} (> {parity_tol}): the sharded stage programs "
            "are not reproducing the single-device math")
    elif not repl_complete:
        invalid_reason = (
            f"replicated x sharded x 3-stage run dropped steps: "
            f"{len(repl_losses)}/{repl_steps} completed finite across "
            "the mid-run kill — exactly-once handoff is broken")
    elif handoffs < 1:
        invalid_reason = (
            "replica kill produced zero handoffs: the chaos never "
            "exercised the failover path, the zero-drop column "
            "measures nothing")
    elif not sps_d2 > sps_d1:
        invalid_reason = (
            f"data=2 middle stage {sps_d2:.2f} <= data=1 twin "
            f"{sps_d1:.2f} steps/s at the same per-device "
            "rows-per-microbatch ceiling: halving the hop count "
            "bought nothing")
    elif compile_count["steady_state"]:
        invalid_reason = (
            f"steady_state_recompiles={compile_count['steady_state']:.0f}"
            " != 0: the composed hot loops retrace after step 2")
    elif mesh_col.get("data") != 2:
        invalid_reason = (
            f"stage_report mesh column says {mesh_col!r} for the "
            "sharded stage (expected data=2): the per-stage mesh "
            "export is broken")
    return {
        "leg": "composed_topology",
        "stages": 3,
        "batch": batch,
        "microbatches": {"data1": 4, "data2": 2},
        "mesh": mesh_col,
        "platform": "cpu+local-loopback",
        "host_cores": os.cpu_count(),
        "one_way_latency_ms": delay * 1e3,
        "batch_ceiling_relative": True,
        "note": ("batch-ceiling-relative: N virtual devices share one "
                 "core, so the device-parallel compute win cannot show "
                 "here (a sharded stage program is marginally slower "
                 "per row). The gated claim is the pipeline "
                 "consequence: at a fixed per-device "
                 "rows-per-microbatch ceiling a data=2 middle stage "
                 "admits double-size microbatches, draining each step "
                 "in half the hop round-trips and amortizing the "
                 "fixed per-hop wire cost twice as far"),
        "steps_per_sec_data1": sps_d1,
        "steps_per_sec_data2": sps_d2,
        "speedup_data2_vs_data1": speedup,
        "compile_count": compile_count,
        "loss_mesh1_max_abs_diff": m1_diff,
        "loss_data2_max_abs_diff": d2_diff,
        "parity_tol": parity_tol,
        "replicated_steps_completed": len(repl_losses),
        "replicated_steps_expected": repl_steps,
        "replica_handoffs": handoffs,
        "stage_report_data1": rep_d1,
        "stage_report_data2": rep_d2,
        "valid": invalid_reason is None,
        "invalid_reason": invalid_reason,
    }


def measure_flash_micro(quick: bool) -> dict:
    """Kernel-level flash block sweep: fwd and fwd+bwd timed SEPARATELY
    per block edge (VERDICT r4 #8 asked for exactly this split — the
    full-step `sweep.*` legs answer which edge wins end-to-end, this
    role says WHERE the win/loss lives). One subprocess covers every
    edge at one (T, batch) so a single window leg yields the whole
    row.

    Timing discipline matches the fused leg for real: every timed
    window is closed by a host transfer of a data-dependent scalar,
    grown past the fixed close-out cost (``grow_window`` — a fixed rep
    count at these ~30-50 ms calls would sit on the close-out transfer
    and fail linearity, the exact round-4 CNN failure),
    cross-checked at 2x, and each cell is gated by ``validate_leg``
    itself (shared bounds). The utilization denominator for the GATE is the causal
    kernel's actual FLOPs (~dense/2 — future key blocks are skipped
    entirely via ``pl.when``); the dense-equivalent rate is reported
    alongside for cross-edge comparison.

    Env: SLT_BENCH_SEQ (default 4096), SLT_BENCH_BATCH (default 16),
    SLT_FLASH_MICRO_BLOCKS (comma list, default "256,512,1024")."""
    import jax
    import jax.numpy as jnp

    from split_learning_tpu.ops.flash_attention import flash_attention
    from split_learning_tpu.utils.flops import device_peak_flops, mfu

    t = int(os.environ.get("SLT_BENCH_SEQ", "4096"))
    batch = int(os.environ.get("SLT_BENCH_BATCH", "16"))
    heads, d = 2, 128
    blocks = [int(b) for b in os.environ.get(
        "SLT_FLASH_MICRO_BLOCKS", "256,512,1024").split(",")]
    reps0 = 4 if quick else 16

    if jax.default_backend() == "cpu":
        # interpret-mode kernels at T=4096 take hours on CPU; shrink to
        # a smoke shape so the role stays runnable everywhere
        t, batch, reps0 = 256, 4, 2

    q, k, v = (jax.random.normal(jax.random.PRNGKey(i),
                                 (batch, t, heads, d), jnp.bfloat16)
               for i in range(3))
    device = q.devices().pop()
    peak = device_peak_flops(device)
    # dense-equivalent attention FLOPs: fwd 2 units of B*H*T^2*D MACs,
    # bwd 4 more (2 FLOPs per MAC folded into the unit); the causal
    # kernel executes ~half of them (block-skipped future keys), which
    # is what the physical gate must count
    unit = 2 * batch * heads * t * t * d
    flops_fwd_dense = 2 * unit
    flops_step_dense = 6 * unit

    def run_cell(block):
        os.environ["SLT_FLASH_BLOCK"] = str(block)
        try:
            fwd = jax.jit(lambda a, b, c: flash_attention(
                a, b, c, causal=True).astype(jnp.float32).sum())
            grad_fn = jax.grad(lambda a: flash_attention(
                a, k, v, causal=True).astype(jnp.float32).sum())
            # one compiled call each, closing on a scalar — symmetric,
            # so bwd_only = t_bwd - t_fwd has no unfused reduce skew
            bwd = jax.jit(lambda a: grad_fn(a).astype(
                jnp.float32).sum())

            def window(fn, *a):
                def w(n):
                    t0 = time.perf_counter()
                    s = 0.0
                    for _ in range(n):
                        s = fn(*a)
                    # close the window ON the clock: the host transfer
                    # must be inside the timed region, or the loop
                    # measures dispatch only. The 2026-08-01 attempt
                    # read 6,000 "TFLOP/s" (util gate caught it)
                    # because the tuple below evaluated perf_counter()
                    # before float(s)
                    s = float(s)
                    return time.perf_counter() - t0, s
                return w

            wf, wb = window(fwd, q, k, v), window(bwd, q)
            wf(1), wb(1)   # compile + warm
            cell = {"block": block}
            for name, w, dense in (("fwd", wf, flops_fwd_dense),
                                   ("bwd", wb, flops_step_dense)):
                n = grow_window(w, reps0)
                t_med = sorted(w(n)[0] for _ in range(3))[1] / n
                lin = w(2 * n)[0] / (t_med * n)
                cell[f"{name}_ms"] = t_med * 1e3
                cell[f"{name}_dense_equiv_tflops"] = \
                    dense / t_med / 1e12
                cell[f"linearity_2x_{name}"] = lin
                pseudo = {"linearity_2x": lin,
                          # actual causal FLOPs ~ dense/2: the gate
                          # counts work the kernel really executes
                          "model_tflops_per_sec":
                              dense / 2 / t_med / 1e12,
                          "util_vs_bf16_peak":
                              mfu(dense / 2 / t_med, peak)}
                ok, reason = validate_leg(pseudo)
                cell[f"util_causal_{name}"] = pseudo["util_vs_bf16_peak"]
                if not ok:
                    cell.setdefault("invalid_reason", reason)
            cell["bwd_only_ms_est"] = cell["bwd_ms"] - cell["fwd_ms"]
            cell["valid"] = "invalid_reason" not in cell
            return cell
        except Exception as e:  # a rejected edge is a result, not a crash
            return {"block": block,
                    "error": f"{type(e).__name__}: {str(e)[:200]}"}
        finally:
            os.environ.pop("SLT_FLASH_BLOCK", None)

    cells = [run_cell(b) for b in blocks]

    return {
        "leg": "flash_micro", "seq_len": t, "batch": batch,
        "heads": heads, "head_dim": d, "dtype": "bfloat16",
        "platform": device.platform,
        "device_kind": getattr(device, "device_kind", "") or "",
        "cells": cells,
        # the record is usable iff at least one cell measured cleanly
        "valid": any(c.get("valid") for c in cells),
    }


def measure_decode(quick: bool) -> dict:
    """Autoregressive decode throughput (tokens/s) of the KV-cache path
    vs the O(T^2) re-forward path, same LM plan (runtime/generate.py).

    The timed window is data-dependent (np.asarray of the generated
    tokens — the host transfer cannot complete until the scan executed)
    and cross-checked by a 2x-new-tokens window: KV decode cost is
    ~linear in generated tokens, so linearity_2x must land near 2; the
    re-forward path is quadratic-ish, reported for the speedup ratio
    only. Env overrides: SLT_DECODE_PROMPT / SLT_DECODE_NEW /
    SLT_DECODE_BATCH."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from split_learning_tpu.models.transformer import transformer_plan
    from split_learning_tpu.runtime.generate import greedy_generate

    prompt_len = int(os.environ.get("SLT_DECODE_PROMPT",
                                    "128" if quick else "1024"))
    n_new = int(os.environ.get("SLT_DECODE_NEW", "32" if quick else "256"))
    batch = int(os.environ.get("SLT_DECODE_BATCH", "8"))
    rs = np.random.RandomState(0)
    prompt = rs.randint(0, 256, (batch, prompt_len)).astype(np.int32)
    plan = transformer_plan(lm=True, dtype=np.dtype("bfloat16"),
                            d_model=256, num_heads=2,
                            max_len=max(2048, prompt_len + 2 * n_new))
    params = plan.init(jax.random.PRNGKey(0), jnp.asarray(prompt))
    device = jax.devices()[0]

    def window(n: int, kv: bool) -> float:
        t0 = time.perf_counter()
        out = greedy_generate(plan, params, prompt, n, kv_cache=kv)
        np.asarray(out)  # host transfer: data-dependent close
        return time.perf_counter() - t0

    window(n_new, kv=True)  # compile + warm
    times = sorted(window(n_new, kv=True) for _ in range(3))
    t_med = times[1]
    window(2 * n_new, kv=True)  # compile + warm (its own program)
    t_2x = sorted(window(2 * n_new, kv=True) for _ in range(3))[1]
    # both windows include the same prefill, so the *difference* is pure
    # decode for n_new extra tokens — the per-token rate comes from the
    # slope, not the whole-window ratio (which is < 2 by construction
    # whenever prefill is not negligible)
    decode_s_per_token = (t_2x - t_med) / n_new
    prefill_s = t_med - n_new * decode_s_per_token
    leg = {
        "leg": "decode",
        "prompt_len": prompt_len,
        "n_new": n_new,
        "batch": batch,
        "dtype": "bfloat16",
        "platform": device.platform,
        "device_kind": getattr(device, "device_kind", "") or "",
        "kv_tokens_per_sec": (batch / decode_s_per_token
                              if decode_s_per_token > 0 else None),
        "kv_ms_per_token": decode_s_per_token * 1e3,
        "whole_window_tokens_per_sec": batch * n_new / t_med,
        "prefill_s_est": prefill_s,
        "window_s": {"best": times[0], "median": t_med, "worst": times[-1],
                     "2x_new_tokens": t_2x},
    }
    if not quick:
        window(n_new, kv=False)  # compile
        t_ref = min(window(n_new, kv=False) for _ in range(2))
        leg["reforward_tokens_per_sec"] = batch * n_new / t_ref
        leg["kv_speedup_vs_reforward"] = t_ref / t_med
    # gate: doubling the generated tokens must cost real extra time
    # (slope > 0) and the implied prefill must be non-negative (within
    # 10% of the window for noise) — otherwise the window measured
    # dispatch, not execution
    ok = decode_s_per_token > 0 and prefill_s > -0.1 * t_med
    leg["valid"] = bool(ok)
    leg["invalid_reason"] = None if ok else (
        f"decode window not work-scaling: slope {decode_s_per_token:.2e}"
        f" s/token, implied prefill {prefill_s:.3f}s of a {t_med:.3f}s "
        "window")
    return leg


def _run_subprocess(role: str, quick: bool, env_overrides: dict,
                    timeout: float, capture: bool = False):
    """Run one measurement role in a fresh process and parse its JSON
    line. Default: dict | None (errors printed). With ``capture=True``:
    ``(record | None, CompletedProcess | "timeout")`` so callers (e.g.
    scripts/measure_long_context.py) can classify failures themselves —
    the one place the subprocess-and-parse protocol lives."""
    env = dict(os.environ)
    env.update(env_overrides)
    cmd = [sys.executable, os.path.abspath(__file__), "--role", role]
    if quick:
        cmd.append("--quick")
    try:
        out = subprocess.run(cmd, capture_output=True, text=True,
                             timeout=timeout, env=env,
                             cwd=os.path.dirname(os.path.abspath(__file__)))
    except subprocess.TimeoutExpired:
        if capture:
            return None, "timeout"
        print(f"[bench] {role} timed out", file=sys.stderr)
        return None
    if not capture and out.returncode != 0:
        print(f"[bench] {role} failed:\n{out.stderr[-2000:]}", file=sys.stderr)
        return None
    rec = None
    for line in out.stdout.splitlines():
        line = line.strip()
        if line.startswith("{"):
            rec = json.loads(line)
            break
    if capture:
        return rec, out
    if rec is None:
        print(f"[bench] {role}: no JSON in output", file=sys.stderr)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--role",
                    choices=["baseline", "fused", "dp", "wire", "topk8",
                             "pipelined", "coalesced", "reply_latency_2bp",
                             "chaos_soak", "fleet_soak",
                             "replica_failover", "autoscale_diurnal",
                             "decode",
                             "flash_micro", "sharded_server",
                             "mpmd_pipeline", "mpmd_colocated",
                             "mpmd_compressed", "fleet_telemetry",
                             "composed_topology"],
                    default=None)
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()

    if args.role is not None:
        from split_learning_tpu.utils import configure_compile_cache
        configure_compile_cache()
        if args.role in DEVICE_ROLES:
            _require_chip(args.role)
        fn = {"baseline": measure_baseline, "fused": measure_fused,
              "dp": measure_dp, "wire": measure_wire,
              "topk8": measure_topk8,
              "pipelined": measure_pipelined,
              "coalesced": measure_coalesced,
              "reply_latency_2bp": measure_reply_latency_2bp,
              "chaos_soak": measure_chaos_soak,
              "fleet_soak": measure_fleet_soak,
              "replica_failover": measure_replica_failover,
              "autoscale_diurnal": measure_autoscale_diurnal,
              "decode": measure_decode,
              "flash_micro": measure_flash_micro,
              "sharded_server": measure_sharded_server,
              "mpmd_pipeline": measure_mpmd_pipeline,
              "mpmd_colocated": measure_mpmd_colocated,
              "mpmd_compressed": measure_mpmd_compressed,
              "fleet_telemetry": measure_fleet_telemetry,
              "composed_topology": measure_composed_topology}[args.role]
        print(json.dumps(fn(args.quick)))
        return

    # orchestrator (stays off JAX): baseline on CPU by design; fused on
    # the default backend, one subprocess at a time
    baseline = _run_subprocess("baseline", args.quick, CPU_ENV, timeout=900)
    if baseline is None:
        # nothing downstream can be scored without the denominator — bail
        # before spending up to 45 min of device benchmarking on a doomed run
        print(json.dumps({"metric": "mnist_split_cnn_steps_per_sec",
                          "value": None, "unit": "steps/sec",
                          "vs_baseline": None}))
        sys.exit(1)

    detail = {"baseline": baseline}
    fused = _run_subprocess("fused", args.quick, {}, timeout=1500)
    if fused is not None and not args.quick and fused.get("valid"):
        # extra legs run only after the fused run SUCCEEDED and passed
        # the gate — an invalid headline exits below, so spending up to
        # 2x900s on side legs first would be wasted work
        def side_leg(env_overrides, role="fused"):
            return _run_subprocess(role, args.quick, env_overrides,
                                   timeout=900)

        bf16 = side_leg({"SLT_BENCH_DTYPE": "bfloat16"})
        if bf16 is not None and bf16.get("valid"):
            fused["bf16_steps_per_sec"] = bf16["steps_per_sec"]
            fused["bf16_mfu_vs_bf16_peak"] = bf16.get("util_vs_bf16_peak")
        elif bf16 is not None:
            print(f"[bench] bf16 leg INVALID: {bf16.get('invalid_reason')}",
                  file=sys.stderr)
        # ResNet-18/CIFAR-10 leg (BASELINE.json configs[3]): the model with
        # enough arithmetic intensity for MFU to mean something
        resnet = side_leg({"SLT_BENCH_MODEL": "resnet18",
                           "SLT_BENCH_BATCH": "256",
                           "SLT_BENCH_DTYPE": "bfloat16"})
        if resnet is not None:
            if not resnet.get("valid"):
                # full redaction: every throughput-derived field goes (a
                # nulled steps/sec with model_tflops_per_sec left intact
                # would still publish the number in other units)
                print(f"[bench] resnet leg INVALID: "
                      f"{resnet.get('invalid_reason')}", file=sys.stderr)
                keep = ("model", "batch", "dtype", "platform", "device_kind",
                        "flops_per_step", "valid", "invalid_reason")
                resnet = {k: resnet.get(k) for k in keep}
            detail["resnet18_b256_bf16"] = resnet
        # config 5: U-shaped 3-hop split, fused on the device (the client
        # holds stages A and C; one program, labels never cross the cut).
        # Same scope as bf16/resnet: device legs only next to a valid
        # device headline.
        usplit = side_leg({"SLT_BENCH_MODE": "u_split"})
        if usplit is not None and usplit.get("valid"):
            detail["u_split_fused"] = usplit
        elif usplit is not None:
            print(f"[bench] u_split leg INVALID: "
                  f"{usplit.get('invalid_reason')}", file=sys.stderr)
        # large-batch leg: same split CNN at batch 1024 — the workload
        # whose per-step work is big enough to fill the chip. Shows
        # where the batch-64 headline's utilization gap comes from
        # (on-device critical path of a tiny step, not dispatch: the
        # headline already scans ~469 steps per dispatch)
        b1024 = side_leg({"SLT_BENCH_BATCH": "1024",
                          "SLT_BENCH_DTYPE": "bfloat16"})
        if b1024 is not None and b1024.get("valid"):
            detail["split_cnn_b1024_bf16"] = b1024
        elif b1024 is not None:
            print(f"[bench] b1024 leg INVALID: "
                  f"{b1024.get('invalid_reason')}", file=sys.stderr)
        # the hand-written Pallas kernels (ops/) vs plain XLA on the same
        # step — the kernels' first on-device perf evidence
        pallas = side_leg({"SLT_BENCH_KERNELS": "pallas"})
        if pallas is not None and pallas.get("valid"):
            detail["fused_pallas_kernels"] = pallas
        elif pallas is not None:
            print(f"[bench] pallas leg INVALID: "
                  f"{pallas.get('invalid_reason')}", file=sys.stderr)
        # the long-context family on the device: dense vs Pallas-flash
        # attention at T=256 (models/transformer.py, ops/flash_attention.py)
        for leg_name, extra in (
                ("transformer_t256_dense", {}),
                ("transformer_t256_flash", {"SLT_BENCH_ATTN": "flash"})):
            env = {"SLT_BENCH_MODEL": "transformer",
                   "SLT_BENCH_DTYPE": "bfloat16", **extra}
            tfm = side_leg(env)
            if tfm is not None and tfm.get("valid"):
                detail[leg_name] = tfm
            elif tfm is not None:
                print(f"[bench] {leg_name} leg INVALID: "
                      f"{tfm.get('invalid_reason')}", file=sys.stderr)
        # round-4 ViT family: the transformer trunk on images
        vit = side_leg({"SLT_BENCH_MODEL": "vit", "SLT_BENCH_BATCH": "256",
                        "SLT_BENCH_DTYPE": "bfloat16"})
        if vit is not None and vit.get("valid"):
            detail["vit_b256_bf16"] = vit
        elif vit is not None:
            print(f"[bench] vit leg INVALID: "
                  f"{vit.get('invalid_reason')}", file=sys.stderr)
        # KV-cache decode throughput (runtime/generate.py): tokens/s at
        # a 1024-token prompt, vs the O(T^2) re-forward path
        dec = side_leg({}, role="decode")
        if dec is not None and dec.get("valid"):
            detail["decode_kv_cache"] = dec
        elif dec is not None:
            print(f"[bench] decode leg INVALID: "
                  f"{dec.get('invalid_reason')}", file=sys.stderr)

    if not args.quick and fused is not None and fused.get("valid"):
        # CPU side legs — skipped when the headline is doomed to exit(1)
        # below, so an invalid run never burns subprocess budget on them.
        # config 3: multi-client DP on the virtual host mesh (no
        # multi-chip hardware here; scheduling-relative, loss parity is
        # the exact part)
        dp_env = dict(CPU_ENV)
        dp_env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        dp = _run_subprocess("dp", args.quick, dp_env, timeout=900)
        if dp is not None:
            detail["multi_client_dp"] = dp
        # the int8 wire-compression latency claim
        wire = _run_subprocess("wire", args.quick, CPU_ENV, timeout=900)
        if wire is not None:
            detail["http_wire_compression"] = wire
        # sparse error-feedback compression (top-k + int8) byte/parity
        # gates: 3 x 300 training steps over a synthetic 80 ms wire
        tk = _run_subprocess("topk8", args.quick, CPU_ENV, timeout=1800)
        if tk is not None:
            detail["wire_topk8"] = tk
        # the in-flight-window client vs the reference's lock-step loop
        piped = _run_subprocess("pipelined", args.quick, CPU_ENV,
                                timeout=900)
        if piped is not None:
            detail["pipelined_http"] = piped
        # server-side request coalescing: N concurrent clients folded
        # into batched dispatches vs the serialized round-robin relay
        coal = _run_subprocess("coalesced", args.quick, CPU_ENV,
                               timeout=900)
        if coal is not None:
            detail["multi_client_coalesced"] = coal
        # reply-first decoupled backward (2BP): reply p50 coupled vs
        # decoupled at 4 concurrent clients over the synthetic wire
        twobp = _run_subprocess("reply_latency_2bp", args.quick, CPU_ENV,
                                timeout=900)
        if twobp is not None:
            detail["reply_latency_2bp"] = twobp
        # robustness soak: a seeded response-drop/dup/5xx schedule must
        # lose zero batches and match the fault-free run's loss
        soak = _run_subprocess("chaos_soak", args.quick, CPU_ENV,
                               timeout=900)
        if soak is not None:
            detail["chaos_soak"] = soak
        # continuous batching vs fixed-window under a bursty 1000+
        # client fleet, plus its chaos-composed twin
        fleet = _run_subprocess("fleet_soak", args.quick, CPU_ENV,
                                timeout=900)
        if fleet is not None:
            detail["fleet_soak"] = fleet
        # horizontal replication: twin 3-replica groups, one losing its
        # busiest replica mid-run — exactly-once handoff, zero dropped,
        # loss parity vs the unkilled twin
        repl = _run_subprocess("replica_failover", args.quick, CPU_ENV,
                               timeout=900)
        if repl is not None:
            detail["replica_failover"] = repl
        # elastic autoscaling vs static peak provisioning over a seeded
        # diurnal cycle: held SLO, zero drops, strictly fewer
        # replica-seconds through the exactly-once scale-down handoff
        elastic = _run_subprocess("autoscale_diurnal", args.quick,
                                  CPU_ENV, timeout=900)
        if elastic is not None:
            detail["autoscale_diurnal"] = elastic
        # sharded server (pjit over the virtual host mesh): mesh-aware
        # coalesced dispatch; batch-ceiling-relative throughput gate,
        # mesh=1 bit-identity, zero steady-state recompiles
        sh_env = dict(CPU_ENV)
        sh_env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        sharded = _run_subprocess("sharded_server", args.quick, sh_env,
                                  timeout=900)
        if sharded is not None:
            detail["sharded_server"] = sharded
        # K-stage MPMD split pipeline: GPipe microbatching over two
        # synthetic heterogeneous wires vs the serialized M=1 chain,
        # plus loss parity against the 1-cut split
        mpmd = _run_subprocess("mpmd_pipeline", args.quick, CPU_ENV,
                               timeout=900)
        if mpmd is not None:
            detail["mpmd_pipeline"] = mpmd
        # co-located device-native chain (PR 16): zero-copy hops +
        # 1F1B schedule vs the fused single-program twin, HTTP-loopback
        # contrast for copy accounting and M=1 bit-identity
        coloc = _run_subprocess("mpmd_colocated", args.quick, CPU_ENV,
                                timeout=900)
        if coloc is not None:
            detail["mpmd_colocated"] = coloc
        # compressed hop wires (PR 18): dense vs topk8 vs clapping over
        # real HTTP loopback hops — >= 10x hop bytes at end-loss parity
        comp = _run_subprocess("mpmd_compressed", args.quick, CPU_ENV,
                               timeout=900)
        if comp is not None:
            detail["mpmd_compressed"] = comp
        # composable party runtime (ISSUE 20): per-stage pjit on the
        # chain's middle stage, replicated x sharded x 3-stage
        # composition with a mid-run kill; batch-ceiling-relative
        # throughput gate, mesh=1 bit-identity, zero steady-state
        # recompiles
        ct_env = dict(CPU_ENV)
        ct_env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        composed = _run_subprocess("composed_topology", args.quick,
                                   ct_env, timeout=900)
        if composed is not None:
            detail["composed_topology"] = composed

    detail["fused"] = fused
    if fused is None:
        print(json.dumps({"metric": "mnist_split_cnn_steps_per_sec",
                          "value": None, "unit": "steps/sec",
                          "vs_baseline": None}))
        sys.exit(1)

    print(f"[bench] detail: {json.dumps(detail)}", file=sys.stderr)

    if not fused.get("valid", False):
        # THE GATE (README "every published figure must pass steps/sec
        # x FLOPs/step <= chip peak", enforced since round 3): an
        # invalid measurement publishes null + the reason, never the
        # number.
        reason = fused.get("invalid_reason") or "leg reported valid=false"
        print(f"[bench] headline INVALID: {reason}", file=sys.stderr)
        print(json.dumps({"metric": "mnist_split_cnn_steps_per_sec",
                          "value": None, "unit": "steps/sec",
                          "vs_baseline": None,
                          "invalid_reason": reason}))
        sys.exit(1)

    ceiling = fused.get("steps_per_sec_ceiling_at_peak")
    if ceiling:
        print(f"[bench] sanity: {fused['steps_per_sec']:.0f} steps/s vs "
              f"ceiling {ceiling:.0f} steps/s at 100% bf16 peak "
              f"(util {fused['util_vs_bf16_peak']:.3f})", file=sys.stderr)

    print(json.dumps({
        "metric": "mnist_split_cnn_steps_per_sec",
        "value": round(fused["steps_per_sec"], 2),
        "unit": "steps/sec",
        "vs_baseline": round(fused["steps_per_sec"] / baseline["steps_per_sec"], 2),
        "platform": fused.get("platform"),
        "device_kind": fused.get("device_kind"),
    }))


if __name__ == "__main__":
    main()
