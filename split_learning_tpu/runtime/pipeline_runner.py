"""PipelineRunner — the client-side GPipe driver of the K-stage MPMD
chain (PR 14).

`parallel/pipeline.py` is pipeline parallelism INSIDE one jitted SPMD
program: every stage lives on one mesh, cuts are ``ppermute`` hops, one
party owns everything. The MPMD chain is the same schedule pulled apart
across parties (arXiv:2412.14374): stage 0 runs here (the data owner —
split learning's privacy boundary), stages 1..S-1 are remote
:class:`~split_learning_tpu.runtime.stage.StageRuntime` parties reached
through one :class:`Transport` each, and the cut tensors cross real
wires. The driver is the hub: it relays each microbatch's activations
stage-to-stage (hub-and-spoke MPMD — the Transport abstraction is
client↔party, and the data owner stays the only party that sees every
cut, exactly as in the 2-party protocol).

Schedule: GPipe with M microbatches in flight, or 1F1B (PR 16,
PiPar arXiv:2302.12803): ``schedule="1f1b"`` injects only the warmup
depth W = min(S, M) of stage-0 forwards up front, then exactly one new
forward per drained cotangent — the strict 1-forward-1-backward steady
state. Both schedules accumulate cotangents in microbatch order on the
SAME per-step params snapshot, so the loss trajectory is bit-identical
between them at every M (the schedule changes WHEN work is in flight,
never the arithmetic); what 1F1B buys is the bounded in-flight depth —
W microbatch residuals live at once instead of M. Each wire gets TWO
dedicated worker threads — one forward, one backward — fed by FIFO
queues, so (a) microbatch m+1's forward overlaps microbatch m's
backward on the same wire (full duplex), (b) per (stage, direction)
the hops leave in microbatch order (the strict-seq handshake and
invariants SLT113/SLT115 both key on that), and (c) middle stages
never idle while the chain is full. The tick math is
`parallel/pipeline.py`'s: T = M + S - 1 clock ticks per step for BOTH
schedules (the per-step apply is a barrier; 1F1B's last cotangent
still lands at tick M + S - 1), ideal bubble (S-1)/(M+S-1) —
``stage_report()`` carries the theoretical number per schedule and the
measured one (1 - wire-busy/wall).

Transports advertising ``device_native`` (transport/device.py) flip
the driver's stage-0 boundary to device buffers: the injected payload
is the jitted forward's output ``jax.Array`` (no ``np.asarray``, no
``expected_d2h`` region) and returned cotangents feed ``_bwd_acc``
as-is — the whole hop path stays on device; the one sanctioned D2H
left in a step is the loss scalar at the metrics edge.

Weight updates: the last stage's loss hop replies per-microbatch
cut-cotangents pre-scaled by 1/M (see StageRuntime._build_jitted), so
summing the M per-microbatch stage-0 vjp contributions reproduces the
batch-mean gradient; one optimizer apply per step, after the step's
last cotangent returns. Cotangents are accumulated in microbatch
order, not arrival order, so a run is deterministic regardless of
wire jitter. Remote stages defer their own applies under their own
``apply_lag`` (staleness bounds compose per stage, arXiv:1910.05104).

Fault policy: transient wire faults (TransportError — chaos drop/dup,
a 5xx, a lost reply) retry with bounded backoff; the stages' replay
caches make the retry exactly-once. Backpressure honors the advised
delay. ProtocolError is permanent and propagates.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from split_learning_tpu.core.stage import SplitPlan
from split_learning_tpu.obs import dispatch_debug as obs_dispatch
from split_learning_tpu.obs import spans
from split_learning_tpu.runtime.party import state_device_ids
from split_learning_tpu.runtime.server import ProtocolError
from split_learning_tpu.runtime.state import (
    TrainState, apply_grads, make_state, make_tx)
from split_learning_tpu.transport.base import (
    Backpressure, Transport, TransportError)
from split_learning_tpu.utils.config import Config

# bounded retry of one hop delivery: covers chaos's max_faults_per_key
# (2) with room for a real transient on top
DEFAULT_HOP_RETRIES = 4


# hub-driver schedules: GPipe (all M in flight) or 1F1B (PiPar-style
# warmup + strict 1-forward-1-backward steady state)
SCHEDULES = ("gpipe", "1f1b")


def pipeline_ticks(microbatches: int, num_stages: int) -> int:
    """Clock length per step (parallel/pipeline.py: T = M + S - 1).
    Identical for GPipe and 1F1B: the per-step apply is a barrier, and
    1F1B's throttled injection still lands the last cotangent at tick
    M + S - 1 — the schedules differ in in-flight DEPTH, not length."""
    return int(microbatches) + int(num_stages) - 1


def bubble_fraction(microbatches: int, num_stages: int) -> float:
    """Idle ticks / total ticks of the ideal schedule: (S-1)/(M+S-1).
    The per-step ideal coincides for GPipe and 1F1B (same T); what the
    measured numbers separate is how far real wires fall from it."""
    s = int(num_stages)
    return (s - 1) / float(pipeline_ticks(microbatches, s))


def onefb_warmup(microbatches: int, num_stages: int) -> int:
    """1F1B warmup depth W = min(S, M): enough forwards to fill every
    stage of the pipe, never more than there are microbatches. From the
    W-th drain on, the driver is in the strict 1-forward-1-backward
    steady state and at most W microbatch residuals exist at stage 0."""
    return min(int(num_stages), int(microbatches))


class _HopWorker(threading.Thread):
    """One direction of one wire: pops (step, mb, payload...) jobs in
    FIFO order, runs the hop with bounded retry, pushes downstream.
    A failed job parks the exception on the runner; the sentinel it
    forwards unblocks whoever is waiting at the chain's end."""

    def __init__(self, name: str, runner: "PipelineRunner", fn) -> None:
        super().__init__(name=name, daemon=True)
        self.q: "queue.Queue" = queue.Queue()
        self._runner = runner
        self._fn = fn
        self.busy_s = 0.0
        self.calls = 0
        self.durations: List[float] = []

    def run(self) -> None:
        while True:
            job = self.q.get()
            if job is None:
                return
            try:
                t0 = time.perf_counter()
                self._fn(*job)
                dt = time.perf_counter() - t0
                self.busy_s += dt
                self.calls += 1
                self.durations.append(dt)
                reg = self._runner.telemetry_registry
                if reg is not None:  # telemetry plane (PR 17), off=None
                    reg.observe(spans.WIRE, dt)
            except BaseException as exc:  # noqa: BLE001 — parked, re-raised
                self._runner._park_error(exc)


class PipelineRunner:
    """Drives stage 0 locally and S-1 remote stages through their
    transports, M microbatches in flight per step."""

    def __init__(self, plan: SplitPlan, cfg: Config, rng: jax.Array,
                 sample_input: np.ndarray,
                 transports: Sequence[Transport],
                 microbatches: int = 1,
                 client_id: int = 0,
                 hop_retries: int = DEFAULT_HOP_RETRIES,
                 step_timeout_s: float = 300.0,
                 schedule: str = "gpipe") -> None:
        """``transports[i]`` reaches stage ``i + 1`` (LocalTransport
        around an in-process StageRuntime, HttpTransport to a
        ``serve --role stage`` process, DeviceTransport around a
        co-located StageRuntime, ChaosTransport around any).
        ``rng``/``sample_input`` are the shared plan-level seed all
        parties initialize from — stage 0's params here agree with the
        chain's by construction, no weights ship. ``schedule`` picks
        the injection discipline (see module docstring); the default
        stays GPipe."""
        if plan.num_stages < 2:
            raise ValueError("a pipeline chain needs >= 2 stages")
        if len(transports) != plan.num_stages - 1:
            raise ValueError(
                f"need one transport per remote stage "
                f"({plan.num_stages - 1}; got {len(transports)})")
        self.plan = plan
        self.cfg = cfg
        self.microbatches = int(microbatches)
        if self.microbatches < 1:
            raise ValueError(
                f"microbatches must be >= 1 (got {microbatches})")
        self.client_id = int(client_id)
        self.transports = list(transports)
        self.hop_retries = int(hop_retries)
        self.step_timeout_s = float(step_timeout_s)
        self.schedule = str(schedule)
        if self.schedule not in SCHEDULES:
            raise ValueError(
                f"unknown schedule {schedule!r} "
                f"(expected one of {SCHEDULES})")
        # device payloads only when EVERY wire carries them: a single
        # host-bound transport in the chain reinstates the numpy
        # boundary for all (its peer would np.asarray a jax.Array —
        # correct, but a hidden D2H per hop)
        self._device_native = all(
            getattr(t, "device_native", False) for t in self.transports)

        self._tx = make_tx(cfg)
        params0 = plan.init(rng, jnp.asarray(sample_input))[0]
        self.state: TrainState = make_state(params0, self._tx)
        if self._device_native:
            # pin the hub's state to its device up front: device-native
            # cotangent replies arrive committed (transport/device.py
            # _to_hub), and a committed-ness flip after the first apply
            # would retrace every hub program at step 2
            self.state = jax.device_put(self.state, jax.devices()[0])
        self._dd = obs_dispatch.attach()
        self._ddtok = obs_dispatch.token()
        self._build_jitted()

        self._err_lock = threading.Lock()
        self._errs: List[BaseException] = []
        self._losses: Dict[Tuple[int, int], float] = {}
        self._done_q: "queue.Queue" = queue.Queue()
        self._workers: List[_HopWorker] = []
        self._fwd_workers: List[_HopWorker] = []
        self._bwd_workers: List[_HopWorker] = []
        self._spawn_workers()
        self.steps_done = 0
        self._wall_s = 0.0
        # telemetry plane (PR 17): an obs.metrics.Registry the hub's
        # TelemetryRing snapshots — attached by the launcher/bench when
        # telemetry is on, None otherwise (zero-overhead-off: the only
        # cost when off is this None check per hop/step)
        self.telemetry_registry = None
        # adaptive density (PR 18): a transport.density.DensityController
        # shared with the chain transports — attached by the launcher
        # when --compress-density auto, None otherwise. The driver is
        # the single writer of note_loss (between steps, no hops in
        # flight), which is what makes the trajectory deterministic.
        self.density_controller = None

    # ------------------------------------------------------------------ #
    def _build_jitted(self) -> None:
        stage0 = self.plan.stages[0]
        tx = self._tx

        def fwd0_fn(params, x):
            return stage0.apply(params, x)

        def bwd_acc_fn(params, x, g, acc):
            _, vjp = jax.vjp(lambda p: stage0.apply(p, x), params)
            (gp,) = vjp(g)
            return jax.tree_util.tree_map(jnp.add, acc, gp)

        def zeros_fn(params):
            return jax.tree_util.tree_map(jnp.zeros_like, params)

        def apply_fn(state, grads):
            return apply_grads(tx, state, grads)

        # fixed microbatch shapes => each compiles once; the dispatch
        # watchdog's steady_state_recompiles gauge pins that
        self._fwd0 = jax.jit(fwd0_fn)
        self._bwd_acc = jax.jit(bwd_acc_fn)
        self._zeros = jax.jit(zeros_fn)
        self._apply = jax.jit(apply_fn)

    # ------------------------------------------------------------------ #
    def _park_error(self, exc: BaseException) -> None:
        with self._err_lock:
            self._errs.append(exc)
        # unblock the step loop; the payload slot flags the failure
        self._done_q.put(("err", exc))

    def _wire(self, fn, *args):
        """Bounded-retry delivery of one hop. Transient faults retry
        (the stage's replay cache makes redelivery exactly-once);
        ProtocolError is permanent and propagates."""
        delay = 0.05
        for attempt in range(self.hop_retries + 1):
            try:
                return fn(*args)
            except Backpressure as bp:
                if attempt >= self.hop_retries:
                    raise
                time.sleep(bp.retry_after_s or delay)
            except ProtocolError:
                raise
            except TransportError:
                if attempt >= self.hop_retries:
                    raise
                time.sleep(delay)
                delay = min(delay * 2, 1.0)

    def _spawn_workers(self) -> None:
        W = len(self.transports)
        self._fwd_workers = []
        self._bwd_workers = [None] * max(W - 1, 0)

        def make_fwd(i: int):
            t = self.transports[i]
            if i == W - 1:
                def last_hop(step, mb, x, labels):
                    g, loss = self._wire(t.hop_loss, x, labels, step, mb,
                                         self.client_id)
                    loss_host = float(loss)  # host scalar before the lock
                    with self._err_lock:
                        self._losses[(step, mb)] = loss_host
                    if W == 1:
                        self._done_q.put((step, mb, g))
                    else:
                        self._bwd_workers[W - 2].q.put((step, mb, g))
                return last_hop

            def mid_hop(step, mb, x, labels):
                y = self._wire(t.hop_forward, x, step, mb, self.client_id)
                self._fwd_workers[i + 1].q.put((step, mb, y, labels))
            return mid_hop

        def make_bwd(i: int):
            t = self.transports[i]

            def bwd_hop(step, mb, g):
                g_in = self._wire(t.hop_backward, g, step, mb,
                                  self.client_id)
                if i == 0:
                    self._done_q.put((step, mb, g_in))
                else:
                    self._bwd_workers[i - 1].q.put((step, mb, g_in))
            return bwd_hop

        for i in range(W):
            w = _HopWorker(f"pipe-fwd-{i + 1}", self, make_fwd(i))
            self._fwd_workers.append(w)
        for i in range(W - 1):
            self._bwd_workers[i] = _HopWorker(
                f"pipe-bwd-{i + 1}", self, make_bwd(i))
        self._workers = self._fwd_workers + list(self._bwd_workers)
        for w in self._workers:
            w.start()

    # ------------------------------------------------------------------ #
    def step(self, x: np.ndarray, y: np.ndarray,
             step: Optional[int] = None) -> float:
        """One training step: M microbatches pipelined through the
        chain, one stage-0 apply. Returns the batch loss (mean of the
        per-microbatch CE means — equal-size microbatches)."""
        M = self.microbatches
        x = np.asarray(x)
        y = np.asarray(y)
        if x.shape[0] % M != 0:
            raise ValueError(
                f"batch {x.shape[0]} not divisible by microbatches {M}")
        step_i = self.steps_done if step is None else int(step)
        with self._err_lock:
            if self._errs:
                raise self._errs[0]
        t_wall0 = time.perf_counter()
        mbsz = x.shape[0] // M
        x_dev: Dict[int, jax.Array] = {}

        def inject(m: int) -> None:
            """Stage-0 forward of microbatch m, payload onto wire 0.
            All injections of a step run on the same self.state.params
            (the apply is after the drain), so 1F1B's later injections
            see exactly the weights GPipe's up-front ones would."""
            xs = jnp.asarray(x[m * mbsz:(m + 1) * mbsz])
            with obs_dispatch.step_scope(
                    self._dd, (self._ddtok, "pipe_fwd0"),
                    sig_fn=lambda: (xs.shape, str(xs.dtype))):
                y0 = self._fwd0(self.state.params, xs)
            x_dev[m] = xs
            if self._device_native:
                payload = y0  # the device buffer IS the wire payload
            else:
                with obs_dispatch.expected_d2h(self._dd):
                    payload = np.asarray(y0)
            self._fwd_workers[0].q.put(
                (step_i, m, payload, y[m * mbsz:(m + 1) * mbsz]))

        # fill the pipe: GPipe streams all M stage-0 forwards out up
        # front; 1F1B stops at the warmup depth W = min(S, M), then the
        # drain loop injects exactly one forward per drained cotangent
        # — the strict 1-forward-1-backward steady state. Injection
        # order is 0..M-1 either way.
        warm = M if self.schedule == "gpipe" else onefb_warmup(
            M, self.plan.num_stages)
        for m in range(warm):
            inject(m)
        next_m = warm
        # drain: the step's M cotangents, arrival order
        cts: Dict[int, Any] = {}
        deadline = time.monotonic() + self.step_timeout_s
        while len(cts) < M:
            try:
                item = self._done_q.get(
                    timeout=max(deadline - time.monotonic(), 0.01))
            except queue.Empty:
                raise TransportError(
                    f"pipeline stalled: step {step_i} got "
                    f"{len(cts)}/{M} cotangents within "
                    f"{self.step_timeout_s:.0f}s") from None
            if item[0] == "err":
                raise item[1]
            s, m, g = item
            if s != step_i:  # stale sentinel from an aborted step
                continue
            cts[m] = g
            if next_m < M:  # 1F1B steady state: one fwd per bwd
                inject(next_m)
                next_m += 1
        # accumulate in MICROBATCH order (determinism), apply once
        acc = self._zeros(self.state.params)
        for m in range(M):
            g_dev = jnp.asarray(cts[m])
            with obs_dispatch.step_scope(
                    self._dd, (self._ddtok, "pipe_bwd0"),
                    sig_fn=lambda: (g_dev.shape, str(g_dev.dtype))):
                acc = self._bwd_acc(self.state.params, x_dev[m], g_dev,
                                    acc)
        with obs_dispatch.step_scope(
                self._dd, (self._ddtok, "pipe_apply0"),
                sig_fn=lambda: ()):
            self.state = self._apply(self.state, acc)
        with self._err_lock:
            losses = [self._losses.pop((step_i, m)) for m in range(M)]
        self.steps_done += 1
        step_wall = time.perf_counter() - t_wall0
        self._wall_s += step_wall
        reg = self.telemetry_registry
        if reg is not None:  # telemetry plane (PR 17), off=None
            reg.observe(spans.STEP_TOTAL, step_wall)
            reg.incr("hub_steps_total")
        loss_mean = float(np.mean(losses))
        dc = self.density_controller
        if dc is not None:
            # rung moves happen HERE, between steps — no request reads a
            # density mid-change, so same seed + schedule => same
            # trajectory (SLT004: pure function of losses and ratios)
            dc.note_loss(loss_mean)
            if reg is not None:
                for wire, d in dc.densities().items():
                    reg.set_gauge(f"{spans.WIRE_DENSITY}_{wire}", d)
        return loss_mean

    def predict(self, x: np.ndarray) -> np.ndarray:
        """Forward-only through the whole chain (each stage's predict
        sits behind its own flush barrier)."""
        y0 = self._fwd0(self.state.params, jnp.asarray(x))
        with obs_dispatch.expected_d2h(self._dd):
            out = np.asarray(y0)
        for t in self.transports:
            out = t.predict(out, self.client_id)
        return np.asarray(out)

    # -- accounting ----------------------------------------------------- #
    def stage_report(self) -> List[Dict[str, Any]]:
        """Per remote stage: measured bubble fraction (1 - wire-busy /
        driver wall), the ideal bubble for BOTH schedules (the per-step
        ideal coincides — see bubble_fraction — so measured-vs-ideal is
        what separates them), the active schedule and its warmup depth,
        hop-reply p50, and the stage's deferred-apply depth (over its
        own health endpoint — transport-agnostic)."""
        S = self.plan.num_stages
        theo = bubble_fraction(self.microbatches, S)
        warm = (self.microbatches if self.schedule == "gpipe"
                else onefb_warmup(self.microbatches, S))
        out = []
        for i, t in enumerate(self.transports):
            fwd = self._fwd_workers[i]
            bwd = (self._bwd_workers[i]
                   if i < len(self._bwd_workers) else None)
            busy = fwd.busy_s + (bwd.busy_s if bwd is not None else 0.0)
            durs = sorted(fwd.durations
                          + (bwd.durations if bwd is not None else []))
            p50 = durs[len(durs) // 2] if durs else 0.0
            depth = None
            mesh_info = None
            devices = None
            try:
                h = t.health()
                depth = h.get("counters", {}).get("deferred_apply_depth")
                mesh_info = h.get("mesh")
                devices = h.get("devices")
            except Exception:  # noqa: BLE001 — report stays best-effort
                pass
            # per-stage MFU (ISSUE 20): the party's traced-only program
            # accounting, best-effort — None off-trace, None over HTTP
            # (the wire exposes health, not trace_metadata), and the
            # honest None on CPU where no peak is known
            mfu_val = None
            srv = getattr(t, "server", None)
            if srv is not None and hasattr(srv, "trace_metadata"):
                try:
                    progs = srv.trace_metadata().get("programs", {})
                    mfus = [p.get("mfu") for p in progs.values()
                            if p.get("mfu") is not None]
                    mfu_val = max(mfus) if mfus else None
                except Exception:  # noqa: BLE001 — best-effort
                    pass
            row = {
                "stage": i + 1,
                "schedule": self.schedule,
                "warmup_depth": warm,
                "bubble_fraction": (max(0.0, 1.0 - busy / self._wall_s)
                                    if self._wall_s > 0 else None),
                "bubble_theoretical": theo,
                "bubble_theoretical_gpipe": theo,
                "bubble_theoretical_1f1b": theo,
                "reply_p50_ms": p50 * 1e3,
                "hop_calls": fwd.calls + (bwd.calls if bwd else 0),
                "deferred_apply_depth": depth,
                # per-stage mesh shape (ISSUE 20): the composed-topology
                # report's sharding column — meshless stages report the
                # honest 1-device layout, matching mesh_axes(None)
                "mesh": mesh_info or {"devices": 1, "data": 1},
                # device ids the stage's state lives on (None when the
                # party does not say, e.g. an older remote stage)
                "devices": devices,
                "mfu": mfu_val,
            }
            # compressed hop wire accounting (PR 18): cumulative ratio
            # from the transport's own counters, plus the controller's
            # current density when adaptive density drives this wire
            summ = t.stats.summary()
            if summ.get("compress_wire_bytes"):
                row["compression_ratio"] = summ.get("compression_ratio")
                row["compress_raw_bytes"] = summ["compress_raw_bytes"]
                row["compress_wire_bytes"] = summ["compress_wire_bytes"]
            dc = self.density_controller
            wid = getattr(t, "wire_id", None) or getattr(
                getattr(t, "inner", None), "wire_id", None)
            if dc is not None and wid is not None:
                row["density"] = dc.densities().get(wid)
            out.append(row)
        return out

    def trace_metadata(self) -> Dict[str, Any]:
        """The STAGE_META sidecar payload (obs/spans.py): what
        scripts/trace_report.py's pipeline section renders."""
        return {
            "num_stages": self.plan.num_stages,
            "microbatches": self.microbatches,
            "schedule": self.schedule,
            "warmup_depth": (self.microbatches
                             if self.schedule == "gpipe"
                             else onefb_warmup(self.microbatches,
                                               self.plan.num_stages)),
            "device_native": self._device_native,
            "hub_devices": state_device_ids(self.state),
            "ticks_per_step": pipeline_ticks(self.microbatches,
                                             self.plan.num_stages),
            "steps": self.steps_done,
            "stages": self.stage_report(),
            # adaptive density (PR 18): full deterministic trajectory —
            # absent entirely when no controller is attached, so the
            # report's tolerant parser stays backward-compatible
            **({"density": self.density_controller.snapshot()}
               if self.density_controller is not None else {}),
        }

    def close(self) -> None:
        """Stop the hop workers (transports stay the caller's to
        close)."""
        for w in self._workers:
            w.q.put(None)
        for w in self._workers:
            w.join(timeout=5)
