"""StageRuntime — one party of the K-stage MPMD split pipeline (PR 14).

The 2-party split (`runtime/server.py` ServerRuntime) hard-codes ONE cut:
client bottom, server top, one blocking round trip per step. MPMD
pipeline parallelism (arXiv:2412.14374) generalizes the same
decomposition to K stages — each stage is its own program, its own
party, its own optimizer — and PiPar (arXiv:2302.12803) shows the
bubble cost is what microbatching must fill. A StageRuntime owns exactly
one ``SplitPlan`` stage ``i`` (0 < i < K) and serves three hop ops to
the pipeline driver (`runtime/pipeline_runner.py`):

- ``hop_forward(x, step, mb)``   — run the stage forward on one
  microbatch, pin the (params, x) residual for the backward.
- ``hop_backward(g, step, mb)``  — 2BP reply (PR 10): the cut-layer
  cotangent ``d(loss)/d(x)`` is computed and returned IMMEDIATELY from
  the pinned residual; the grad-of-weights + optimizer apply for the
  whole step is deferred onto a :class:`_DeferredApply` queue bounded
  by this stage's own ``apply_lag``.
- ``hop_loss(x, labels, step, mb)`` — the LAST stage's fused hop:
  forward + per-microbatch CE + immediate cut-gradient reply (scaled by
  1/M so the M per-stage weight-gradient contributions sum to exactly
  the batch-mean gradient), weight update deferred like above.

Weight-update unit is one STEP, not one microbatch: all M microbatches
of a step run on the SAME pinned params snapshot (GPipe semantics —
required for the deferred vjp to be the gradient of the forward the
driver saw), and when the step's last cotangent lands the stage queues
ONE deferred entry holding the M stacked residuals; the jitted deferred
program recomputes and sums the M per-microbatch weight gradients and
applies once. At ``apply_lag=0`` that apply lands inside the last
microbatch's backward call — sequential-equivalent, which is what the
M=1 bit-identity test pins.

Exactly-once per hop rides the same replay-claim machinery as the
server (runtime/replay.py): each (client, op, step, mb) is claimed once
under the composite key ``step * MB_STRIDE + mb``; duplicate deliveries
(chaos dup, retried drop_resp) lose the claim and are served the one
materialized reply — a cotangent is never recomputed, a weight update
never double-queued (slt-check scenario ``pipeline_hop_chain``,
invariant SLT113).

Since ISSUE 20 the shared machinery lives on
:class:`split_learning_tpu.runtime.party.PartyRuntime` and a stage can
carry its OWN ``mesh=``: the three hop programs (and the deferred
apply) compile per-stage with NamedSharding specs over the PR-11
``SpecLayout`` rules, incoming hop activations H2D-scatter straight
onto the ``data`` axis (``_to_dev``), and hop replies leave through the
sanctioned per-shard ``_host_gather`` (device-native replies skip it —
the resharding between stage meshes is the transport's job). A
1-device mesh collapses to the legacy single-device programs
byte-for-byte.
"""

from __future__ import annotations

import time
from collections import OrderedDict
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from split_learning_tpu.core.losses import final_loss
from split_learning_tpu.core.stage import SplitPlan
from split_learning_tpu.obs import dispatch_debug as obs_dispatch
from split_learning_tpu.obs import flight as obs_flight
from split_learning_tpu.obs import spans
from split_learning_tpu.obs import trace as obs_trace
from split_learning_tpu.runtime.party import (
    PartyRuntime, ProtocolError, _DeferredApply, mesh_axes,
    state_device_ids)
from split_learning_tpu.runtime.state import (
    TrainState, apply_grads, make_state, make_tx)
from split_learning_tpu.utils.config import Config

# composite replay/chaos key: one monotonic sequence per (step, mb) so
# the bounded replay window and the strict-monotonicity handshake both
# see hops in delivery order. 2**16 microbatches per step is far above
# any real M; the key stays an int so every existing keyed mechanism
# (ReplayCache, ChaosPolicy draws, _AttemptCounter) works unchanged.
MB_STRIDE = 1 << 16

# pending per-step residual records (params snapshot + microbatch
# activations/cotangents) kept before the step's deferred entry forms —
# the u_residual discipline: bounded FIFO, a backward for an evicted
# step is a protocol error, not an OOM
MAX_PENDING_STEPS = 8


def hop_seq(step: int, mb: int) -> int:
    """The composite (step, microbatch) ordinal every hop is keyed by."""
    return int(step) * MB_STRIDE + int(mb)


class StageRuntime(PartyRuntime):
    """One middle/last stage of the MPMD chain. Thread-safe: HTTP
    handler threads and the in-process driver's hop workers may call
    concurrently; all state transitions happen under one reentrant
    lock, materialization runs off it (the async-dispatch discipline)."""

    def __init__(self, plan: SplitPlan, stage_index: int, cfg: Config,
                 rng: jax.Array, sample_input: np.ndarray,
                 strict_steps: bool = True,
                 microbatches: int = 1,
                 apply_lag: int = 0,
                 replay_window: int = 8,
                 tenants: int = 1,
                 quota: Optional[Any] = None,
                 slo_ms: Optional[Any] = None,
                 mesh: Optional[Any] = None,
                 ef_mode: str = "topk8",
                 device: Optional[Any] = None) -> None:
        """``rng``/``sample_input`` are the SHARED plan-level seed and
        stage-0 sample every party initializes the full plan from
        (keeping only its own stage) — the same convention the client
        and server runtimes use, so a chain's parties agree on every
        stage's init without shipping weights.

        ``microbatches`` must match the driver's M: it fixes the 1/M
        loss-hop scaling and the deferred entry's stacked-residual
        arity. ``apply_lag`` is this stage's OWN staleness bound in
        steps (bounds compose per stage across the chain, arXiv:
        1910.05104). ``mesh`` shards THIS stage (per-stage pjit; stages
        of one chain may carry different meshes — the hop wire reshards
        between them). ``device`` is where a meshless stage lives (the
        backend's first by default). Which stage gets which devices is
        the launcher's decision (launch/run.py ``_stage_placement``),
        never derived here."""
        if not 0 < stage_index < plan.num_stages:
            raise ValueError(
                f"stage_index must be in [1, {plan.num_stages - 1}] "
                f"(stage 0 is the client's; got {stage_index})")
        super().__init__(cfg, party=f"stage{int(stage_index)}",
                         lock_name="StageRuntime._lock", mesh=mesh,
                         replay_window=replay_window, tenants=tenants,
                         quota=quota, slo_ms=slo_ms, ef_mode=ef_mode)
        self.plan = plan
        self.stage_index = int(stage_index)
        self.strict_steps = strict_steps
        self.microbatches = int(microbatches)
        if self.microbatches < 1:
            raise ValueError(
                f"microbatches must be >= 1 (got {microbatches})")
        self.apply_lag = int(apply_lag)
        if self.apply_lag < 0:
            raise ValueError(f"apply_lag must be >= 0 (got {apply_lag})")
        self.is_last = self.stage_index == plan.num_stages - 1

        all_params = plan.init(rng, jnp.asarray(sample_input))
        self._tx = make_tx(cfg)
        self.state = make_state(all_params[self.stage_index], self._tx)
        # sharded layout (or, meshless, pin to one device up front:
        # device-native hop payloads arrive committed, and a
        # committed-ness flip after this stage's first apply would
        # retrace every stage program on the next step)
        self._install_layout(
            pin_device=device if device is not None else jax.devices()[0])
        self._build_jitted()

        self._deferred = _DeferredApply(
            self._apply_deferred_entry, self.apply_lag, self._lock)

        # per-(client, step) residual records: the pinned params
        # snapshot + per-microbatch device arrays, until the step's
        # deferred entry forms. FIFO-bounded like the u_residual store.
        self._recs: "OrderedDict[Tuple[int, int], Dict[str, Any]]" = (
            OrderedDict())
        # strict hop handshake: per (client, op) last composite seq
        self._last_seq: Dict[Tuple[int, str], int] = {}
        self._seq_floor = -1
        self._hops = {"hop_fwd": 0, "hop_bwd": 0, "hop_loss": 0}

    # ------------------------------------------------------------------ #
    def _build_jitted(self) -> None:
        stage = self.plan.stages[self.stage_index]
        tx = self._tx
        M = self.microbatches
        # 1/M on the loss hop's reply: the driver sums M per-stage
        # weight-gradient contributions per step, so scaling the
        # per-microbatch CE-mean cotangent here makes that sum exactly
        # the batch-mean gradient — one apply per step, sequential
        # parity. M=1 skips the multiply so the lag=0 chain is
        # BIT-identical to chained sequential steps, not just equal.
        inv_m = 1.0 / float(M)

        # per-stage pjit (PartyRuntime._jit): on a mesh every hop
        # program compiles with explicit NamedSharding in/out specs;
        # without one, _jit is jax.jit verbatim — the legacy programs.
        if self._mesh is not None:
            batch = self._batch_sharding
            state_sh = self._state_sharding
            params_sh = self._params_sharding
            repl = self._layout.replicated()
        else:
            batch = state_sh = params_sh = repl = None
        _jit = self._jit

        def fwd_fn(params, x):
            return stage.apply(params, x)

        self._fwd = _jit(fwd_fn, (params_sh, batch), batch)

        if self.is_last:
            def loss_reply_fn(params, x, labels):
                def fwd(x):
                    return final_loss(stage, params, x, labels)
                loss, g_x = jax.value_and_grad(fwd)(x)
                if M > 1:
                    g_x = g_x * inv_m
                return g_x, loss

            self._loss_reply = _jit(
                loss_reply_fn, (params_sh, batch, batch), (batch, repl))

            def deferred_apply_fn(state: TrainState, fwd_params, xs, ys):
                g_sum = None
                for x, y in zip(xs, ys):
                    def loss_fn(p, x=x, y=y):
                        ce = final_loss(stage, p, x, y)
                        return ce * inv_m if M > 1 else ce
                    gp = jax.grad(loss_fn)(fwd_params)
                    g_sum = gp if g_sum is None else jax.tree_util.tree_map(
                        jnp.add, g_sum, gp)
                return apply_grads(tx, state, g_sum)
        else:
            def bwd_reply_fn(params, x, g_out):
                _, vjp = jax.vjp(lambda x: stage.apply(params, x), x)
                (g_x,) = vjp(g_out)
                return g_x

            self._bwd_reply = _jit(
                bwd_reply_fn, (params_sh, batch, batch), batch)

            def deferred_apply_fn(state: TrainState, fwd_params, xs, gs):
                g_sum = None
                for x, g in zip(xs, gs):
                    _, vjp = jax.vjp(
                        lambda p: stage.apply(p, x), fwd_params)
                    (gp,) = vjp(g)
                    g_sum = gp if g_sum is None else jax.tree_util.tree_map(
                        jnp.add, g_sum, gp)
                return apply_grads(tx, state, g_sum)

        # tuples of M same-shaped microbatch arrays ride in as pytrees,
        # so the deferred program's signature is stable for a fixed M —
        # one compile, zero steady-state recompiles. No donation: with
        # lag > 0 queued entries still hold the params snapshot. The
        # in_shardings leaves broadcast over the M-tuples (pytree
        # prefix), so the sharded twin is still one compile.
        self._deferred_apply_fn = _jit(
            deferred_apply_fn, (state_sh, params_sh, batch, batch),
            state_sh)

    # ------------------------------------------------------------------ #
    def _check_seq(self, op: str, seq: int, client_id: int) -> None:
        last = max(self._last_seq.get((client_id, op), -1),
                   self._seq_floor)
        if self.strict_steps and seq <= last:
            raise ProtocolError(
                f"non-monotonic hop seq {seq} for {op} from client "
                f"{client_id} at stage {self.stage_index} (last seen "
                f"{last}); duplicate outside the replay window — "
                "refusing to desync")

    def _rec_for(self, client_id: int, step: int) -> Dict[str, Any]:
        """The step's residual record, pinning the params snapshot on
        first touch (all M microbatches of a step MUST run on the same
        weights — GPipe semantics, and what makes the deferred vjp the
        gradient of the forward the driver saw)."""
        key = (int(client_id), int(step))
        rec = self._recs.get(key)
        if rec is None:
            with self._lock:  # reentrant: hop ops already hold it
                rec = {"params": self.state.params, "xs": {}, "gs": {},
                       "ys": {}}
            self._recs[key] = rec
            while len(self._recs) > MAX_PENDING_STEPS:
                self._recs.popitem(last=False)
        return rec

    def _maybe_queue_apply(self, rec: Dict[str, Any], key_done: str,
                           client_id: int, step: int) -> None:
        """When the step's last microbatch residual lands, queue ONE
        deferred weight update holding the M stacked residuals and
        drain the over-lag tail (still under the lock — the drain only
        dispatches, SLT001-clean)."""
        done = rec[key_done]
        if len(done) != self.microbatches:
            return
        mbs = range(self.microbatches)
        entry = {
            "kind": "stage", "step": int(step),
            "client_id": int(client_id),
            "fwd_params": rec["params"],
            "xs": tuple(rec["xs"][m] for m in mbs),
            "cts": tuple(done[m] for m in mbs),
        }
        self._recs.pop((int(client_id), int(step)), None)
        self._deferred.push(entry)
        self._deferred.drain_over_lag()

    def _apply_deferred_entry(self, entry: Dict[str, Any]) -> None:
        xs, cts = entry["xs"], entry["cts"]
        with obs_trace.span(spans.DEFERRED_APPLY, party=self.party,
                            tid=entry["client_id"], step=entry["step"],
                            registry=self._metrics), \
                obs_dispatch.step_scope(
                    self._dd,
                    (self._ddtok, f"stage{self.stage_index}_apply"),
                    sig_fn=lambda: tuple((x.shape, str(x.dtype))
                                         for x in xs + cts)):
            self.state = self._deferred_apply_fn(
                self.state, entry["fwd_params"], xs, cts)
        fl = obs_flight.get_recorder()
        if fl is not None:
            fl.record(spans.FL_DEFER_APPLY, step=entry["step"],
                      client_id=entry["client_id"], party=self.party,
                      kind=entry["kind"])

    # -- the three hop ops --------------------------------------------- #
    def hop_forward(self, x: np.ndarray, step: int, mb: int = 0,
                    client_id: int = 0, *,
                    device: bool = False) -> np.ndarray:
        """Forward one microbatch through this stage; the (params, x)
        residual is pinned for the step's backward. On the last stage
        this is a residual-free plain forward (the loss hop is the
        stateful one) — the chain's predict path.

        ``device=True`` (the co-located DeviceTransport's calling
        convention, PR 16) returns the reply as a jax.Array instead of
        materializing it to host numpy: the driver relays the buffer to
        the next stage zero-copy (on a sharded stage, still sharded —
        the transport reshards it onto the NEXT stage's mesh). Replay
        claims store whatever the owner resolved, so duplicates are
        served the same device buffer — exactly-once semantics are
        unchanged."""
        seq = hop_seq(step, mb)
        entry = None
        if self.replay is not None:
            entry, owner = self.replay.begin(client_id, "hop_fwd", seq)
            if not owner:
                return self.replay.wait(entry)
        admitted = False
        try:
            if self._admission is not None:
                self._admission.admit(client_id)
                admitted = True
            with self._lock:
                t0 = obs_trace.stamp()  # None unless something records
                self._check_seq("hop_fwd", seq, client_id)
                self._check_batch_rows(int(np.shape(x)[0]))
                x_dev = self._to_dev(x)
                if not self.is_last:
                    rec = self._rec_for(client_id, step)
                    params = rec["params"]
                else:
                    params = self.state.params
                with obs_dispatch.step_scope(
                        self._dd,
                        (self._ddtok, f"stage{self.stage_index}_fwd"),
                        sig_fn=lambda: (np.shape(x), str(x_dev.dtype))):
                    y = self._fwd(params, x_dev)
                if not self.is_last:
                    rec["xs"][int(mb)] = x_dev
                self._last_seq[(client_id, "hop_fwd")] = seq
                self._hops["hop_fwd"] += 1
            # off the lock: overlap discipline (device replies skip the
            # materialization entirely — dispatch stays async; host
            # replies leave through the one sanctioned gather)
            if device:
                y_host = y
            else:
                with obs_dispatch.expected_d2h(self._dd):
                    y_host = self._host_gather(y)
            if t0 is not None:
                # the stage's forward compute window (dispatch through
                # materialization) — /telemetry's critical-path input
                self._metrics.observe(spans.DISPATCH,
                                      (obs_trace.now_ns() - t0) * 1e-9)
            if entry is not None:
                self.replay.resolve(entry, y_host)
            if admitted:
                admitted = False
                self._admission.complete(client_id)
            fl = obs_flight.get_recorder()
            if fl is not None:
                fl.record(spans.FL_STAGE_REPLY, step=int(step),
                          client_id=int(client_id), party=self.party,
                          op="hop_fwd", stage=self.stage_index,
                          mb=int(mb))
            return y_host
        except BaseException as exc:
            # pair the admit before releasing the claim; fail() is the
            # last replay-visible act on the path (SLT002)
            if admitted:
                self._admission.complete(client_id)
            if entry is not None:
                self.replay.fail(entry, exc)
            raise

    def hop_backward(self, g_out: np.ndarray, step: int, mb: int = 0,
                     client_id: int = 0, *,
                     device: bool = False) -> np.ndarray:
        """2BP reply: return ``d(loss)/d(x)`` for one microbatch
        immediately from the pinned residual; queue the step's weight
        update once its last cotangent lands. ``device=True`` replies
        the cotangent as a jax.Array (see hop_forward)."""
        if self.is_last:
            raise ProtocolError(
                f"hop_backward on the last stage {self.stage_index}; "
                "the loss hop already returned its cotangent",
                status=400)
        seq = hop_seq(step, mb)
        entry = None
        if self.replay is not None:
            entry, owner = self.replay.begin(client_id, "hop_bwd", seq)
            if not owner:
                return self.replay.wait(entry)
        try:
            with self._lock:
                t0 = obs_trace.stamp()  # None unless something records
                self._check_seq("hop_bwd", seq, client_id)
                self._check_batch_rows(int(np.shape(g_out)[0]))
                rec = self._recs.get((int(client_id), int(step)))
                if rec is None or int(mb) not in rec["xs"]:
                    raise ProtocolError(
                        f"unknown pipeline residual for step {step} "
                        f"mb {mb} at stage {self.stage_index} (evicted "
                        "or never forwarded)")
                g_dev = self._to_dev(g_out)
                x_dev = rec["xs"][int(mb)]
                with obs_dispatch.step_scope(
                        self._dd,
                        (self._ddtok, f"stage{self.stage_index}_bwd"),
                        sig_fn=lambda: (np.shape(g_out),
                                        str(g_dev.dtype))):
                    g_in = self._bwd_reply(rec["params"], x_dev, g_dev)
                rec["gs"][int(mb)] = g_dev
                self._maybe_queue_apply(rec, "gs", client_id, step)
                self._last_seq[(client_id, "hop_bwd")] = seq
                self._hops["hop_bwd"] += 1
            if device:  # off the lock
                g_host = g_in
            else:
                with obs_dispatch.expected_d2h(self._dd):
                    g_host = self._host_gather(g_in)
            # the reply window: lock held -> cut gradient off the lock
            obs_trace.span_at(spans.REPLY_GRAD, t0, obs_trace.stamp(),
                              party=self.party, tid=client_id, step=step,
                              registry=self._metrics)
            if entry is not None:
                self.replay.resolve(entry, g_host)
            fl = obs_flight.get_recorder()
            if fl is not None:
                fl.record(spans.FL_STAGE_REPLY, step=int(step),
                          client_id=int(client_id), party=self.party,
                          op="hop_bwd", stage=self.stage_index,
                          mb=int(mb))
            return g_host
        except BaseException as exc:
            if entry is not None:
                self.replay.fail(entry, exc)
            raise

    def hop_loss(self, x: np.ndarray, labels: np.ndarray, step: int,
                 mb: int = 0,
                 client_id: int = 0, *,
                 device: bool = False) -> Tuple[np.ndarray, float]:
        """Last stage's fused hop: forward + per-microbatch CE; the
        (1/M-scaled) cut cotangent and the microbatch loss reply
        immediately, the weight update defers. ``device=True`` replies
        (device cotangent, device loss scalar) — the sanctioned
        loss-edge D2H then happens at the CALLER'S ``expected_d2h``
        region (transport/device.py), not here."""
        if not self.is_last:
            raise ProtocolError(
                f"hop_loss on non-last stage {self.stage_index}; only "
                f"stage {self.plan.num_stages - 1} owns the loss",
                status=400)
        seq = hop_seq(step, mb)
        entry = None
        if self.replay is not None:
            entry, owner = self.replay.begin(client_id, "hop_loss", seq)
            if not owner:
                return self.replay.wait(entry)
        admitted = False
        try:
            if self._admission is not None:
                self._admission.admit(client_id)
                admitted = True
            with self._lock:
                t0 = obs_trace.stamp()  # None unless something records
                self._check_seq("hop_loss", seq, client_id)
                self._check_batch_rows(int(np.shape(x)[0]))
                rec = self._rec_for(client_id, step)
                x_dev = self._to_dev(x)
                y_dev = self._to_dev(labels)
                with obs_dispatch.step_scope(
                        self._dd,
                        (self._ddtok, f"stage{self.stage_index}_loss"),
                        sig_fn=lambda: (np.shape(x), str(x_dev.dtype),
                                        np.shape(labels),
                                        str(y_dev.dtype))):
                    g_x, loss = self._loss_reply(rec["params"], x_dev,
                                                 y_dev)
                rec["xs"][int(mb)] = x_dev
                rec["ys"][int(mb)] = y_dev
                self._maybe_queue_apply(rec, "ys", client_id, step)
                self._last_seq[(client_id, "hop_loss")] = seq
                self._hops["hop_loss"] += 1
            if device:  # off the lock
                g_host, loss_f = g_x, loss
            else:
                # the loss edge: the chain's one sanctioned host exit
                with obs_dispatch.expected_d2h(self._dd):
                    g_host = self._host_gather(g_x)
                    loss_f = float(loss)
            # the reply window: lock held -> cut gradient off the lock
            obs_trace.span_at(spans.REPLY_GRAD, t0, obs_trace.stamp(),
                              party=self.party, tid=client_id, step=step,
                              registry=self._metrics)
            res = (g_host, loss_f)
            if entry is not None:
                self.replay.resolve(entry, res)
            if admitted:
                admitted = False
                self._admission.complete(client_id)
            fl = obs_flight.get_recorder()
            if fl is not None:
                fl.record(spans.FL_STAGE_REPLY, step=int(step),
                          client_id=int(client_id), party=self.party,
                          op="hop_loss", stage=self.stage_index,
                          mb=int(mb))
            return res
        except BaseException as exc:
            if admitted:
                self._admission.complete(client_id)
            if entry is not None:
                self.replay.fail(entry, exc)
            raise

    def predict(self, x: np.ndarray, client_id: int = 0) -> np.ndarray:
        """Forward-only, no residual, no handshake — but behind the
        flush barrier: a read of the stage's params must see every
        update whose reply already shipped. On a sharded stage the
        batch pads up to the ``data`` axis (forward-only, so padding is
        exact) and only the real rows gather back."""
        with self._lock:
            self._deferred.flush()
            xj = jnp.asarray(x)
            n = int(xj.shape[0])
            pad = (-n) % self._mesh_data
            if pad:
                xj = jnp.concatenate(
                    [xj, jnp.zeros((pad,) + tuple(xj.shape[1:]),
                                   xj.dtype)])
            y = self._fwd(self.state.params, self._to_dev(xj))
        with obs_dispatch.expected_d2h(self._dd):
            return self._host_gather(y, rows=n)

    # -- PartyRuntime hooks --------------------------------------------- #
    def _reset_protocol_state(self, step: int) -> None:
        self._recs.clear()
        self._last_seq = {}
        self._seq_floor = int(step) * MB_STRIDE - 1

    # ------------------------------------------------------------------ #
    def counters(self) -> Dict[str, Any]:
        with self._lock:
            out: Dict[str, Any] = dict(self._hops)
            out["pending_steps"] = len(self._recs)
        out.update(self._deferred.counters())
        if self.replay is not None:
            out.update(self.replay.counters())
        return out

    def health(self) -> Dict[str, Any]:
        from split_learning_tpu.version import __version__
        uptime = time.monotonic() - self._t_start
        with self._lock:
            seq = max(self._last_seq.values(), default=-1)
            seq = max(seq, self._seq_floor)
        info = {
            "status": "ok",
            "role": "stage",
            "stage_index": self.stage_index,
            "stage_name": self.plan.stages[self.stage_index].name,
            "is_last": self.is_last,
            "microbatches": self.microbatches,
            "apply_lag": self.apply_lag,
            # the highest step any hop of which this stage has
            # acknowledged (or re-armed to via resume_from) — the same
            # contract ServerRuntime.health() exposes, which is what
            # lets ReplicaGroup fail a sharded stage over mid-run
            "step": max(seq // MB_STRIDE, -1),
            "uptime_s": uptime,  # legacy spelling, pre-PR-17 callers
            "uptime_seconds": uptime,
            "version": __version__,
            "counters": self.counters(),
            # where this stage's state lives: one id when pinned, the
            # mesh's ids when sharded
            "devices": state_device_ids(self.state),
        }
        if self._mesh is not None:
            info["mesh"] = mesh_axes(self._mesh)
        return info

    def metrics(self) -> Dict[str, Any]:
        """In-process equivalent of ``GET /metrics`` — the same
        Registry-snapshot-plus-scrape-time-folds contract
        ServerRuntime.metrics() honors, so stages are first-class
        observability citizens (hop counters as ``_total`` counters,
        depths as gauges, admission splits when multi-tenant). Runs
        entirely off the hop path."""
        snap = self._metrics.snapshot()
        # point-in-time depths are gauges; monotone hop/replay/deferred
        # counts are counters with the server's _total suffix convention
        gauge_keys = ("pending_steps", "deferred_apply_depth",
                      "replay_cache_size")
        for k, v in self.counters().items():
            if k in gauge_keys:
                snap["gauges"][k] = float(v)
            else:
                snap["counters"][f"{k}_total"] = float(v)
        snap["gauges"]["stage_index"] = float(self.stage_index)
        self._fold_shared_metrics(snap)
        return snap
