"""Server-party runtime: the top-half step, U-trunk hops, and FedAvg.

Re-expresses the reference's FastAPI handler bodies (``src/server_part.py``)
as pure jitted functions over explicit state:

- split step  ≡ ``/forward_pass``  (``src/server_part.py:25-58``): receive
  activations+labels, forward top half, CE loss, backward, SGD step, return
  the cut-layer gradient and the loss.
- aggregate   ≡ ``/aggregate_weights`` (``src/server_part.py:60-93``), but
  with real N-client FedAvg (the reference's averaging is a TODO comment at
  ``src/server_part.py:81-82``; with one client the mean degenerates to the
  reference's overwrite, bit-for-bit).
- health      ≡ ``/health`` (``src/server_part.py:95-102``).

Plus what the reference lacks (SURVEY.md §5): a step handshake — the server
validates that client step counters advance monotonically, instead of
silently desyncing after a client restart.

Since ISSUE 20 the shared machinery — lock/metrics/watchdog wiring, mesh
layout + ``_jit`` sharding specs, replay cache, deferred-apply queue,
runtime-extras export/restore — lives on
:class:`split_learning_tpu.runtime.party.PartyRuntime`; this class is the
2-party configuration of it. ``ProtocolError`` and ``_DeferredApply`` are
re-exported here so every pre-existing import path keeps working.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from split_learning_tpu.core.losses import (
    final_loss, per_example_cross_entropy)
from split_learning_tpu.core.stage import SplitPlan
from split_learning_tpu.obs import dispatch_debug as obs_dispatch
from split_learning_tpu.obs import flight as obs_flight
from split_learning_tpu.obs import locks as obs_locks
from split_learning_tpu.obs import spans
from split_learning_tpu.obs import trace as obs_trace
from split_learning_tpu.runtime.coalesce import (
    CoalesceRequest, RequestCoalescer, pow2_bucket)
from split_learning_tpu.runtime.party import (  # noqa: F401  (re-exports)
    PartyRuntime, ProtocolError, _DeferredApply, mesh_axes)
from split_learning_tpu.runtime.state import (
    TrainState, apply_grads, make_state, make_tx)
from split_learning_tpu.utils.config import Config


class ServerRuntime(PartyRuntime):
    """Holds the server-owned stage state and serves the three ops.

    Thread-safe: HTTP transports may call from handler threads; all state
    transitions happen under one lock, and the math itself is pure."""

    def __init__(self, plan: SplitPlan, cfg: Config, rng: jax.Array,
                 sample_input: np.ndarray, strict_steps: bool = True,
                 coalesce_max: int = 1,
                 coalesce_window_ms: float = 2.0,
                 replay_window: int = 8,
                 batching: str = "window",
                 tenants: int = 1,
                 quota: Optional[Any] = None,
                 slo_ms: Optional[Any] = None,
                 decouple_bwd: bool = False,
                 apply_lag: int = 0,
                 mesh: Optional[Any] = None,
                 ef_mode: str = "topk8") -> None:
        """coalesce_max > 1 turns on request coalescing (classic split
        mode only): concurrent split_step calls that arrive within
        ``coalesce_window_ms`` of each other batch into one dispatch, up
        to ``coalesce_max`` per group (runtime/coalesce.py). 1 = the
        serialized path, bit-for-bit — the coalescer is never built.
        ``batching`` picks the flush policy for that coalescer:
        ``"window"`` (the window/size flusher, which cuts a group
        back to the requests that fill a smaller row bucket where the
        step times it has measured say so, runtime/coalesce.py) or
        ``"continuous"`` (runtime/coalesce.py ContinuousBatcher — the
        next group is whatever is admitted the moment the previous
        group's jitted call is dispatched, picked EDF on admission
        deadlines; requires coalesce_max >= 2).

        ``tenants`` / ``quota`` / ``slo_ms`` switch on multi-tenant
        admission control (runtime/admission.py): clients map to
        tenants by ``client_id %% tenants``; ``quota`` (steps/sec per
        tenant, scalar or per-tenant sequence) bounds each tenant with
        a token bucket — an over-quota split_step raises
        ``Backpressure`` (HTTP 429 + Retry-After on the wire) instead
        of queueing silently; ``slo_ms`` stamps each admitted request
        with an earliest-deadline-first priority the continuous batcher
        honors. Defaults leave the admission layer off entirely.

        ``replay_window`` bounds the per-(client, op) reply cache that
        makes step delivery exactly-once within the window: a duplicate
        or retried request whose original was applied is served the
        original reply instead of 409-ing (runtime/replay.py). 0
        disables the cache and restores at-most-once semantics.

        Host materialization runs off the lock: the lock covers only
        step admission + the jitted dispatch (which returns device
        futures immediately, chaining on the donated state), and the D2H
        transfer (``np.asarray``/``float``) runs after release — step
        t's transfer overlaps step t+1's device compute.

        ``decouple_bwd`` (2BP, arXiv:2405.18047) splits the split-mode
        server step into two dispatches: a *reply* program (forward +
        grad-of-activations only) whose result is materialized and
        returned to the client immediately, and a *deferred apply*
        program (grad-of-weights from the on-device residuals — the
        activations/labels and the params snapshot the reply used — plus
        the optimizer apply) queued in a :class:`_DeferredApply` and
        drained off the reply critical path. ``apply_lag`` bounds the
        queue depth N: step t's forward may use weights from step t−k
        with k ≤ N (k = the queue depth at dispatch), and the over-lag
        tail is drained under the lock right after each reply dispatch,
        so the bound is an invariant, not a hint. ``apply_lag=0`` keeps
        the queue empty across lock releases — every update lands before
        the next step is admitted, which is exactly the legacy
        application order. Flush barriers (``predict``,
        ``export_state``/checkpointing, ``flush_deferred`` for
        ``sync_bottoms``, ``close``) apply everything queued before
        state is read. Default off: the fused legacy program is the only
        thing built and the wire/loss stay bit-for-bit identical.

        ``mesh`` (a ``parallel.mesh.make_mesh``/``make_host_mesh`` Mesh)
        shards the server half: the TrainState lives as a sharded pytree
        under the ``parallel.distributed.SpecLayout`` rule (batch dims
        along ``data``, heavy weight matrices along ``model``), all six
        jitted programs compile with explicit NamedSharding in/out specs,
        and coalesced groups round to a multiple of the ``data`` axis
        (padding rows carry zero weight, so the math is unchanged). A
        mesh of one device — or None, the default — degenerates to the
        legacy single-device programs byte-for-byte, which is what makes
        the mesh=1 bit-identity gate structural rather than numerical."""
        super().__init__(cfg, party="server",
                         lock_name="ServerRuntime._lock", mesh=mesh,
                         replay_window=replay_window, tenants=tenants,
                         quota=quota, slo_ms=slo_ms, ef_mode=ef_mode)
        self.plan = plan
        self.mode = cfg.mode
        self.strict_steps = strict_steps
        # optional hook fired (under the lock) after every completed op
        # with the acknowledged client step — the serve CLI hangs periodic
        # checkpointing off it
        self.on_step: Optional[Any] = None
        # per-client step handshake (multi-client split: SURVEY.md config 3);
        # _step_floor is a global minimum installed by resume_from so that
        # EVERY client — known or not — must resume at or after the
        # checkpointed step
        self._last_step: Dict[int, int] = {}
        self._step_floor = -1

        all_params = plan.init(rng, jnp.asarray(sample_input))
        self._tx = make_tx(cfg)

        self._coalescer: Optional[RequestCoalescer] = None
        if coalesce_max > 1 and cfg.mode != "split":
            raise ValueError(
                f"coalesce_max={coalesce_max} is split-mode only (the "
                "batched group step computes the loss server-side); mode "
                f"is {cfg.mode!r}")
        if batching not in ("window", "continuous"):
            raise ValueError(
                f"batching must be 'window' or 'continuous' "
                f"(got {batching!r})")
        if batching == "continuous" and coalesce_max < 2:
            raise ValueError(
                "continuous batching runs inside the coalescer — raise "
                f"coalesce_max to >= 2 (got {coalesce_max})")
        self.batching = batching
        self.decouple_bwd = bool(decouple_bwd)
        self.apply_lag = int(apply_lag)
        if self.apply_lag < 0:
            raise ValueError(f"apply_lag must be >= 0 (got {apply_lag})")
        if self.apply_lag > 0 and not self.decouple_bwd:
            raise ValueError(
                f"apply_lag={apply_lag} needs decouple_bwd=True (the "
                "deferred-apply queue only exists on a decoupled server)")
        if self.decouple_bwd and cfg.mode != "split":
            raise ValueError(
                "decouple_bwd is split-mode only (the reply/apply split "
                "decouples the classic split step, where the server "
                f"computes the loss); mode is {cfg.mode!r}")

        if cfg.mode == "federated":
            # federated server keeps the full model (ref src/model_def.py:56-57)
            self.state = make_state(tuple(all_params), self._tx)
            self._agg = FedAvgAggregator(cfg.num_clients)
        else:
            server_idx = plan.stages_of("server")
            if len(server_idx) != 1:
                raise ValueError("server must own exactly one contiguous stage")
            self.server_stage = server_idx[0]
            self.state = make_state(all_params[self.server_stage], self._tx)
            self._agg = None
            # install the sharded layout BEFORE compiling: the state
            # tree moves onto the mesh (weights along ``model``,
            # optimizer mirrors with their weights, scalars replicated)
            # and _build_jitted reads these shardings into every
            # program's in/out specs. No-op without a mesh.
            self._install_layout()
            self._build_jitted()
            if self.decouple_bwd:
                self._deferred = _DeferredApply(
                    self._apply_deferred_entry, self.apply_lag, self._lock)
            if coalesce_max > 1:
                # distinct padded group shapes compiled so far — the
                # pow2 buckets bound this at O(log max_group_rows), and
                # its size is the compile_count counter /health reports
                self._coalesce_shapes: set = set()
                self._coalescer = RequestCoalescer(
                    self._dispatch_group, coalesce_max,
                    coalesce_window_ms / 1e3, mode=batching)
        # residuals for the U-shaped two-hop step, keyed by step
        self._u_residual: Dict[int, Any] = {}

    # ------------------------------------------------------------------ #
    def _build_jitted(self) -> None:
        stage = self.plan.stages[self.server_stage]
        tx = self._tx
        is_last = self.server_stage == self.plan.num_stages - 1

        # On a mesh, every program compiles with explicit NamedSharding
        # in/out specs (PartyRuntime._jit): the state/params trees keep
        # the SpecLayout placement across steps (donation aliases
        # shard-for-shard), batch-shaped values ride the ``data`` axis,
        # scalars replicate. Without a mesh, _jit is jax.jit verbatim —
        # the legacy programs.
        if self._mesh is not None:
            batch = self._batch_sharding
            state_sh = self._state_sharding
            params_sh = self._params_sharding
            repl = self._layout.replicated()
        else:
            batch = state_sh = params_sh = repl = None
        _jit = self._jit

        if is_last:
            # classic split: server half computes the loss (ref
            # src/server_part.py:45-52) and returns d(loss)/d(acts).
            def step_fn(state: TrainState, acts, labels):
                def loss_fn(params, acts):
                    return final_loss(stage, params, acts, labels)
                loss, (g_params, g_acts) = jax.value_and_grad(
                    loss_fn, argnums=(0, 1))(state.params, acts)
                new_state = apply_grads(tx, state, g_params)
                return new_state, g_acts, loss

            self._split_step = _jit(
                step_fn, (state_sh, batch, batch), (state_sh, batch, repl),
                donate=(0,))

            # coalesced group step: one dispatch over a concatenated
            # (pow2-padded) group. ``weights`` is 1/num_real on real rows
            # and 0 on padding, so the scalar objective is the group-mean
            # loss and padded rows contribute exactly nothing to either
            # gradient; the per-example vector comes back so the caller
            # can hand each client its own segment-mean loss.
            def group_step_fn(state: TrainState, acts, labels, weights):
                def loss_fn(params, acts):
                    per_ex = final_loss(stage, params, acts, labels,
                                        per_example_cross_entropy)
                    return jnp.sum(per_ex * weights), per_ex
                (_, per_ex), (g_params, g_acts) = jax.value_and_grad(
                    loss_fn, argnums=(0, 1), has_aux=True)(
                        state.params, acts)
                new_state = apply_grads(tx, state, g_params)
                return new_state, g_acts, per_ex

            self._coalesced_step = _jit(
                group_step_fn, (state_sh, batch, batch, batch),
                (state_sh, batch, batch), donate=(0,))

            if self.decouple_bwd:
                # 2BP reply program: forward + d(loss)/d(acts) ONLY —
                # the weight-gradient matmuls and the optimizer apply
                # leave the client's critical path. ``params`` is a
                # plain (non-donated) argument: with apply_lag > 0 the
                # same weights serve several replies before their
                # deferred updates land, and queued entries hold them as
                # the on-device residual snapshot.
                def reply_fn(params, acts, labels):
                    def fwd(acts):
                        return final_loss(stage, params, acts, labels)
                    loss, g_acts = jax.value_and_grad(fwd)(acts)
                    return g_acts, loss

                self._reply_step = _jit(
                    reply_fn, (params_sh, batch, batch), (batch, repl))

                # deferred apply: grad-of-weights recomputed from the
                # entry's residuals (acts/labels + the params snapshot
                # the reply used — delayed-gradient semantics: the
                # update is exactly the gradient of the forward the
                # client saw) + optimizer apply. No donation: at lag=0
                # ``fwd_params`` aliases ``state.params``, and with
                # lag > 0 other queued entries may still hold the same
                # snapshot — donating would invalidate live buffers.
                def deferred_apply_fn(state: TrainState, fwd_params,
                                      acts, labels):
                    def loss_fn(params, acts):
                        return final_loss(stage, params, acts, labels)
                    g_params = jax.grad(loss_fn)(fwd_params, acts)
                    return apply_grads(tx, state, g_params)

                self._deferred_apply = _jit(
                    deferred_apply_fn, (state_sh, params_sh, batch, batch),
                    state_sh)

                # coalesced-group twins of the pair above (group-mean
                # objective, pow2-padded shapes — same bucketing as the
                # fused group step, so compile counts stay bounded)
                def group_reply_fn(params, acts, labels, weights):
                    def fwd(acts):
                        per_ex = final_loss(stage, params, acts, labels,
                                            per_example_cross_entropy)
                        return jnp.sum(per_ex * weights), per_ex
                    (_, per_ex), g_acts = jax.value_and_grad(
                        fwd, has_aux=True)(acts)
                    return g_acts, per_ex

                self._group_reply_step = _jit(
                    group_reply_fn, (params_sh, batch, batch, batch),
                    (batch, batch))

                def group_apply_fn(state: TrainState, fwd_params,
                                   acts, labels, weights):
                    def loss_fn(params, acts):
                        per_ex = final_loss(stage, params, acts, labels,
                                            per_example_cross_entropy)
                        return jnp.sum(per_ex * weights)
                    g_params = jax.grad(loss_fn)(fwd_params, acts)
                    return apply_grads(tx, state, g_params)

                self._group_deferred_apply = _jit(
                    group_apply_fn,
                    (state_sh, params_sh, batch, batch, batch), state_sh)
        else:
            # U-shaped trunk: forward produces features; backward receives
            # d(loss)/d(features) from the client head and returns
            # d(loss)/d(acts), updating trunk params on the way.
            def fwd_fn(params, acts):
                return stage.apply(params, acts)

            def bwd_fn(state: TrainState, acts, g_feats):
                def trunk(params, acts):
                    return stage.apply(params, acts)
                _, vjp = jax.vjp(trunk, state.params, acts)
                g_params, g_acts = vjp(g_feats)
                new_state = apply_grads(tx, state, g_params)
                return new_state, g_acts

            self._u_fwd = _jit(fwd_fn, (params_sh, batch), batch)
            self._u_bwd = _jit(bwd_fn, (state_sh, batch, batch),
                               (state_sh, batch), donate=(0,))

        # inference: the server-owned forward with no loss, no optimizer
        # and no residuals — the serving half of split-party prediction
        # (runtime/evaluate.py evaluate_remote)
        self._predict = _jit(stage.apply, (params_sh, batch), batch)

    # ------------------------------------------------------------------ #
    def _check_step(self, step: int, client_id: int = 0) -> None:
        last = max(self._last_step.get(client_id, -1), self._step_floor)
        if self.strict_steps and step <= last:
            raise ProtocolError(
                f"non-monotonic step {step} from client {client_id} "
                f"(last seen {last}); client restarted or replayed — "
                "refusing to desync")

    def split_step(self, activations: np.ndarray, labels: np.ndarray,
                   step: int, client_id: int = 0) -> Tuple[np.ndarray, float]:
        if self.mode != "split":
            # mode guard ≡ HTTP 400 (ref src/server_part.py:31-36)
            raise ProtocolError(
                f"split_step called in mode {self.mode!r}", status=400)
        # duplicate delivery (lost response, retried request, dup'd
        # frame): claim the step exactly once. Losers of the claim block
        # on the winner's in-flight future — materialization now runs
        # off the lock, so "still materializing" is a real window a
        # retry can land in — and are served the one materialized reply:
        # the update must not run twice, and the client must still get
        # its cut-layer gradient instead of a 409.
        entry = None
        if self.replay is not None:
            entry, owner = self.replay.begin(client_id, "split_step", step)
            if not owner:
                return self.replay.wait(entry)
        # obs: every span below is made by obs_trace.span — off, an
        # annotation and nothing else (no record, no clock read, no
        # lock); on, one record a span. Tracing adds no sync.
        admitted = False
        deadline = None
        try:
            if self._admission is not None:
                # quota gate: Backpressure raised here rides the
                # except-path below, so replay.fail releases the claim
                # and the advised retry re-owns the step cleanly
                deadline = self._admission.admit(client_id)
                admitted = True
            if self._coalescer is not None:
                # block on the group's future; the handshake runs at
                # dispatch-admission time so a replayed step 409s its own
                # client without poisoning the group. The trace id and
                # the enqueue stamp are None unless something records.
                res = self._coalescer.submit(
                    activations, labels, step, client_id,
                    trace_id=obs_trace.CTX.trace_id,
                    t_enqueue=obs_trace.stamp(),
                    deadline=deadline)
                if entry is not None:
                    self.replay.resolve(entry, res)
                if admitted:
                    admitted = False
                    self._admission.complete(client_id)
                fl = obs_flight.get_recorder()
                if fl is not None:
                    fl.record(spans.FL_REPLY, step=step,
                              client_id=client_id, party="server",
                              op="split_step", coalesced=True)
                return res
            who = {"party": "server", "tid": client_id, "step": step,
                   "registry": self._metrics}
            wait = obs_trace.span(spans.QUEUE_WAIT, **who)
            with wait, self._lock:
                wait.close()  # queue_wait ends where the lock is held
                with obs_trace.span(spans.DISPATCH, **who) as disp:
                    self._check_step(step, client_id)
                    self._check_batch_rows(int(np.shape(activations)[0]))
                    with obs_trace.span(
                            spans.H2D,
                            bytes=obs_trace.nbytes(activations, labels)):
                        acts_dev = self._to_dev(activations)
                        labels_dev = self._to_dev(labels)
                    if self._deferred is not None:
                        # 2BP: dispatch the reply program on the current
                        # (<= apply_lag steps stale) weights, queue the
                        # weight update with its on-device residuals, and
                        # drain only the over-lag tail. The drained applies
                        # dispatch AFTER the reply, so the device runs the
                        # client-visible work first; a replayed duplicate
                        # never reaches here (the begin() claim above), so
                        # it can never re-enqueue an apply.
                        with obs_dispatch.step_scope(
                                self._dd, (self._ddtok, "reply_grad"),
                                sig_fn=lambda: (activations.shape,
                                                str(activations.dtype),
                                                labels.shape,
                                                str(labels.dtype))):
                            g_acts, loss = self._reply_step(
                                self.state.params, acts_dev, labels_dev)
                        self._deferred.push({
                            "kind": "single", "step": step,
                            "client_id": client_id,
                            "fwd_params": self.state.params,
                            "acts": acts_dev, "labels": labels_dev})
                        self._deferred.drain_over_lag()
                        if obs_trace.enabled():
                            self._note_flops(
                                "reply_grad", self._reply_step,
                                (self.state.params, acts_dev, labels_dev),
                                disp.elapsed_s())
                    else:
                        with obs_dispatch.step_scope(
                                self._dd, (self._ddtok, "split_step"),
                                sig_fn=lambda: (activations.shape,
                                                str(activations.dtype),
                                                labels.shape,
                                                str(labels.dtype))):
                            self.state, g_acts, loss = self._split_step(
                                self.state, acts_dev, labels_dev)
                        if obs_trace.enabled():
                            self._note_flops(
                                "split_step", self._split_step,
                                (self.state, acts_dev, labels_dev),
                                disp.elapsed_s())
                    # max(): with strict_steps off (pipelined clients) steps
                    # can arrive out of order, and the acknowledged step —
                    # what /health reports and checkpoints are labeled with —
                    # must never regress below state the server has absorbed
                    acked = max(self._last_step.get(client_id, -1), step)
                    self._last_step[client_id] = acked
                    if self.on_step is not None:
                        self.on_step(acked)
            fl = obs_flight.get_recorder()
            if fl is not None:
                fl.record(spans.FL_DISPATCH, step=step,
                          client_id=client_id, party="server",
                          program=("reply_grad" if self._deferred
                                   is not None else "split_step"))
            # off the lock: the jitted call above returned device
            # futures (async dispatch), so forcing the transfer here
            # lets step t's D2H overlap step t+1's device compute
            with obs_trace.span(spans.D2H, bytes=obs_trace.nbytes(g_acts, loss),
                                **who) as d2h, \
                    obs_dispatch.expected_d2h(self._dd):
                g_host = self._host_gather(g_acts)
                loss_f = float(loss)
            if disp.recording:
                self._publish_server_spans(wait, disp, d2h, who)
            res = (g_host, loss_f)
            if entry is not None:
                self.replay.resolve(entry, res)
            if admitted:
                admitted = False
                self._admission.complete(client_id)
            if fl is not None:
                fl.record(spans.FL_REPLY, step=step, client_id=client_id,
                          party="server", op="split_step",
                          coalesced=False)
            return res
        except BaseException as exc:
            # the apply never produced a reply (admission 409, quota
            # 429, dispatch error): release the claim so a retry can
            # re-own the step, and hand the error to anyone already
            # blocked on it
            # pair the admit before releasing the claim: the in-flight
            # depth gauge must drain on failure too, and doing it here
            # (not in a finally) keeps the claim's fail() the last
            # replay-visible act on the path — a finally would give the
            # handler an exit that skips fail() (slt-lint SLT002)
            if admitted:
                self._admission.complete(client_id)
            if entry is not None:
                self.replay.fail(entry, exc)
            raise

    def _publish_server_spans(self, wait, disp, d2h, who: dict) -> None:
        """What one recorded serialized step adds beyond its spans: the
        ``lock_hold`` histogram (fed from ``dispatch``, the lock-held
        window — as a span it would double-cover it), the step counter,
        on a decoupled server the ``reply_grad`` window (reply dispatch
        -> cut-layer gradient on host, what a trace compares
        against the coupled dispatch + d2h), and ``CTX.server_spans``,
        so the transport can hand the server's seconds back to the
        client (wire accounting)."""
        srv_spans = {spans.QUEUE_WAIT: wait.duration_s,
                     spans.DISPATCH: disp.duration_s,
                     spans.D2H: d2h.duration_s}
        if self._deferred is not None:
            obs_trace.span_at(spans.REPLY_GRAD, disp.t0, d2h.t1, **who)
        self._metrics.observe(spans.LOCK_HOLD, disp.duration_s)
        self._metrics.incr("split_steps_total")
        obs_trace.CTX.server_spans = srv_spans

    def _apply_deferred_entry(self, entry: Dict[str, Any]) -> None:
        """Dispatch one queued weight update (called by _DeferredApply's
        drain, under the runtime lock). Async dispatch only — nothing is
        materialized here, so draining inside a lock-held window is
        legal (SLT001) and cheap: the jitted call returns device futures
        and the lock is released long before they resolve."""
        with obs_trace.span(spans.DEFERRED_APPLY, party="server",
                            tid=entry["client_id"], step=entry["step"],
                            registry=self._metrics):
            if entry["kind"] == "group":
                # freshness captured at reply time holds here too:
                # entries drain FIFO, so the first apply of a padded
                # signature is exactly the apply of the first reply
                # that saw it
                with obs_dispatch.step_scope(
                        self._dd, (self._ddtok, "group_deferred_apply"),
                        fresh=entry["fresh"]):
                    self.state = self._group_deferred_apply(
                        self.state, entry["fwd_params"], entry["acts"],
                        entry["labels"], entry["weights"])
            else:
                acts, labels = entry["acts"], entry["labels"]
                with obs_dispatch.step_scope(
                        self._dd, (self._ddtok, "deferred_apply"),
                        sig_fn=lambda: (acts.shape, str(acts.dtype),
                                        labels.shape, str(labels.dtype))):
                    self.state = self._deferred_apply(
                        self.state, entry["fwd_params"], acts, labels)
        fl = obs_flight.get_recorder()
        if fl is not None:
            fl.record(spans.FL_DEFER_APPLY, step=entry["step"],
                      client_id=entry["client_id"], party="server",
                      kind=entry["kind"])

    def _dispatch_group(self, group: "list[CoalesceRequest]",
                        reason: str) -> None:
        """Flusher callback (runtime/coalesce.py): one batched dispatch
        for a same-shape group. Applies a SINGLE SGD update on the
        group-mean loss; each client receives the gradient of its OWN
        segment-mean loss (the group gradient rescaled by group/segment
        rows — exact, because the loss is per-example) and its
        segment-mean loss, so a group of one reproduces the serialized
        semantics and the client-side math never changes."""
        # group pickup: each request's queue_wait runs from its enqueue
        # (stamped in split_step, on the waiter's thread) to here, the
        # coalescer window included — one span a request, both ends on
        # the same clock
        t_pick = obs_trace.stamp()
        if t_pick is not None:
            for r in group:
                if r.t_enqueue is not None:
                    obs_trace.span_at(
                        spans.QUEUE_WAIT, r.t_enqueue, t_pick,
                        party="server", tid=r.client_id, step=r.step,
                        trace_id=r.trace_id, registry=self._metrics)
        with self._lock:
            # ONE dispatch span for the group's one lock-held window
            # (not a copy per request: that counts the lock len(group)
            # times in any sum); it names its requests in ``traces``
            with obs_trace.span(spans.DISPATCH, party="server", tid=-1,
                                step=max(r.step for r in group),
                                registry=self._metrics,
                                reason=reason) as disp:
                admitted = []
                # a retry can land in the same flush window as its original:
                # leaders compute, followers of the same (client, step) share
                # the leader's reply. (With replay enabled, duplicates are
                # already deduplicated upstream — split_step's begin() claim —
                # so followers only arise on replay-disabled servers.)
                leaders: Dict[Tuple[int, int], CoalesceRequest] = {}
                followers: Dict[Tuple[int, int], list] = {}
                for r in group:
                    key = (r.client_id, r.step)
                    if key in leaders:
                        followers.setdefault(key, []).append(r)
                        continue
                    try:
                        self._check_step(r.step, r.client_id)
                        leaders[key] = r
                        admitted.append(r)
                    except ProtocolError as exc:
                        r.error = exc
                        r.done.set()
                if not admitted:
                    return
                sizes = [int(r.acts.shape[0]) for r in admitted]
                total = sum(sizes)
                padded = pow2_bucket(total)
                if self._mesh_data > 1:
                    # mesh-aware group sizing: the padded group must tile the
                    # ``data`` axis exactly. pow2 buckets are already
                    # multiples when data is a power of two >= the bucket;
                    # the ceil covers small buckets and non-pow2 axes. Padded
                    # rows keep weight 0, so the objective is untouched.
                    padded = -(-max(padded, self._mesh_data)
                               // self._mesh_data) * self._mesh_data
                acts = np.concatenate([r.acts for r in admitted], axis=0)
                labels = np.concatenate([r.labels for r in admitted], axis=0)
                if padded > total:
                    acts = np.concatenate(
                        [acts, np.zeros((padded - total,) + acts.shape[1:],
                                        acts.dtype)])
                    labels = np.concatenate(
                        [labels, np.zeros((padded - total,) + labels.shape[1:],
                                          labels.dtype)])
                weights = np.zeros((padded,), np.float32)
                weights[:total] = 1.0 / total
                sig = (acts.shape, acts.dtype.str, labels.dtype.str)
                fresh = sig not in self._coalesce_shapes
                if fresh:
                    self._coalesce_shapes.add(sig)
                    self._coalescer.stats.incr("compile_count")
                # the coalescer already tracks padded-shape signatures (the
                # compile_count counter above) — hand its freshness verdict
                # to the watchdog instead of double-tracking
                deferred_entry = None
                with obs_trace.span(spans.H2D, bytes=obs_trace.nbytes(
                        acts, labels, weights)):
                    acts_dev = self._to_dev(acts)
                    labels_dev = self._to_dev(labels)
                    w_dev = self._to_dev(weights)
                if self._deferred is not None:
                    # 2BP group dispatch: reply program first (on the
                    # current weights), the group's single weight update
                    # queued and drained only after every member below holds
                    # its reply — replies before apply, by construction
                    with obs_dispatch.step_scope(
                            self._dd, (self._ddtok, "group_reply"),
                            fresh=fresh):
                        g_acts, per_ex = self._group_reply_step(
                            self.state.params, acts_dev, labels_dev, w_dev)
                    deferred_entry = {
                        "kind": "group",
                        "step": max(r.step for r in admitted),
                        "client_id": -1,
                        "fwd_params": self.state.params,
                        "acts": acts_dev, "labels": labels_dev,
                        "weights": w_dev, "fresh": fresh}
                    if obs_trace.enabled():
                        self._note_flops(
                            "group_reply", self._group_reply_step,
                            (self.state.params, acts_dev, labels_dev, w_dev),
                            disp.elapsed_s())
                else:
                    with obs_dispatch.step_scope(
                            self._dd, (self._ddtok, "coalesced_step"),
                            fresh=fresh):
                        self.state, g_acts, per_ex = self._coalesced_step(
                            self.state, acts_dev, labels_dev, w_dev)
                    if obs_trace.enabled():
                        self._note_flops(
                            "coalesced_step", self._coalesced_step,
                            (self.state, acts_dev, labels_dev, w_dev),
                            disp.elapsed_s())
                # what a waiter is told of the group's one dispatch: the
                # lock-held window up to the jitted call's return
                dw = disp.elapsed_s()
                if disp.recording:
                    disp.set(group=len(admitted), rows=total,
                             padded=padded,
                             traces=[r.trace_id for r in admitted])
                fl = obs_flight.get_recorder()
                if fl is not None:
                    # one causal event for the whole batched dispatch; the
                    # per-member replies are journaled by split_step
                    fl.record(spans.FL_DISPATCH,
                              step=max(r.step for r in admitted),
                              party="server",
                              program=("group_reply" if self._deferred
                                       is not None else "coalesced_step"),
                              size=len(admitted), rows=total, padded=padded,
                              reason=reason)
                pg = _GroupD2H(self, g_acts, per_ex, rows=total)
                off = 0
                for r, b in zip(admitted, sizes):
                    # deferred: the flusher thread hands each waiter a
                    # thunk instead of a value, so it is free to collect
                    # group t+1 while group t's waiters share one D2H
                    # (the first to arrive materializes; see _GroupD2H)
                    r.result = pg.segment(r, off, b, total)
                    off += b
                    for f in followers.get((r.client_id, r.step), ()):
                        f.result = r.result
                        f.done.set()
                    acked = max(self._last_step.get(r.client_id, -1), r.step)
                    self._last_step[r.client_id] = acked
                    if self.on_step is not None:
                        self.on_step(acked)
                    if t_pick is not None and r.t_enqueue is not None:
                        # what this waiter hands back to its transport
                        # (wire accounting); d2h is back-filled by the thunk
                        qw = max(t_pick - r.t_enqueue, 0) * 1e-9
                        r.server_spans = {spans.QUEUE_WAIT: qw,
                                          spans.DISPATCH: dw}
                        self._metrics.incr("split_steps_total")
                    r.done.set()
                if deferred_entry is not None:
                    # every member above already holds its result (or D2H
                    # thunk) and its done event is set; only now does the
                    # group's weight update enter the queue, and only the
                    # over-lag tail dispatches behind the replies
                    self._deferred.push(deferred_entry)
                    self._deferred.drain_over_lag()
            if disp.recording:
                self._metrics.observe(spans.LOCK_HOLD, disp.duration_s)

    def predict(self, activations: np.ndarray,
                client_id: int = 0) -> np.ndarray:
        """Forward-only through the server-owned stage: logits for the
        classic split (server holds the head), features for the U-shape
        (the client applies its own head). No step handshake — inference
        is stateless and never desyncs training."""
        if self.mode == "federated":
            raise ProtocolError(
                "predict called in mode 'federated' (the client holds "
                "the full model; evaluate locally)", status=400)
        with self._lock:
            if self._deferred is not None:
                # flush barrier: inference must see every update whose
                # reply has already been delivered, or a predict racing
                # a lagged trainer reads weights the loss series has
                # already moved past
                self._deferred.flush()
            params = self.state.params
        x = jnp.asarray(activations)
        n = int(x.shape[0])
        pad = (-n) % self._mesh_data
        if pad:
            # forward-only, so padding is exact: pad rows to tile the
            # ``data`` axis, gather back only the real ones below
            x = jnp.concatenate(
                [x, jnp.zeros((pad,) + tuple(x.shape[1:]), x.dtype)])
        x = self._to_dev(x)
        with obs_dispatch.step_scope(
                self._dd, (self._ddtok, "predict"),
                sig_fn=lambda: (x.shape, str(x.dtype))):
            out = self._predict(params, x)
        with obs_dispatch.expected_d2h(self._dd):
            return self._host_gather(out, rows=n)

    # bounds on residuals awaiting their hop-2 u_backward. Per-client FIFO
    # cap: one client's backlog can never evict another's live residual.
    # Global cap: residuals of clients that died between hops (and whose
    # client_id never returns) are still reclaimed by other clients'
    # traffic, so total pinned cut-layer memory is bounded regardless of
    # client churn.
    MAX_PENDING_RESIDUALS = 8
    MAX_TOTAL_RESIDUALS = 64

    def u_forward(self, activations: np.ndarray, step: int,
                  client_id: int = 0) -> np.ndarray:
        if self.mode != "u_split":
            raise ProtocolError(
                f"u_forward called in mode {self.mode!r}", status=400)
        # duplicate hop 1: block on / serve the original features and
        # KEEP the stored residual — hop 2 may still be coming
        entry = None
        if self.replay is not None:
            entry, owner = self.replay.begin(client_id, "u_forward", step)
            if not owner:
                return self.replay.wait(entry)
        try:
            with self._lock:
                self._check_step(step, client_id)
                self._check_batch_rows(int(np.shape(activations)[0]))
                acts = self._to_dev(activations)
                with obs_dispatch.step_scope(
                        self._dd, (self._ddtok, "u_fwd"),
                        sig_fn=lambda: (acts.shape, str(acts.dtype))):
                    feats = self._u_fwd(self.state.params, acts)
                self._u_residual[(client_id, step)] = acts
                mine = [k for k in self._u_residual if k[0] == client_id]
                # FIFO eviction (dict preserves insertion order): this
                # client's longest-waiting residual is the likeliest orphan
                for key in mine[:max(len(mine) - self.MAX_PENDING_RESIDUALS,
                                     0)]:
                    del self._u_residual[key]
                # global FIFO backstop: reclaims orphans of dead client_ids
                overflow = len(self._u_residual) - self.MAX_TOTAL_RESIDUALS
                if overflow > 0:
                    for key in list(self._u_residual)[:overflow]:
                        del self._u_residual[key]
            # off the lock: async dispatch returned device futures
            with obs_dispatch.expected_d2h(self._dd):
                feats_host = self._host_gather(feats)
            if entry is not None:
                self.replay.resolve(entry, feats_host)
            return feats_host
        except BaseException as exc:
            if entry is not None:
                self.replay.fail(entry, exc)
            raise

    def u_backward(self, feat_grads: np.ndarray, step: int,
                   client_id: int = 0) -> np.ndarray:
        if self.mode != "u_split":
            raise ProtocolError(
                f"u_backward called in mode {self.mode!r}", status=400)
        # duplicate hop 2: the residual was consumed by the original
        # apply — without the cache this is the "unknown step" failure a
        # lost response turns into
        entry = None
        if self.replay is not None:
            entry, owner = self.replay.begin(client_id, "u_backward", step)
            if not owner:
                return self.replay.wait(entry)
        try:
            with self._lock:
                acts = self._u_residual.pop((client_id, step), None)
                if acts is None:
                    raise ProtocolError(
                        f"u_backward for unknown step {step} "
                        f"(client {client_id})")
                with obs_dispatch.step_scope(
                        self._dd, (self._ddtok, "u_bwd"),
                        sig_fn=lambda: (acts.shape, str(acts.dtype),
                                        feat_grads.shape,
                                        str(feat_grads.dtype))):
                    self.state, g_acts = self._u_bwd(
                        self.state, acts, self._to_dev(feat_grads))
                # max(): with strict_steps off (pipelined clients) steps
                # can arrive out of order, and the acknowledged step —
                # what /health reports and checkpoints are labeled with —
                # must never regress below state the server has absorbed
                acked = max(self._last_step.get(client_id, -1), step)
                self._last_step[client_id] = acked
                if self.on_step is not None:
                    self.on_step(acked)
            # off the lock: async dispatch returned device futures
            with obs_dispatch.expected_d2h(self._dd):
                g_host = self._host_gather(g_acts)
            if entry is not None:
                self.replay.resolve(entry, g_host)
            return g_host
        except BaseException as exc:
            if entry is not None:
                self.replay.fail(entry, exc)
            raise

    def aggregate(self, params: Any, epoch: int, loss: float,
                  step: int, num_examples: Optional[int] = None) -> Any:
        if self.mode != "federated":
            raise ProtocolError(
                f"aggregate called in mode {self.mode!r}", status=400)
        if num_examples is not None and num_examples <= 0:
            raise ProtocolError(
                f"num_examples must be positive (got {num_examples})",
                status=400)
        # submit() blocks until the FedAvg round is full — it must run
        # OUTSIDE the runtime lock or concurrent clients deadlock.
        mean_params = self._agg.submit(
            params,
            weight=float(num_examples) if num_examples is not None else None)
        with self._lock:
            self.state = TrainState(
                params=mean_params,
                opt_state=self.state.opt_state,
                step=self.state.step + 1)
            self._last_step[0] = max(self._last_step.get(0, -1), step)
            if self.on_step is not None:
                self.on_step(step)
        return mean_params

    # -- PartyRuntime hooks --------------------------------------------- #
    def _reset_protocol_state(self, step: int) -> None:
        self._last_step = {}
        self._step_floor = step - 1  # applies to every client_id
        self._u_residual.clear()

    def _post_resume_hook(self) -> None:
        if self._agg is not None:
            # drop any pre-restore FedAvg submissions: averaging stale
            # params into the first post-restore round would corrupt it
            self._agg = FedAvgAggregator(self._agg.num_clients)

    def _close_hook(self) -> None:
        # flush and join the coalescer BEFORE the base drains the
        # deferred queue — the coalescer's final groups enqueue applies
        # of their own (no-op on serialized servers)
        if self._coalescer is not None:
            self._coalescer.close()

    def health(self) -> Dict[str, Any]:
        """≡ GET /health (src/server_part.py:95-102), plus ``step``: the
        highest client step this server has acknowledged (or re-armed to
        via resume_from) — lets a resuming client detect a server that is
        behind its checkpoint instead of silently desyncing."""
        model_type = ("FullModel" if self.mode == "federated"
                      else self.plan.stages[self.plan.stages_of('server')[0]].name)
        with self._lock:
            step = max(self._last_step.values(), default=-1)
            step = max(step, self._step_floor)
        from split_learning_tpu.version import __version__
        info = {"status": "healthy", "mode": self.mode,
                "model_type": model_type, "step": step,
                # pipelined clients (depth > 1) need this False: with W
                # lanes in flight, arrival order is a thread race and the
                # strict handshake would 409 nondeterministically
                "strict_steps": self.strict_steps,
                # build attribution (ISSUE 13): dumps, traces, and
                # scrapes all name the build they came from
                "version": __version__,
                "uptime_seconds": time.monotonic() - self._t_start}
        if self._coalescer is not None:
            info["coalescing"] = {
                "coalesce_max": self._coalescer.max_group,
                "coalesce_window_ms": self._coalescer.window_s * 1e3,
                "batching": self._coalescer.mode,
                **self._coalescer.counters()}
        if self._admission is not None:
            info["admission"] = {
                **self._admission.config(),
                **self._admission.counters(),
                **self._admission.gauges()}
        if self._deferred is not None:
            info["decoupled_bwd"] = {
                "apply_lag": self.apply_lag,
                **self._deferred.counters()}
        if self._mesh is not None:
            info["mesh"] = mesh_axes(self._mesh)
        return info

    def metrics(self) -> Dict[str, Any]:
        """In-process equivalent of ``GET /metrics``: the histogram/
        counter/gauge snapshot (obs/metrics.py Registry.snapshot shape),
        enriched with scrape-time state — the acked step and, on
        coalescing servers, the coalescer counters. Runs entirely off
        the step path (the lock is taken only here, at scrape time)."""
        snap = self._metrics.snapshot()
        h = self.health()
        snap["gauges"]["acked_step"] = float(h["step"])
        for k, v in h.get("coalescing", {}).items():
            if isinstance(v, (int, float)):
                snap["counters"][f"coalesce_{k}"] = float(v)
        if self.replay is not None:
            rc = self.replay.counters()
            snap["gauges"]["replay_cache_size"] = float(
                rc.pop("replay_cache_size"))
            for k, v in rc.items():
                snap["counters"][f"{k}_total"] = float(v)
        if self._deferred is not None:
            dc = self._deferred.counters()
            snap["gauges"]["deferred_apply_depth"] = float(
                dc.pop("deferred_apply_depth"))
            for k, v in dc.items():
                snap["counters"][f"{k}_total"] = float(v)
        self._fold_shared_metrics(snap)
        return snap


class _GroupD2H:
    """Deferred host materialization for one coalesced group.

    ``_dispatch_group`` resolves each request with a thunk instead of a
    value: the flusher thread never blocks on the
    transfer (it is already collecting group t+1), and the first waiter
    thread to redeem its thunk pays the group's single D2H — everyone
    else reads the cached host arrays. The device references are dropped
    after the transfer so the group's buffers are not pinned past it."""

    __slots__ = ("_runtime", "_g_dev", "_per_ex_dev", "_rows",
                 "_lock", "g", "per_ex", "hw")

    def __init__(self, runtime: "ServerRuntime", g_dev, per_ex_dev,
                 rows: Optional[int] = None) -> None:
        self._runtime = runtime
        self._g_dev = g_dev
        self._per_ex_dev = per_ex_dev
        # only the group's real rows cross D2H; the padded tail (zero
        # weight, possibly resident on other mesh devices) stays put
        self._rows = rows
        self._lock = obs_locks.make_lock("_GroupD2H._lock", reentrant=False)
        self.g: Optional[np.ndarray] = None
        self.per_ex: Optional[np.ndarray] = None
        self.hw = 0.0

    def _materialize(self, req: CoalesceRequest) -> None:
        with self._lock:
            if self.g is None:
                # the group's ONE d2h span, on the waiter that pays it
                with obs_trace.span(
                        spans.D2H, party="server", tid=req.client_id,
                        step=req.step, trace_id=req.trace_id,
                        registry=self._runtime._metrics) as d2h:
                    with obs_dispatch.expected_d2h(self._runtime._dd):
                        g = self._runtime._host_gather(
                            self._g_dev, rows=self._rows)
                        per_ex = self._runtime._host_gather(
                            self._per_ex_dev, rows=self._rows)
                    d2h.set(bytes=obs_trace.nbytes(g, per_ex))
                self.hw = d2h.duration_s
                self.g, self.per_ex = g, per_ex
                self._g_dev = self._per_ex_dev = None

    def segment(self, req: CoalesceRequest, off: int, b: int, total: int):
        """The thunk ``RequestCoalescer.submit`` redeems on the waiter
        thread: materialize (once), slice + rescale this request's
        segment, and back-fill the group's ``d2h`` seconds into the
        request's server spans (unknown at dispatch time — the transfer
        had not happened yet)."""
        def _seg() -> Tuple[np.ndarray, float]:
            self._materialize(req)
            g, per_ex = self.g, self.per_ex
            seg = (g[off:off + b] * (total / b)).astype(g.dtype,
                                                        copy=False)
            res = (seg, float(per_ex[off:off + b].mean()))
            if req.server_spans is not None:
                req.server_spans = dict(req.server_spans,
                                        **{spans.D2H: self.hw})
            return res
        return _seg


class FedAvgAggregator:
    """Real FedAvg over a round of ``num_clients`` submissions.

    The reference aggregates by overwriting with the single client's weights
    (``src/server_part.py:81-83``). The mean over one submission is that
    same overwrite, so 1-client behavior is preserved exactly.
    """

    def __init__(self, num_clients: int) -> None:
        if num_clients < 1:
            raise ValueError("num_clients must be >= 1")
        self.num_clients = num_clients
        self._pending: list = []
        # completed-round means keyed by round id, refcounted by reads: a
        # round's result is read exactly num_clients times (the completing
        # submitter plus every woken waiter — timed-out waiters withdrew
        # their submission, so they were never part of a completed round),
        # then freed. A slow client preempted between its round completing
        # and its wakeup still reads ITS round's mean (the round-1 VERDICT
        # flagged the single-slot predecessor, which a subsequent round
        # could overwrite), and server memory stays O(live rounds) instead
        # of pinning a window of full-model pytrees.
        self._results: Dict[int, list] = {}  # round -> [mean, reads_left]
        self._round = 0
        self._cond = obs_locks.make_condition("FedAvgAggregator._cond")

    def _read_result(self, round_id: int) -> Any:
        slot = self._results[round_id]
        slot[1] -= 1
        if slot[1] <= 0:
            del self._results[round_id]
        return slot[0]

    def submit(self, params: Any, timeout: float = 120.0,
               weight: Optional[float] = None) -> Any:
        """Blocks until the round is full, then returns the mean pytree of
        the round this submission joined (keyed by round id — late wakeups
        never see a newer round's result). ``weight`` is this client's
        FedAvg weight (canonically its example count; None = uniform).
        A round is weighted only when EVERY submission carries a weight —
        mixing a raw example count against a defaulted 1.0 would silently
        near-exclude the defaulting client, so mixed rounds fall back to
        uniform with a warning."""
        if weight is not None and not weight > 0:
            # reject before touching shared state: a bad weight must 400
            # its own client, never poison the round for everyone else
            raise ValueError(f"FedAvg weight must be > 0 (got {weight})")
        entry = (object(), params, weight)  # token: a retry after timeout
        with self._cond:            # must not leave a stale double-count
            round_id = self._round
            self._pending.append(entry)
            if len(self._pending) >= self.num_clients:
                from split_learning_tpu.runtime.state import fedavg_mean
                ws = [w for _, _, w in self._pending]
                if any(w is None for w in ws):
                    if any(w is not None for w in ws):
                        import sys
                        print("[fedavg] mixed weighted/unweighted round "
                              "(some clients omitted num_examples); "
                              "falling back to uniform averaging",
                              file=sys.stderr)
                    ws = None
                self._results[round_id] = [
                    fedavg_mean([p for _, p, _ in self._pending],
                                weights=ws),
                    self.num_clients]
                self._pending = []
                self._round += 1
                self._cond.notify_all()
            else:
                if not self._cond.wait_for(
                        lambda: self._round != round_id, timeout=timeout):
                    self._pending = [e for e in self._pending
                                     if e[0] is not entry[0]]
                    raise TimeoutError(
                        f"FedAvg round incomplete: {len(self._pending)}/"
                        f"{self.num_clients} clients reported")
            return self._read_result(round_id)
