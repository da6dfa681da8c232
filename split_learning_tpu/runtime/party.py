"""PartyRuntime — the shared core every split-learning party runs on.

``ServerRuntime`` (the 2-party top half) and ``StageRuntime`` (one
K-stage MPMD pipeline party) grew the same machinery twice: a jitted
program table compiled against per-party ``SpecLayout`` sharding specs,
the replay cache + exactly-once claim, the 2BP deferred-apply queue,
runtime-extras export/restore, and the flight/telemetry/metrics
surfaces. This module is the single implementation both are thin
configurations of (ISSUE 20, ROADMAP "Unify shard × stage × replica"):

- construction: one Registry + instrumented lock, dispatch-watchdog
  attach, mesh normalization (a ≤1-device mesh IS the legacy layout and
  collapses to ``None`` — bit-identity is structural, not numerical),
  replay cache, admission controller, wire error-feedback, lineage and
  uptime bookkeeping.
- ``_install_layout`` / ``_jit`` / ``_to_dev`` / ``_check_batch_rows``
  / ``_host_gather``: the PR-11 pjit rules — state trees live on the
  mesh under ``parallel.distributed.server_state_layout``, programs
  compile with explicit NamedSharding in/out specs, host batches H2D-
  scatter straight onto the ``data`` axis, and the one sanctioned D2H
  is the per-shard ``host_gather``.
- barriers and durability: ``flush_deferred`` / ``export_state`` /
  ``export_runtime_extras`` / ``resume_from`` / ``close`` with the
  SLT108/SLT112 ordering (flush-before-read, drop-on-restore) held in
  ONE place, parameterized by two subclass hooks
  (``_reset_protocol_state``, ``_post_resume_hook``).
- observability: ``trace_metadata`` (mesh shape + per-program MFU —
  stages gain it by inheritance), ``note_wire_compression``, and the
  shared metrics folds.

Hot paths stay in the subclasses — ``split_step`` and the coalesced
group dispatch on the server, the three hop ops on a stage — because
their protocol state machines genuinely differ; everything they lean
on lives here.

Replication composes over this surface: ``runtime/replica.py``'s
``ReplicaGroup`` routes any ``PartyRuntime`` (server ops AND hop ops),
so a replicated × sharded × K-stage topology is a configuration, not a
new runtime.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from split_learning_tpu.obs import dispatch_debug as obs_dispatch
from split_learning_tpu.obs import flight as obs_flight
from split_learning_tpu.obs import locks as obs_locks
from split_learning_tpu.obs import spans
from split_learning_tpu.obs.metrics import Registry
from split_learning_tpu.parallel.distributed import server_state_layout
from split_learning_tpu.parallel.mesh import host_gather
from split_learning_tpu.runtime.admission import AdmissionController
from split_learning_tpu.runtime.replay import ReplayCache
from split_learning_tpu.runtime.state import TrainState
from split_learning_tpu.utils.config import Config


class ProtocolError(RuntimeError):
    """Permanent protocol violation (mode mismatch, step replay, unknown
    residual). ``status`` carries the HTTP status the wire transport maps
    it to: 400 = mode guard (reference behavior, src/server_part.py:31-36),
    409 = handshake/state conflict."""

    def __init__(self, message: str, status: int = 409) -> None:
        super().__init__(message)
        self.status = status


def mesh_axes(mesh: Optional[Any]) -> Dict[str, int]:
    """The ``{"devices": n, axis: size, ...}`` dict /health,
    /metrics and trace_metadata all describe a mesh with; the meshless
    answer is the honest 1-device layout, not an empty dict."""
    if mesh is None:
        return {"devices": 1, "data": 1}
    return {"devices": int(mesh.size),
            **{str(k): int(v) for k, v in dict(mesh.shape).items()}}


def state_device_ids(state: TrainState) -> list:
    """Ids of the devices a TrainState lives on, read off its step
    scalar (pinned: one id; on a mesh the scalar is replicated, so every
    mesh device). ``[]`` for a host-restored tree not yet stepped on."""
    step = state.step
    if not isinstance(step, jax.Array):
        return []
    return sorted(d.id for d in step.devices())


class PartyRuntime:
    """Base class: one party's shared runtime machinery. Subclasses own
    their protocol ops and jitted-program tables; everything those lean
    on — lock, mesh layout, replay, deferred queue plumbing, extras,
    metrics — is defined once here. Thread-safe under ``self._lock``
    (reentrant, instrumented)."""

    def __init__(self, cfg: Config, *, party: str, lock_name: str,
                 mesh: Optional[Any] = None,
                 replay_window: int = 8,
                 tenants: int = 1,
                 quota: Optional[Any] = None,
                 slo_ms: Optional[Any] = None,
                 ef_mode: str = "topk8") -> None:
        self.cfg = cfg
        self.party = str(party)
        # obs (PR 2): queue-wait / dispatch histograms behind GET
        # /metrics and self.metrics(). Allocated at init (never on the
        # step path); populated only while tracing is enabled. Created
        # before the lock so the SLT_LOCK_DEBUG watchdog can feed
        # slt_lock_hold_seconds through it.
        self._metrics = Registry()
        self._lock = obs_locks.make_lock(lock_name, registry=self._metrics)
        # dispatch watchdog (slt-lint phase 2): None unless
        # SLT_DISPATCH_DEBUG=1 — every hook below gates on it
        self._dd = obs_dispatch.attach()
        self._ddtok = obs_dispatch.token()
        # sharded party (pjit): a 1-device mesh IS the legacy layout, so
        # normalize it to None and never branch again on the hot path
        if mesh is not None and mesh.size <= 1:
            mesh = None
        if mesh is not None and cfg.mode == "federated":
            raise ValueError(
                "mesh sharding applies to the jitted split/u_split server "
                "stage; the federated server holds plain param trees")
        self._mesh = mesh
        self._layout = None
        self._mesh_data = 1
        # the one device a meshless party pinned its state to
        # (_install_layout); None = left to jax's default placement
        self._device = None
        # per-program MFU accounting (traced-only, under the lock):
        # program name -> [matmul flops total, dispatch seconds, calls];
        # the flops of a (program, arg-shapes) pair are traced once and
        # cached — never on an untraced step path
        self._prog_stats: Dict[str, list] = {}
        self._flops_cache: Dict[Any, float] = {}
        # deferred-apply queue (2BP): subclasses that decouple install
        # one; None means every barrier below is a no-op
        self._deferred: Optional[_DeferredApply] = None
        # exactly-once within a window: applied replies are cached and
        # replayed verbatim to duplicate deliveries; below the window the
        # strict-step 409 still holds (a replay that stale is a protocol
        # bug, not a retry)
        self.replay: Optional[ReplayCache] = (
            ReplayCache(window=replay_window) if replay_window > 0
            else None)
        # admission layer: built only when any knob is non-default, so
        # existing parties pay nothing (admit() is never called)
        self._admission: Optional[AdmissionController] = None
        if tenants > 1 or quota is not None or slo_ms is not None:
            self._admission = AdmissionController(
                tenants=tenants, quota=quota, slo_ms=slo_ms)
        # reply-direction error feedback for the compressed wire modes,
        # keyed (client_id, op) by the transports. Lives on the runtime,
        # not the transport, so it follows the training state:
        # resume_from resets it with everything else. ef_mode "clapping"
        # (PR 18) swaps in the storage-free ledger: identical selection
        # math, but export/restore/merge are no-ops.
        from split_learning_tpu.transport import codec as _codec
        self.ef_mode = str(ef_mode)
        self.wire_ef = _codec.make_wire_ef(self.ef_mode)
        self._wire_totals = [0, 0]  # raw, wire — behind the ratio gauge
        # monotonic commit counter for the runtime-extras sidecar
        # (runtime/checkpoint.py): stamps every export so a restore can
        # reject a sidecar that does not belong to the Orbax step it
        # actually restored
        self._ckpt_lineage = 0
        # build attribution for /health, /metrics and trace_metadata():
        # uptime measured from runtime construction
        self._t_start = time.monotonic()

    # -- mesh layout + program compilation ------------------------------ #
    def _install_layout(self, pin_device: Optional[Any] = None) -> None:
        """Install the PR-11 sharded layout over ``self.state`` (call
        after the subclass builds its TrainState, before compiling): the
        state tree moves onto the mesh (weights along ``model``,
        optimizer mirrors with their weights, scalars replicated) and
        ``_jit`` reads these shardings into every program's in/out
        specs. Without a mesh, ``pin_device`` optionally pins the state
        to that device up front — device-native hop payloads arrive
        committed (transport/device.py), and a committed-ness flip after
        the first apply would retrace every program on the next step."""
        if self._mesh is not None:
            self._layout = server_state_layout(self._mesh)
            self._mesh_data = self._layout.data
            self._state_sharding = self._layout.state(self.state)
            self._params_sharding = self._state_sharding.params
            self._batch_sharding = self._layout.batch()
            self.state = jax.device_put(self.state, self._state_sharding)
        elif pin_device is not None:
            self._device = pin_device
            self.state = jax.device_put(self.state, pin_device)

    def _jit(self, fn: Any, in_sh: Any, out_sh: Any,
             donate: Tuple[int, ...] = ()) -> Any:
        """On a mesh, every program compiles with explicit NamedSharding
        in/out specs: the state/params trees keep the SpecLayout
        placement across steps (donation aliases shard-for-shard),
        batch-shaped values ride the ``data`` axis, scalars replicate.
        Without a mesh this is jax.jit verbatim — the legacy programs."""
        if self._mesh is not None:
            return jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                           donate_argnums=donate)
        return jax.jit(fn, donate_argnums=donate)

    def _to_dev(self, x: Any) -> jax.Array:
        """Host batch -> device. On a sharded party this is the H2D
        scatter onto the ``data``-sharded layout (explicit, so the jitted
        call never implicitly reshards a committed input); device-native
        hop payloads (transport/device.py, PR 16) arrive as jax.Arrays
        and move device-to-device — ``np.asarray`` on one would force
        the very D2H the device transport exists to remove. Without a
        mesh it is the legacy ``jnp.asarray`` — except that a
        device-native payload from a neighbour stage's device moves onto
        this party's pinned one (D2D; the same buffer when it is already
        there, which on one device is always)."""
        if self._mesh is not None:
            if not isinstance(x, jax.Array):
                x = np.asarray(x)
            return jax.device_put(x, self._batch_sharding)
        if self._device is not None and isinstance(x, jax.Array):
            return jax.device_put(x, self._device)
        return jnp.asarray(x)

    def _check_batch_rows(self, rows: int) -> None:
        """Serialized ops on a mesh need the batch to tile the ``data``
        axis exactly (the coalesced path pads its groups instead)."""
        if self._mesh is not None and rows % self._mesh_data != 0:
            raise ProtocolError(
                f"batch of {rows} rows cannot shard over the mesh 'data' "
                f"axis of size {self._mesh_data}; send a multiple of "
                f"{self._mesh_data} (coalesced groups pad automatically)",
                status=400)

    def _host_gather(self, x: Any, rows: Optional[int] = None) -> np.ndarray:
        """The sanctioned D2H for jitted-program outputs (slt-lint
        SLT013): per-addressable-shard gather on a mesh — ``rows`` bounds
        the transfer to the rows the caller actually needs, so a padded
        group's padding never crosses D2H — and a plain ``np.asarray``
        (bit-identical to the legacy transfer) otherwise."""
        out = host_gather(x, rows=rows)
        if self._mesh is not None:
            # gather-byte accounting is mesh-only so the legacy hot path
            # does not grow even a counter update
            self._metrics.incr(spans.GATHER_BYTES, float(out.nbytes))
            fl = obs_flight.get_recorder()
            if fl is not None:
                fl.record(spans.FL_GATHER, party=self.party,
                          nbytes=int(out.nbytes))
        return out

    # -- traced-only MFU accounting ------------------------------------- #
    def _note_flops(self, name: str, fn: Any, args: Tuple[Any, ...],
                    dispatch_s: float) -> None:
        """Fold one traced dispatch into the per-program MFU accounting
        (trace_metadata). Called only while tracing is enabled, with the
        runtime lock held (reentrant — every call site already owns it).
        The matmul-flops trace of a (program, arg shapes) pair runs once
        and is cached; abstract tracing only, so donated jitted fns are
        safe to pass."""
        key = (name,) + tuple(
            (tuple(a.shape), str(a.dtype)) for a in args
            if hasattr(a, "shape") and hasattr(a, "dtype"))
        with self._lock:
            flops = self._flops_cache.get(key)
            if flops is None:
                try:
                    from split_learning_tpu.utils.flops import (
                        jaxpr_matmul_flops)
                    flops = float(jaxpr_matmul_flops(fn, *args))  # slt-lint: disable=SLT001 (abstract jaxpr trace yields a Python int — no device value, no D2H)
                except Exception:
                    flops = 0.0
                self._flops_cache[key] = flops
            st = self._prog_stats.setdefault(name, [0.0, 0.0, 0])
            st[0] += flops
            st[1] += dispatch_s
            st[2] += 1

    def trace_metadata(self) -> Dict[str, Any]:
        """Mesh/MFU sidecar for ``Tracer.export_chrome(metadata=...)``:
        the mesh shape, per-program matmul-flops rates over their
        dispatch windows (collected only while tracing), cumulative
        sharded-gather bytes, and MFU where the device peak is known —
        ``None`` on CPU (utils/flops.device_peak_flops), which is the
        honest answer, not a zero."""
        from split_learning_tpu.utils.flops import device_peak_flops, mfu
        peak = device_peak_flops(jax.devices()[0])
        with self._lock:
            stats = {k: tuple(v) for k, v in self._prog_stats.items()}
            gather = self._metrics.snapshot()["counters"].get(
                spans.GATHER_BYTES, 0.0)
        mesh_info = mesh_axes(self._mesh)
        n_dev = mesh_info["devices"]
        programs = {}
        for name, (fl, secs, calls) in stats.items():
            rate = (fl / secs) if secs > 0 else None
            programs[name] = {
                "calls": calls,
                "model_flops": fl,
                "dispatch_s": secs,
                "model_flops_per_sec": rate,
                "mfu": (mfu(rate, peak * n_dev)
                        if (peak and rate) else None),
            }
        from split_learning_tpu.version import __version__
        return {"mesh": mesh_info,
                "gather_bytes": int(gather),
                "peak_flops_per_device": peak,
                "programs": programs,
                # build attribution: every trace/dump names the build it
                # came from (ISSUE 13 — same fields as /health)
                "build": {"version": __version__,
                          "uptime_seconds": time.monotonic() - self._t_start}}

    # -- barriers / durability ------------------------------------------ #
    def flush_deferred(self) -> int:
        """Flush barrier: apply every queued deferred update now, in
        step order, and return how many were applied. No-op (0) on a
        coupled party. Callers are anything about to READ the party
        state as if training were caught up: ``predict``,
        ``export_state`` (checkpoints), ``MultiClientSplitRunner.
        sync_bottoms``, ``close``. Safe from any thread, and re-entrant
        from under the runtime lock (the lock is reentrant and the
        drain only dispatches — no D2H)."""
        if self._deferred is None:
            return 0
        return self._deferred.flush()

    def export_state(self) -> TrainState:
        """The one sanctioned way to read ``state`` for checkpointing or
        any other export: flushes the deferred-apply queue first (a
        decoupled party's live state may be up to apply_lag updates
        behind the replies already delivered), then returns the
        caught-up TrainState. On a coupled party this is exactly
        ``self.state``."""
        with self._lock:
            if self._deferred is not None:
                self._deferred.flush()
            return self.state

    def export_runtime_extras(self, step: int) -> Dict[str, Any]:
        """Checksummed sidecar payload for the runtime state Orbax does
        not carry: the replay cache (so post-restart duplicates are
        served the pre-crash replies bit-identically) and the topk8 EF
        residual ledger. Flushes the deferred-apply queue first, under
        the same lock as the snapshot — the sidecar must describe the
        same caught-up instant as the ``export_state`` tree it rides
        beside (SLT112's flush-before-save contract)."""
        from split_learning_tpu.runtime import checkpoint as _ckpt
        with self._lock:
            if self._deferred is not None:
                self._deferred.flush()
            self._ckpt_lineage += 1
            payload = _ckpt.build_extras(
                step, self._ckpt_lineage,
                replay=(self.replay.export_state()
                        if self.replay is not None else None),
                # clapping mode exports [] -> falsy -> key omitted: a
                # storage-free party hands off / checkpoints NO ledger
                wire_ef=(self.wire_ef.export_state() or None))
        fl = obs_flight.get_recorder()
        if fl is not None:
            fl.record(spans.FL_CKPT_CAPTURE, step=int(step),
                      party=self.party, lineage=payload["lineage"])
        return payload

    def _reset_protocol_state(self, step: int) -> None:
        """Subclass hook (called under the lock by ``resume_from``):
        re-arm the party's handshake floors and drop pre-restore
        residuals so the next accepted op is ``step`` or later."""
        raise NotImplementedError

    def _post_resume_hook(self) -> None:
        """Subclass hook (under the lock, after extras restore): reset
        any protocol machinery beyond the shared pieces."""

    def resume_from(self, state: TrainState, step: int,
                    extras: Optional[Dict[str, Any]] = None) -> None:
        """Adopt a restored TrainState and re-arm the handshake so the
        next client op must be at step ``step`` or later (checkpoint/
        resume protocol — SURVEY.md §5).

        ``extras`` is the runtime-extras sidecar payload
        (:meth:`export_runtime_extras`, read back through
        ``checkpoint.read_latest_extras``): when present, valid, and
        stamped with this exact ``step``, the replay cache and EF
        residuals are restored from it — a client retrying its
        in-flight step against the recovered party is then served the
        pre-crash reply instead of a 409. Anything else (no sidecar,
        torn file, stale step) falls back to the PR 4 semantics: clear
        the cache, reset the residuals. On a sharded party the restored
        tree (host/single-device values) is re-scattered onto THIS
        party's mesh first — which is what lets a handoff or resume
        reshard state captured under a different layout."""
        from split_learning_tpu.runtime import checkpoint as _ckpt
        use_extras = (extras is not None and _ckpt.extras_valid(extras)
                      and extras["step"] == int(step))
        with self._lock:
            if self._deferred is not None:
                # DROP (not flush) pending applies: they are gradients
                # of the pre-restore lineage — applying them to the
                # restored state would graft stale updates onto a
                # checkpoint that, via export_state, was already flushed
                # when it was taken
                self._deferred.clear()
            if self._mesh is not None:
                # restored trees arrive as host/single-device values;
                # re-install the mesh layout before stepping on them
                state = jax.device_put(state, self._state_sharding)
            else:
                # the reverse reshard: a capture taken under some OTHER
                # party's mesh arrives with leaves still spanning that
                # mesh — move each onto this party's single device (pure
                # D2D, never through host) so the legacy programs keep
                # one stable placement. Host/np restores pass through
                # untouched: the legacy path, bit for bit.
                dev0 = (self._device if self._device is not None
                        else jax.devices()[0])

                def _unshard(x: Any) -> Any:
                    if isinstance(x, jax.Array) \
                            and len(x.sharding.device_set) > 1:
                        return jax.device_put(x, dev0)
                    return x

                state = jax.tree_util.tree_map(_unshard, state)
            self.state = state
            self._reset_protocol_state(int(step))
            # replies from the pre-restore lineage must not be replayable
            # into the restored one — unless the sidecar carries this
            # step's own cache, in which case restoring it is what makes
            # post-restart duplicate delivery exactly-once
            if self.replay is not None:
                if use_extras and "replay" in extras:
                    self.replay.restore_state(
                        _ckpt.decode_obj(extras["replay"]))
                else:
                    self.replay.clear()
            # error-feedback residuals describe the *pre-restore* stream;
            # feeding them into post-restore steps would inject stale
            # mass — restore them only from a matching sidecar
            if use_extras and "wire_ef" in extras:
                self.wire_ef.restore_state(
                    _ckpt.decode_obj(extras["wire_ef"]))
            else:
                self.wire_ef.reset()
            if use_extras:
                # future exports must stay monotonic past the restored
                # sidecar's commit counter
                self._ckpt_lineage = max(self._ckpt_lineage,
                                         int(extras["lineage"]))
            self._post_resume_hook()
        fl = obs_flight.get_recorder()
        if fl is not None:
            fl.record(spans.FL_CKPT_LINEAGE, step=int(step),
                      party=self.party, use_extras=use_extras,
                      lineage=self._ckpt_lineage)

    def _close_hook(self) -> None:
        """Subclass hook: drain party-specific machinery (e.g. the
        server's coalescer) BEFORE the deferred queue — final groups
        enqueue applies of their own."""

    def close(self) -> None:
        """Drain, never drop: replies for queued steps already shipped,
        so a clean shutdown must land their updates (the mid-run close()
        drain SLT108 pins)."""
        fl = obs_flight.get_recorder()
        if fl is not None:
            fl.record(spans.FL_CLOSE, party=self.party)
        self._close_hook()
        if self._deferred is not None:
            self._deferred.flush()

    # -- wire compression + replay hooks (transports) ------------------- #
    def note_wire_compression(self, raw_bytes: int, wire_bytes: int) -> None:
        """Fold one compressed exchange (logical fp32 bytes vs bytes on
        the wire, both directions — transports call this per request)
        into the metrics Registry: cumulative byte counters plus the
        ``wire_compression_ratio`` gauge /metrics exposes."""
        raw_i, wire_i = int(raw_bytes), int(wire_bytes)
        raw_f, wire_f = float(raw_i), float(wire_i)
        with self._lock:
            self._wire_totals[0] += raw_i
            self._wire_totals[1] += wire_i
            self._metrics.incr("wire_raw_bytes", raw_f)
            self._metrics.incr("wire_bytes", wire_f)
            if self._wire_totals[1] > 0:
                self._metrics.set_gauge(
                    "wire_compression_ratio",
                    self._wire_totals[0] / self._wire_totals[1])

    def replay_lookup(self, client_id: int, op: str,
                      step: int) -> Tuple[Optional[bytes], Optional[Any]]:
        """For wire servers, the cached reply to a duplicate delivery:
        ``(body, result)`` — ``body`` is the exact encoded bytes of the
        original reply (the bit-identical path, preferred), ``result``
        the in-process result when the bytes were never attached. Both
        None on a miss (or when replay is disabled). Blocks on an
        in-flight entry: a duplicate that lands while the original is
        still materializing off the lock waits for that one D2H instead
        of re-dispatching or 409-ing. Stage wire servers pass the
        composite ``hop_seq(step, mb)`` ordinal, never the bare step."""
        if self.replay is None:
            return None, None
        return self.replay.lookup(client_id, op, step)

    def attach_reply_body(self, client_id: int, op: str, step: int,
                          body: bytes) -> None:
        """Pin the encoded wire reply to the step's cache entry so a
        replay ships the original frame byte-for-byte (same payload,
        same CRC, EF ledger untouched)."""
        if self.replay is not None:
            self.replay.attach_body(client_id, op, step, body)

    # -- shared metrics folds ------------------------------------------- #
    def _fold_shared_metrics(self, snap: Dict[str, Any]) -> None:
        """The scrape-time folds every party shares: uptime, admission
        splits (when multi-tenant), dispatch-watchdog gauges, and the
        mesh-shape gauges on a sharded party."""
        snap["gauges"]["uptime_seconds"] = float(
            time.monotonic() - self._t_start)
        if self._admission is not None:
            # counters already carry the admission_ prefix (obs/spans.py
            # names); render_prometheus turns them into slt_admission_*
            for k, v in self._admission.counters().items():
                snap["counters"][k] = float(v)
            snap["gauges"].update(self._admission.gauges())
        if self._dd is not None:
            # watchdog gauges fold in at scrape time; render_prometheus
            # prefixes them slt_
            snap["gauges"].update(self._dd.gauges())
        if self._mesh is not None:
            for k, v in mesh_axes(self._mesh).items():
                snap["gauges"][f"mesh_{k}"] = float(v)


class _DeferredApply:
    """Step-ordered queue of pending party weight updates (2BP).

    The reply path pushes one entry per dispatch (a single step, a
    whole coalesced group, or a pipeline stage's M stacked residuals)
    in lock order — which IS step-application order — and entries drain
    strictly FIFO, each through ``apply_fn`` (the runtime's jitted
    deferred-apply dispatch). Every method takes the OWNING RUNTIME'S
    lock (reentrant), so: on the step path, where the lock is already
    held, re-entry is free and push/drain are atomic with the dispatch
    that produced them; from barrier callers (predict, export_state,
    sync_bottoms, close) on other threads, ``flush`` serializes against
    in-flight steps. Exactly-once by construction — an entry leaves the
    deque exactly when it is applied — and the slt-check scenario
    ``deferred_apply_storm`` explores exactly this object's
    interleavings (invariant SLT108).

    ``lag`` is the staleness bound: ``drain_over_lag`` (called after
    every reply dispatch, still under the lock) applies the oldest
    entries until depth <= lag, so a forward at step t can run on
    weights at most ``lag`` updates old."""

    def __init__(self, apply_fn: Any, lag: int, lock: Any) -> None:
        self._apply = apply_fn
        self.lag = int(lag)
        self._lock = lock
        self._q: "deque[Dict[str, Any]]" = deque()
        self._enqueued = 0
        self._applied = 0
        self._flushes = 0

    def push(self, entry: Dict[str, Any]) -> None:
        with self._lock:
            self._q.append(entry)
            self._enqueued += 1
            depth = len(self._q)
        fl = obs_flight.get_recorder()
        if fl is not None:
            fl.record(spans.FL_DEFER_ENQ, step=entry["step"],
                      client_id=entry["client_id"], party="server",
                      kind=entry["kind"], depth=depth)

    def drain_over_lag(self) -> int:
        """Apply oldest entries until depth <= lag (the staleness
        invariant); 0 applied when the queue is within bounds."""
        return self._drain(limit_to_lag=True)

    def flush(self) -> int:
        """Apply everything queued (the state-export barrier)."""
        return self._drain(limit_to_lag=False)

    def _drain(self, limit_to_lag: bool) -> int:
        n = 0
        with self._lock:
            floor = self.lag if limit_to_lag else 0
            while len(self._q) > floor:
                # pop BEFORE apply: if the apply dispatch raises, the
                # entry must not be retried (its reply already shipped;
                # a second apply would double-count the step)
                entry = self._q.popleft()
                self._apply(entry)
                self._applied += 1
                n += 1
            if n:
                self._flushes += 1
        if n:
            fl = obs_flight.get_recorder()
            if fl is not None:
                fl.record(spans.FL_DEFER_FLUSH, party="server",
                          applied=n,
                          mode=("over_lag" if limit_to_lag else "flush"))
        return n

    def clear(self) -> int:
        """Drop everything queued WITHOUT applying (resume_from only:
        pre-restore-lineage gradients are meaningless against the
        restored state). Returns how many were dropped."""
        with self._lock:
            n = len(self._q)
            self._q.clear()
            return n

    def depth(self) -> int:
        with self._lock:
            return len(self._q)

    def counters(self) -> Dict[str, int]:
        with self._lock:
            return {"deferred_apply_depth": len(self._q),
                    "deferred_enqueued": self._enqueued,
                    "deferred_applied": self._applied,
                    "deferred_flushes": self._flushes}
