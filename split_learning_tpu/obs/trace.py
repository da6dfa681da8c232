"""Per-step distributed tracing across the split-learning parties.

One split step is a chain the reference can't see into (SURVEY.md §5
tracing): client forward -> encode -> wire -> server queue-wait (incl.
the coalescer window) -> jitted dispatch -> wire back -> client
backward -> optimizer apply. This module has ONE span primitive,
:func:`span`, a context manager every instrumented site uses::

    with obs.span(spans.CLIENT_FWD):
        with obs.span(spans.H2D, bytes=x.nbytes):
            xd = jnp.asarray(x)
        ...

A span always enters a ``jax.profiler.TraceAnnotation`` of its name, so
whenever a profiler session runs (``--profile-dir`` on the CLI,
``--trace 1`` in the benchmark) the program's spans are host events in
the same ``.xplane.pb`` as the device operations. While RECORDING it
also appends one record to an in-memory ring: name, span id, parent id
(the span open on this thread), the request's trace id, party, thread,
start and end in nanoseconds on the profiler's clock
(``time.time_ns()``: the xplane's host events are stamped with the same
wall clock), and its few attributes (``bytes``, ``rows``, ``group``,
``reason``; ``counters_read``'s are a step's counters).

Recording is on after :func:`enable` (the CLI's ``--trace PATH`` and
``SLT_TRACE``) and for as long as a profiler session is active
(``TraceAnnotation.is_enabled()``; a span is kept if the session was
active at its entry and at its exit). The last session's records stay
readable through :func:`recorded` until the next session starts.

The taxonomy (names in obs/spans.py, slt-lint SLT003):

- client party: ``step_total`` > ``client_fwd`` (> ``h2d``, ``d2h``),
  ``transport`` (> ``encode``, ``wire``), ``client_bwd`` (> ``h2d``),
  ``opt_apply``; the four phases tile the step (``CLIENT_PHASES``, the
  denominator of :meth:`Tracer.fraction`). ``wire`` is the round trip
  minus what the server reported for itself. ``round`` is the
  parent-less root of a multi-client round on the driving thread.
- server party: ``queue_wait`` (lock wait; enqueue -> group pickup
  under coalescing, window wait included), ``dispatch`` (the lock-held
  window: one span per request on the serialized path, ONE per group
  under coalescing, naming the group's requests in ``traces``), and on
  async-dispatch servers ``d2h``, the off-lock host materialization
  (once per group, on the waiter that redeems it). ``lock_hold`` is a
  metrics histogram fed from ``dispatch``, never a span.
- fused: ``step_total`` > ``h2d``, ``dispatch``, ``loss_wait`` and,
  where the plan's modules sow step counters (``spans.STEP_COUNTERS``:
  the routed layer's pairs and rung, models/afmoe.py),
  ``counters_read``: the one ``device_get`` that brings a step's
  counters to the host. It is opened only while recording, so it is the
  one span that is no annotation when off, and its attributes are the
  record: ``layers`` (module paths) and one list a counter, an entry a
  layer (``pairs``, ``rows``, ``ladder``). On the profiler's clock like
  every record, it lines a step's rungs up with that step's
  ``conditional`` events in the device trace, and it rides the Chrome
  export's ``args``.

TRACING ADDS NO SYNCHRONISATION. A span measures what its thread did,
including the waits the program itself makes (``np.asarray(acts)``
blocks on the device); it never adds a ``block_until_ready``. What the
device did meanwhile is the device trace's to say, on the same clock.

THE OFF CONTRACT: with recording off a span is one annotation (about a
microsecond with this wrapper, nothing in the trace because no session
runs): no record, no lock, no clock read, no trace id, and no payload
key (the wire format is bit-for-bit the untraced one). Propagation
between threads uses the ``CTX`` thread-local: a span opened with
``trace=(client, step)`` makes the request's trace id and holds it in
``CTX.trace_id`` while it is open; the server side (same thread for
LocalTransport, the HTTP handler thread otherwise) adopts it and
writes ``CTX.server_spans`` back.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Any, Dict, Iterable, List, Optional

import numpy as np
from jax.profiler import TraceAnnotation

from split_learning_tpu.obs import spans
from split_learning_tpu.obs.metrics import Registry

# the profiler's clock: xplane host events carry wall-clock nanoseconds
now_ns = time.time_ns
_profiling = TraceAnnotation.is_enabled


class _Ctx(threading.local):
    """Per-thread propagation slots (None = nothing in flight)."""
    trace_id: Optional[str] = None
    server_spans: Optional[Dict[str, float]] = None
    # the spans open on this thread while recording, innermost last
    stack: Optional[list] = None


CTX = _Ctx()

# Chrome-trace process ids: one synthetic "process" per party
PARTY_PIDS = {"client": 1, "server": 2}

# the phase tuples moved to obs/spans.py (the single home of the span
# taxonomy — slt-lint SLT003); re-exported here for compatibility
CLIENT_PHASES = spans.CLIENT_PHASES
SERVER_PHASES = spans.SERVER_PHASES

_FIELDS = ("name", "party", "tid", "step", "trace_id", "span_id",
           "parent_id", "thread", "start_ns", "end_ns", "dur_ns", "attrs")
_span_ids = itertools.count(1)
_UNSET = object()


class Tracer:
    """The recorder: a bounded ring of span records, aggregated into a
    Registry, exported as Chrome trace events. Thread-safe (spans arrive
    from client worker threads, HTTP handler threads, and the coalescer
    flusher at once)."""

    def __init__(self, registry: Optional[Registry] = None,
                 max_spans: int = 200_000) -> None:
        self.registry = registry if registry is not None else Registry()
        # bounded: a long-running traced server must not grow without
        # limit — oldest spans fall off, histograms keep the full tally
        self._spans: deque = deque(maxlen=max_spans)
        self._lock = threading.Lock()
        self._seq = itertools.count()

    # -------------------------------------------------------------- #
    def new_trace_id(self, client_id: int = 0, step: int = -1) -> str:
        return f"c{client_id}-s{step}-{next(self._seq):06x}"

    def _append(self, record: tuple) -> None:
        with self._lock:
            self._spans.append(record)
        self.registry.observe(record[0], record[10] * 1e-9)

    # -------------------------------------------------------------- #
    def spans(self) -> List[Dict[str, Any]]:
        """The records as dicts: the fields of a record (``start_ns`` /
        ``end_ns`` on the profiler's clock, ``dur_ns`` what the span
        accounts for, ``attrs``) plus ``t_start`` and ``duration`` in
        seconds."""
        with self._lock:
            raw = list(self._spans)
        out = []
        for rec in raw:
            sp = dict(zip(_FIELDS, rec))
            sp["attrs"] = dict(rec[11]) if rec[11] else {}
            sp["t_start"] = rec[8] * 1e-9
            sp["duration"] = rec[10] * 1e-9
            out.append(sp)
        return out

    def phase_summary(self) -> Dict[str, Dict[str, float]]:
        """Per-phase count / total / mean / p50 / p90."""
        by_name: Dict[str, list] = {}
        for sp in self.spans():
            by_name.setdefault(sp["name"], []).append(sp["duration"])
        out = {}
        for name, xs in by_name.items():
            arr = np.asarray(xs)
            out[name] = {
                "count": int(arr.size),
                "total_s": float(arr.sum()),
                "mean_ms": float(arr.mean() * 1e3),
                "p50_ms": float(np.percentile(arr, 50) * 1e3),
                "p90_ms": float(np.percentile(arr, 90) * 1e3),
            }
        return out

    def fraction(self, name: str) -> float:
        """Share of ``name`` in the client-level phase total (the four
        phases that tile a step) — ``fraction('transport')`` answers the
        north-star compute-vs-wire question. 0.0 when nothing was
        recorded."""
        totals: Dict[str, float] = {}
        for sp in self.spans():
            totals[sp["name"]] = totals.get(sp["name"], 0.0) + sp["duration"]
        denom = sum(totals.get(p, 0.0) for p in CLIENT_PHASES)
        return totals.get(name, 0.0) / denom if denom > 0 else 0.0

    # -------------------------------------------------------------- #
    def chrome_events(self, metadata: Optional[Dict[str, Any]] = None,
                      stage_metadata: Optional[Dict[str, Any]] = None
                      ) -> List[Dict[str, Any]]:
        """Chrome trace event objects (``ph: "X"`` complete events,
        absolute µs timestamps on the profiler's clock, one pid per
        party; ``args`` carry the trace id, step, span id, parent id
        and the span's attributes).

        ``metadata`` (e.g. ``ServerRuntime.trace_metadata()`` — mesh
        shape + per-program MFU) is emitted as one extra ``ph: "M"``
        event named ``spans.MESH_META`` so viewers ignore it and
        ``scripts/trace_report.py`` can pick it up without a schema
        change to the span lines. ``stage_metadata``
        (``PipelineRunner.trace_metadata()`` — per-stage bubble/reply
        accounting) rides the same way under ``spans.STAGE_META``."""
        events: List[Dict[str, Any]] = [
            {"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
             "args": {"name": f"slt-{party}"}}
            for party, pid in sorted(PARTY_PIDS.items())
        ]
        if metadata is not None:
            events.append({"name": spans.MESH_META, "ph": "M",
                           "pid": 0, "tid": 0, "args": metadata})
        if stage_metadata is not None:
            events.append({"name": spans.STAGE_META, "ph": "M",
                           "pid": 0, "tid": 0, "args": stage_metadata})
        for sp in self.spans():
            events.append({
                "name": sp["name"], "cat": sp["party"], "ph": "X",
                "ts": sp["start_ns"] / 1e3,
                "dur": sp["dur_ns"] / 1e3,
                "pid": PARTY_PIDS.get(sp["party"], 0), "tid": sp["tid"],
                "args": {"trace_id": sp["trace_id"], "step": sp["step"],
                         "span_id": sp["span_id"],
                         "parent_id": sp["parent_id"], **sp["attrs"]},
            })
        return events

    def export_chrome(self, path: str,
                      metadata: Optional[Dict[str, Any]] = None,
                      stage_metadata: Optional[Dict[str, Any]] = None
                      ) -> str:
        """Write the Chrome-trace JSON array, one event per line (valid
        JSON and line-parseable; Perfetto/chrome://tracing load it
        directly). ``metadata``/``stage_metadata`` ride as ``ph:"M"``
        events (see :meth:`chrome_events`). Returns ``path``."""
        parent = os.path.dirname(os.path.abspath(path))
        os.makedirs(parent, exist_ok=True)
        events = self.chrome_events(metadata=metadata,
                                    stage_metadata=stage_metadata)
        with open(path, "w") as f:
            f.write("[\n")
            for i, ev in enumerate(events):
                tail = "," if i < len(events) - 1 else ""
                f.write(json.dumps(ev) + tail + "\n")
            f.write("]\n")
            f.flush()
        return path


def self_times(records: Iterable[Dict[str, Any]]) -> Dict[int, float]:
    """Seconds of each span not covered by its direct children
    (``duration`` minus the children's, by ``parent_id``), keyed by
    span id — what a phase costs by itself."""
    records = list(records)
    own = {r["span_id"]: r["duration"] for r in records}
    for r in records:
        if r["parent_id"] in own:
            own[r["parent_id"]] -= r["duration"]
    return own


# ------------------------------------------------------------------ #
# the switch: an explicit tracer (None = off, the default), else the
# recorder that follows the profiler's sessions
# ------------------------------------------------------------------ #
_tracer: Optional[Tracer] = None
_switch_lock = threading.Lock()
# the recorder of the profiler session that runs now or ran last; its
# records stay readable until the next session starts
_session: Optional[Tracer] = None
_session_live = False


def enable(registry: Optional[Registry] = None,
           max_spans: int = 200_000) -> Tracer:
    """Install (and return) a fresh global tracer. Call sites pick it
    up on their next span; no restart needed."""
    global _tracer
    with _switch_lock:
        _tracer = Tracer(registry=registry, max_spans=max_spans)
        return _tracer


def disable() -> Optional[Tracer]:
    """Turn tracing off; returns the tracer that was active (so callers
    can still export/summarize what it collected)."""
    global _tracer
    with _switch_lock:
        t, _tracer = _tracer, None
        return t


def get_tracer() -> Optional[Tracer]:
    """The explicit tracer (``enable()``), or None."""
    return _tracer


def enabled() -> bool:
    return _tracer is not None


def maybe_enable_from_env() -> Optional[Tracer]:
    """Honor ``SLT_TRACE`` (any non-empty value; a path means "export
    the Chrome trace there on exit" — the caller owns the export)."""
    if os.environ.get("SLT_TRACE") and not enabled():
        return enable()
    return get_tracer() if enabled() else None


def _session_recorder() -> Tracer:
    """The recorder of the running profiler session; a session seen for
    the first time starts a fresh ring."""
    global _session, _session_live
    if not _session_live:
        with _switch_lock:
            if not _session_live:
                _session = Tracer()
                _session_live = True
    return _session


def _session_over() -> None:
    global _session_live
    _session_live = False


def _active() -> Optional[Tracer]:
    """Who records right now: the explicit tracer, else the running
    profiler session's recorder, else nobody. Session boundaries are
    seen here, at span entries and at reads."""
    tr = _tracer
    if tr is not None:
        return tr
    if _profiling():
        return _session_recorder()
    if _session_live:
        _session_over()
    return None


def recording() -> bool:
    return _active() is not None


def nbytes(*arrays: Any) -> int:
    """Bytes of host or device arrays (shape x dtype: no sync), for a
    copy span's ``bytes``."""
    return sum(int(getattr(a, "nbytes", 0)) for a in arrays)


def stamp() -> Optional[int]:
    """A reading of the spans' clock while recording, else None: for an
    interval whose ends lie on different threads (:func:`span_at`)."""
    return now_ns() if _active() is not None else None


def recorder() -> Optional[Tracer]:
    """The recorder to read: the explicit tracer while enabled, else the
    running or last profiler session's (None if there never was one)."""
    return _active() or _session


def recorded() -> List[Dict[str, Any]]:
    """The span records of :func:`recorder`, oldest first."""
    rec = recorder()
    return rec.spans() if rec is not None else []


# ------------------------------------------------------------------ #
# the primitive
# ------------------------------------------------------------------ #
def _placed(parent: Optional["Span"], party: Optional[str],
            tid: Optional[int], step: Optional[int]) -> tuple:
    """(party, tid, step) of a span: what it was given, else the
    enclosing span's, else a client's step outside any."""
    if parent is None:
        return (party if party is not None else "client",
                int(tid) if tid is not None else 0,
                int(step) if step is not None else -1)
    return (party if party is not None else parent.party,
            int(tid) if tid is not None else parent.tid,
            int(step) if step is not None else parent.step)


class Span(TraceAnnotation):
    """One span (see the module docstring); made by :func:`span`.

    Keywords that place the span rather than describe it: ``party``,
    ``tid`` (the client id, the Chrome-trace row) and ``step`` default
    to the enclosing span's; ``trace=(client, step)`` marks the root of
    a request (adopt the thread's trace id or make one, and hold it in
    ``CTX.trace_id`` while open); ``trace_id`` names it outright;
    ``registry`` is a second Registry whose histogram of this name is
    fed at exit (a party's own /metrics). The rest are attributes."""

    __slots__ = ("name", "attrs", "_tr", "_ann", "_follows", "_registry",
                 "_prev_trace", "_sub", "party", "tid", "step", "trace_id",
                 "span_id", "parent_id", "t0", "t1")

    def __init__(self, name: str, attrs: Dict[str, Any]) -> None:
        super().__init__(name)
        self.name = name
        self.attrs = attrs
        self._tr = None
        self._ann = False

    @property
    def recording(self) -> bool:
        """Whether this span is (or was) recorded: call sites gate the
        little they do beyond the span itself on it."""
        return self._tr is not None

    @property
    def duration_s(self) -> float:
        """Seconds the closed span accounts for (0.0 when not recorded)."""
        if self._tr is None:
            return 0.0
        return max(self.t1 - self.t0 - self._sub, 0) * 1e-9

    def elapsed_s(self) -> float:
        """Seconds since the span began (0.0 when not recorded)."""
        return (now_ns() - self.t0) * 1e-9 if self._tr is not None else 0.0

    def __enter__(self) -> "Span":
        super().__enter__()
        self._ann = True
        # _active(), unrolled: the clock is read right behind the
        # annotation's own start, before a session's first span makes
        # the recorder
        tr = _tracer
        if tr is not None or _profiling():
            t0 = now_ns()
            follows = tr is None
            if follows:
                tr = _session_recorder()
            self.t0 = self.t1 = t0
            self._begin(tr, follows)
        elif _session_live:
            _session_over()
        return self

    def _begin(self, tr: Tracer, follows: bool) -> None:
        a = self.attrs
        stack = CTX.stack
        if stack is None:
            stack = CTX.stack = []
        parent = stack[-1] if stack else None
        self.party, self.tid, self.step = _placed(
            parent, a.pop("party", None), a.pop("tid", None),
            a.pop("step", None))
        trace = a.pop("trace", None)
        trace_id = a.pop("trace_id", None)
        self._prev_trace = _UNSET
        if trace_id is None:
            trace_id = CTX.trace_id
            if trace_id is None and trace is not None:
                trace_id = tr.new_trace_id(*trace)
            if trace_id is None and parent is not None:
                trace_id = parent.trace_id
        if trace is not None and CTX.trace_id != trace_id:
            self._prev_trace = CTX.trace_id
            CTX.trace_id = trace_id
        self.trace_id = trace_id
        self._registry = a.pop("registry", None)
        self.span_id = next(_span_ids)
        self.parent_id = parent.span_id if parent is not None else None
        self._follows = follows
        self._sub = 0
        stack.append(self)
        self._tr = tr

    def set(self, **attrs: Any) -> None:
        """Attributes known only inside the span (a group's size)."""
        if self._tr is not None:
            self.attrs.update(attrs)

    def restart(self) -> None:
        """Start the recorded window now (a span entered before a lock
        whose window begins once the lock is held)."""
        if self._tr is not None:
            self.t0 = now_ns()

    def subtract(self, seconds: float) -> None:
        """Time inside this span that other spans account for (``wire``:
        what the server reported for itself)."""
        if self._tr is not None:
            self._sub += int(seconds * 1e9)

    def close(self) -> None:
        """End the span now; ``__exit__`` does, and a span that ends
        inside the block that follows it (``queue_wait`` once the lock
        is held) calls it early. Idempotent."""
        if not self._ann:
            return
        self._ann = False
        super().__exit__(None, None, None)
        tr = self._tr
        if tr is None:
            return
        self.t1 = t1 = now_ns()
        stack = CTX.stack
        if stack and self in stack:  # its own thread's, innermost as a rule
            stack.remove(self)
        if self._prev_trace is not _UNSET:
            CTX.trace_id = self._prev_trace
        if self._follows and not _profiling():
            return  # the session ended under it: not in the window
        dur = max(t1 - self.t0 - self._sub, 0)
        tr._append((self.name, self.party, self.tid, self.step,
                    self.trace_id, self.span_id, self.parent_id,
                    threading.get_ident(), self.t0, t1, dur,
                    self.attrs or None))
        if self._registry is not None:
            self._registry.observe(self.name, dur * 1e-9)

    def __exit__(self, exc_type, exc_value, tb) -> None:
        self.close()


def span(name: str, **attrs: Any) -> Span:
    """The one way a span is made: ``with obs.span(spans.NAME, ...)``."""
    return Span(name, attrs)


def span_at(name: str, start_ns: int, end_ns: int, *,
            party: Optional[str] = None, tid: Optional[int] = None,
            step: Optional[int] = None, trace_id: Optional[str] = None,
            registry: Optional[Registry] = None, **attrs: Any) -> None:
    """Record an interval that no single ``with`` can enclose: its ends
    were stamped (:func:`stamp`, or another span's ``t0``/``t1``) on
    different threads or outlive a lock (the coalescer's ``queue_wait``
    from enqueue to pickup, ``reply_grad`` from dispatch to the reply on
    host). Parent and defaults come from the span open on this thread.
    A no-op while nothing records; no annotation (the interval is
    already over)."""
    tr = _active()
    if tr is None or start_ns is None or end_ns is None:
        return
    stack = CTX.stack
    parent = stack[-1] if stack else None
    if trace_id is None:
        trace_id = CTX.trace_id or (
            parent.trace_id if parent is not None else None)
    start_ns, end_ns = int(start_ns), int(end_ns)
    dur = max(end_ns - start_ns, 0)
    tr._append((name, *_placed(parent, party, tid, step), trace_id,
                next(_span_ids),
                parent.span_id if parent is not None else None,
                threading.get_ident(), start_ns, end_ns, dur, attrs or None))
    if registry is not None:
        registry.observe(name, dur * 1e-9)
