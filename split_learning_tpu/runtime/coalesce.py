"""Server-side request coalescing — dynamic batching of split-step traffic.

The serving fix for the multi-client flat-throughput problem (BASELINE.md
config 3): each client's step is a small jitted dispatch under the server
lock, so server throughput is flat in N and per-dispatch overhead dominates
exactly where the accelerator should be amortizing it. Here concurrent
``split_step`` calls enqueue and block on a future; one flusher thread
stacks up to ``max_group`` same-shape requests (or whatever arrived within
``window_s``) into ONE batched dispatch over the concatenated batch.

Semantics (documented trade-off, README "Request coalescing"): the group
applies a SINGLE server SGD update on the group-mean loss instead of N
sequential updates — each client still receives the gradient of its OWN
segment-mean loss (the group gradient rescaled by group/segment size, exact
for per-example losses), so the client-side math is unchanged and a group
of one reproduces the serialized semantics. A group of one is also what a
window flush with a single waiter produces, which is why ``max_group=1``
servers skip this module entirely (bit-for-bit serialized path).

Two flush policies share the queue (``mode`` ctor knob):

- ``"window"`` (default, the original): block for a head request, then
  wait out ``window_s`` from its pickup hoping peers show up; the group
  closes when it is full or the window ends. Before it is dispatched it
  may be cut (*shed*), by what the flusher has measured: ``S(rows)`` is
  the least of a padded row bucket's last few times from dispatch to
  the first reply redeemed (a bucket counts from its second sample: a
  shape's first call compiles; a group dispatched while the one before
  it had no reply yet is not sampled: it queued behind that one). A
  group of ``k`` requests is cut back to its longest proper prefix of
  ``j`` that fills a bucket exactly, the rest going back to the head of
  the queue for the next group, where
  ``j (S(k) - S(j)) > (2k - j) (S(j) + S(rest) - S(k))``: what the
  first ``j`` gain by their smaller step, against what the cut adds to
  the device's time, which the rest wait now and all ``k`` wait again
  on their next requests (closed-loop clients keep the device busy).
  Where a step's time follows its rows (large models) padding three
  requests to four costs a whole request's step and delays all three,
  and two halves answer half the clients a step earlier for little
  more device time than the whole; where a step is mostly fixed cost,
  or a size is unmeasured, the test fails and the group stays whole.
  Counter: ``flush_shed`` beside ``flush_full`` / ``flush_window``.
- ``"continuous"`` (:class:`ContinuousBatcher`): the flusher NEVER
  sleeps on a timer while work is queued — the moment the previous
  group's dispatch returns (with async dispatch, PR 5, that is the
  moment the jitted call is *enqueued*, not completed), the next group
  is whatever is admitted right now, picked earliest-deadline-first on
  the ``deadline`` the admission layer stamped (runtime/admission.py).
  Group size therefore adapts to arrival rate up to ``max_group``
  by itself: idle server -> groups of one at minimum latency; backlog
  -> full groups at maximum amortization.

This is the queue half; the batched math lives in
:meth:`ServerRuntime._dispatch_group` (runtime/server.py), injected as
``dispatch`` so the coalescer stays free of jax and trivially testable.

Decoupled backward (PR 10, ``--decouple-bwd``): the injected dispatch
resolves every waiter's cut-layer gradient and fires their ``done``
events BEFORE the group's single weight update enters the deferred-apply
queue — replies leave on the reply program's dispatch, the apply rides
the device FIFO behind them and may stay queued up to ``apply_lag``
further groups (slt-check invariant SLT108 pins exactly-once, in-order
application). The coalescer itself is unchanged: the contract lives
entirely inside the injected ``dispatch``, which is why this module
still has no idea the split exists.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Deque, Dict, List, Optional, Tuple

import numpy as np

from split_learning_tpu.obs import locks as obs_locks
from split_learning_tpu.transport.base import TransportStats


def pow2_bucket(n: int) -> int:
    """Smallest power of two >= n — group batches pad up to these buckets
    so the jit cache sees O(log max_batch) distinct shapes, not one entry
    per arrival pattern."""
    if n < 1:
        raise ValueError(f"bucket size must be positive (got {n})")
    return 1 << (n - 1).bit_length()


# why a group closed: ``flush_<reason>`` counts each, and they sum to
# ``groups_flushed``
FLUSH_REASONS = ("full", "window", "shed", "continuous")


@dataclass
class _Flush:
    """One dispatched group, as its waiters see it: the first of them to
    hold its reply times the group (window mode's service-time sample),
    unless the group went out while the one before it had no reply yet:
    it queued behind that one on the device, and its time says more of
    the queue than of its step."""

    t0: float
    bucket: int
    queued: bool
    timed: bool = False


@dataclass
class CoalesceRequest:
    """One enqueued split step waiting for its group to flush."""

    acts: np.ndarray
    labels: np.ndarray
    step: int
    client_id: int
    # via the obs.locks seam (late-bound factory, not the class object)
    # so slt-check can substitute a cooperative event during exploration
    done: threading.Event = field(
        default_factory=lambda: obs_locks.make_event("CoalesceRequest.done"))
    # a value, or (async-dispatch servers) a zero-arg thunk submit()
    # redeems on the waiter thread — see ServerRuntime._GroupD2H
    result: Optional[Any] = None
    error: Optional[BaseException] = None
    # obs (obs/trace.py), set by submit() only while something records:
    # the caller's trace id, the enqueue stamp in nanoseconds on the
    # spans' clock (queue_wait = enqueue -> group pickup, window wait
    # included), and the dispatcher's span timings written back for the
    # waiter to surface
    trace_id: Optional[str] = None
    t_enqueue: Optional[int] = None
    server_spans: Optional[dict] = None
    # EDF priority (continuous mode): the monotonic-clock SLO deadline
    # the admission layer stamped, None = no SLO (sorts last, FIFO)
    deadline: Optional[float] = None
    # arrival sequence, stamped under the queue lock at submit: the EDF
    # tie-breaker. Queue position is NOT a substitute — the queue is
    # rebuilt after every partial take, so index order only happens to
    # equal arrival order; equal-deadline pickup must not depend on that
    seq: int = 0
    # window mode: the dispatched group this request went out with
    flush: Optional[_Flush] = None

    def shape_key(self) -> tuple:
        """Requests coalesce only when everything but the batch row count
        matches — mixing trailing shapes or dtypes in one concatenate
        would be a silent shape error or an implicit cast."""
        return (self.acts.shape[1:], self.acts.dtype.str,
                self.labels.shape[1:], self.labels.dtype.str)


class RequestCoalescer:
    """FIFO queue + flusher thread turning concurrent requests into groups.

    ``dispatch(group, flush_reason)`` must resolve every request in the
    group (set ``result`` or ``error`` and fire ``done``); the coalescer
    guarantees each request is handed to exactly one dispatch call, in
    arrival order within a shape class. Requests whose shape differs from
    the group head's are left queued for the next group, so a mixed-shape
    burst degrades to per-shape groups instead of failing.

    Counters (all under ``stats.counters``, reported by the server's
    /health): ``groups_flushed``, ``requests_coalesced``, ``flush_full`` /
    ``flush_window`` / ``flush_shed`` (why each group closed,
    :data:`FLUSH_REASONS`; they sum to ``groups_flushed``), plus the
    dispatcher's own ``compile_count``. ``stats.record`` times each
    flush, so the p50/p99 the summary reports are per-group dispatch
    latencies.
    """

    def __init__(self, dispatch: Callable[[List[CoalesceRequest], str], None],
                 max_group: int, window_s: float,
                 mode: str = "window") -> None:
        if max_group < 2:
            raise ValueError(
                f"coalescing needs max_group >= 2 (got {max_group}); "
                "max_group=1 is the serialized path — don't build a "
                "coalescer for it")
        if window_s < 0:
            raise ValueError(f"window must be >= 0 (got {window_s})")
        if mode not in ("window", "continuous"):
            raise ValueError(
                f"mode must be 'window' or 'continuous' (got {mode!r})")
        self._dispatch = dispatch
        self.max_group = max_group
        self.window_s = window_s
        self.mode = mode
        self.stats = TransportStats()
        self._queue: List[CoalesceRequest] = []
        self._arrivals = 0  # next CoalesceRequest.seq
        # window mode's observations: what the last few dispatched groups
        # of a (shape class, padded row bucket) took; guarded by _cond
        self._served: Dict[tuple, Deque[float]] = {}
        self._last_flush: Optional[_Flush] = None  # the flusher's own
        self._cond = obs_locks.make_condition("RequestCoalescer._cond")
        self._closed = False
        self._thread = obs_locks.make_thread(
            self._run, name="slt-coalescer", daemon=True)
        self._thread.start()

    # ------------------------------------------------------------------ #
    def submit(self, acts: np.ndarray, labels: np.ndarray, step: int,
               client_id: int, timeout: float = 120.0,
               trace_id: Optional[str] = None,
               t_enqueue: Optional[int] = None,
               deadline: Optional[float] = None
               ) -> Tuple[np.ndarray, float]:
        """Enqueue one request and block until its group's dispatch
        resolves it. Server-side errors (ProtocolError included) re-raise
        in the caller's thread, so the transport-facing contract is
        identical to the serialized path.

        ``trace_id``/``t_enqueue`` (obs): set by the runtime only while
        something records (``obs.stamp()``: nanoseconds on the spans'
        clock); the dispatcher's span timings come back via
        ``req.server_spans`` and are republished on this caller thread's
        CTX so the transport can return them to the client."""
        req = CoalesceRequest(np.asarray(acts), np.asarray(labels),
                              step, client_id, trace_id=trace_id,
                              t_enqueue=t_enqueue, deadline=deadline)
        with self._cond:
            if self._closed:
                raise RuntimeError("coalescer is closed")
            req.seq = self._arrivals
            self._arrivals += 1
            self._queue.append(req)
            depth = len(self._queue)
            self._cond.notify_all()
        # lazy import, like the obs_trace republish below: the flight
        # recorder must not land on the pure queue unit tests' surface
        from split_learning_tpu.obs import flight as obs_flight
        fl = obs_flight.get_recorder()
        if fl is not None:
            from split_learning_tpu.obs import spans
            fl.record(spans.FL_GROUP_FORM, step=int(step),
                      client_id=int(client_id), party="server",
                      depth=depth)
        if not req.done.wait(timeout=timeout):
            raise TimeoutError(
                f"coalesced split_step for client {client_id} step {step} "
                f"not flushed within {timeout}s")
        if req.error is None and callable(req.result):
            # async-dispatch servers resolve with a thunk: the dispatch
            # only queued device work, and THIS waiter thread redeems it
            # — the group's (single, shared) host materialization runs
            # here, off the dispatcher, overlapping the next group's
            # device compute. Redeeming may back-fill server_spans (the
            # d2h span is unknown until the transfer happens), so it
            # runs before the republish below.
            req.result = req.result()
        self._note_served(req)
        if req.server_spans is not None:
            # lazy import: keeps the untraced module surface jax- and
            # obs-free for the pure queue unit tests
            from split_learning_tpu.obs import trace as obs_trace
            obs_trace.CTX.server_spans = req.server_spans
        if req.error is not None:
            raise req.error
        assert req.result is not None
        return req.result

    # ------------------------------------------------------------------ #
    def _note_served(self, req: CoalesceRequest) -> None:
        """On the waiter's thread, its reply in hand: the first reply of
        a group says what the group took since its dispatch (window
        mode's sample; a group that failed, or queued behind the one
        before it, says nothing)."""
        flush = req.flush
        if flush is None or flush.timed:
            return
        with self._cond:
            if flush.timed:
                return
            flush.timed = True
            if not flush.queued and req.error is None:
                self._served.setdefault(
                    (req.shape_key(), flush.bucket), deque(maxlen=4)
                ).append(time.monotonic() - flush.t0)

    def _cost(self, key: tuple, rows: int) -> Optional[float]:
        """Seconds from dispatch to the first redeemed reply for a group
        of ``rows`` of shape class ``key``: the least of the bucket's
        last few samples (one that held a compile, or met the clients'
        own work on the device, reads high), None until it has two -- a
        shape's first call is the one that compiles."""
        samples = self._served.get((key, pow2_bucket(rows)))
        if samples is None or len(samples) < 2:
            return None
        return min(samples)

    # ------------------------------------------------------------------ #
    def _collect_group(self) -> Optional[Tuple[List[CoalesceRequest], str]]:
        """Block for a head request, then form the next group by mode:
        window mode gathers same-shape peers until the group is full or
        the window since the head's pickup closes, and cuts the group
        where the measured step times say so (:meth:`_shed`);
        continuous mode takes whatever is queued RIGHT NOW
        (earliest-deadline-first head, then its same-shape peers in EDF
        order) without ever sleeping on a timer. Returns None only at
        shutdown."""
        with self._cond:
            while not self._queue and not self._closed:
                self._cond.wait()
            if not self._queue:
                return None  # closed and drained

            if self.mode == "continuous":
                # EDF: undeadlined requests sort last, and the submit-
                # stamped arrival sequence breaks ties — a tight-SLO
                # tenant's request becomes the head even behind a
                # batch-tenant backlog, and equal-deadline requests pick
                # up in arrival order on every schedule (slt-check's
                # edf_pickup_order invariant)
                order = sorted(
                    range(len(self._queue)),
                    key=lambda i: (
                        self._queue[i].deadline
                        if self._queue[i].deadline is not None
                        else float("inf"), self._queue[i].seq))
                key = self._queue[order[0]].shape_key()
                group: List[CoalesceRequest] = []
                taken = set()
                for i in order:
                    if len(group) >= self.max_group:
                        break
                    if self._queue[i].shape_key() == key:
                        group.append(self._queue[i])
                        taken.add(i)
                self._queue = [r for i, r in enumerate(self._queue)
                               if i not in taken]
                reason = ("full" if len(group) >= self.max_group
                          else "continuous")
                return group, reason

            head = self._queue[0]
            key = head.shape_key()
            deadline = time.monotonic() + self.window_s

            def take_matching(group: List[CoalesceRequest]) -> None:
                remaining = []
                for r in self._queue:
                    if len(group) < self.max_group and r.shape_key() == key:
                        group.append(r)
                    else:
                        remaining.append(r)
                self._queue = remaining

            group = []
            take_matching(group)
            while len(group) < self.max_group and not self._closed:
                budget = deadline - time.monotonic()
                if budget <= 0:
                    break
                self._cond.wait(timeout=budget)
                take_matching(group)
            if not self._closed and self._shed(key, group):
                return group, "shed"
            reason = "full" if len(group) >= self.max_group else "window"
            return group, reason

    def _shed(self, key: tuple, group: List[CoalesceRequest]) -> bool:
        """Cut ``group`` back to its longest proper prefix that fills a
        row bucket exactly, where the measured times say that pays: the
        prefix answers after its own smaller step, ``S(k) - S(j)``
        sooner each; the cut adds ``S(j) + S(rest) - S(k)`` to the
        device's time (less than nothing where the group only padded
        its bucket), which the rest wait now and everyone waits again on
        the next request. The rest return to the head of the queue, in
        order. A size not measured yet leaves the group whole."""
        rows = [int(r.acts.shape[0]) for r in group]
        fits = [j for j in range(1, len(group))
                if sum(rows[:j]) == pow2_bucket(sum(rows[:j]))]
        if not fits:
            return False
        j, k = fits[-1], len(group)
        whole, first, rest = (self._cost(key, sum(rows)),
                              self._cost(key, sum(rows[:j])),
                              self._cost(key, sum(rows[j:])))
        if whole is None or first is None or rest is None:
            return False
        if j * (whole - first) <= (2 * k - j) * (first + rest - whole):
            return False
        self._queue[:0] = group[j:]
        del group[j:]
        return True

    def _run(self) -> None:
        while True:
            got = self._collect_group()
            if got is None:
                return
            group, reason = got
            from split_learning_tpu.obs import flight as obs_flight
            fl = obs_flight.get_recorder()
            if fl is not None:
                from split_learning_tpu.obs import spans
                fl.record(spans.FL_GROUP_PICKUP, step=int(group[0].step),
                          client_id=int(group[0].client_id),
                          party="server", size=len(group), reason=reason)
            if self.mode == "window":
                last = self._last_flush
                flush = self._last_flush = _Flush(
                    time.monotonic(),
                    pow2_bucket(sum(int(r.acts.shape[0]) for r in group)),
                    queued=last is not None and not last.timed)
                for r in group:
                    r.flush = flush
            t0 = time.perf_counter()
            try:
                self._dispatch(group, reason)
            except BaseException as exc:  # noqa: BLE001 — must not kill
                # the flusher: every waiter gets the failure, the thread
                # lives on for the next group
                for r in group:
                    if not r.done.is_set():
                        r.error = exc
                        r.done.set()
            self.stats.record(time.perf_counter() - t0)
            self.stats.incr("groups_flushed")
            self.stats.incr("requests_coalesced", len(group))
            self.stats.incr(f"flush_{reason}")

    # ------------------------------------------------------------------ #
    def counters(self) -> dict:
        """Snapshot for /health: raw counters plus the derived mean
        occupancy (requests per flushed group — the number the bench leg
        publishes)."""
        with self.stats._lock:
            c = dict(self.stats.counters)
        groups = c.get("groups_flushed", 0)
        c["mean_occupancy"] = (
            c.get("requests_coalesced", 0) / groups if groups else 0.0)
        return c

    def close(self, timeout: float = 5.0) -> None:
        """Stop accepting requests, flush what is queued, join the
        flusher, then fail anything STILL queued (flusher wedged in a
        dispatch, or more arrived than it drained before the join
        deadline) with a terminal error — a waiter must never hang out
        its full submit() timeout because the server shut down under it.
        Idempotent."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        self._thread.join(timeout=timeout)
        with self._cond:
            leftovers, self._queue = self._queue, []
        for r in leftovers:
            if not r.done.is_set():
                r.error = RuntimeError(
                    "coalescer closed before dispatch")
                r.done.set()


class ContinuousBatcher(RequestCoalescer):
    """A :class:`RequestCoalescer` pinned to continuous mode: the next
    dispatch group is whatever is admitted the moment the previous
    group's dispatch returns — no window timer, EDF head selection.
    ``window_s`` exists only so the two modes are ctor-compatible for
    the runtime's ``batching`` knob; continuous collection never waits
    on it."""

    def __init__(self, dispatch: Callable[[List[CoalesceRequest], str], None],
                 max_group: int, window_s: float = 0.0) -> None:
        super().__init__(dispatch, max_group, window_s, mode="continuous")
