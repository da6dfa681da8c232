"""Dispatch-hygiene watchdog — the dynamic half of slt-lint phase 2.

The static rules (SLT006–SLT010) prove what they can at the AST level;
this module checks the two properties that only exist at runtime. When
``SLT_DISPATCH_DEBUG=1`` (or :func:`force` for in-process bench legs)
the runtime trainers attach a process-wide :class:`DispatchTracker`
that

* counts XLA compiles via ``jax.monitoring``'s event-duration stream
  (``.../backend_compile_duration`` fires once per real compile, never
  on a cache hit) into the ``slt_compile_count`` gauge,
* flags a **steady-state recompile** the moment any trace/compile event
  fires inside a step scope whose per-callable ordinal is ≥ 2 and whose
  input signature has been seen before — the first call compiles, a
  second may legitimately retrace (weak-type promotion), anything later
  is a compile storm in the making,
* installs ``jax.transfer_guard_device_to_host("disallow")`` so any
  device-to-host transfer *outside* an :func:`expected_d2h` region
  raises at the offending site; the error is recognized on its way out
  of the step scope and counted into ``slt_unexpected_d2h_total``,
* mirrors each real compile onto the trace timeline as an
  ``xla_compile`` span when the global tracer is on, so
  ``scripts/trace_report.py`` can tabulate a recompile storm.

CPU caveat, measured not assumed: on the host-platform (CPU) backend
the transfer guard is inert at every level — device buffers are
zero-copy views of host memory, so guarded transfers never reach the
guard. The guard is still installed faithfully (it works on real
accelerator backends); what the CPU test suite exercises is the
reporting machinery, fed synthetic guard-shaped errors.

With the env var unset every hook in the runtimes is ``None``-gated and
:func:`step_scope`/:func:`expected_d2h` hand back a shared
``nullcontext`` — zero overhead and bit-for-bit identical numerics, the
same off-path convention as chaos, tracing, and obs/locks.py.
tests/conftest.py fails the session if the default tracker holds any
violation at teardown, so tier-1 itself is policed whenever CI exports
``SLT_DISPATCH_DEBUG=1``.
"""

from __future__ import annotations

import contextlib
import itertools
import os
import sys
import threading
from typing import Any, Callable, Dict, Hashable, List, Optional

from split_learning_tpu.obs import spans
from split_learning_tpu.obs import trace as obs_trace

_COMPILE_EVENT_PREFIX = "/jax/core/compile/"
_BACKEND_COMPILE_SUFFIX = "backend_compile_duration"

# a retrace on the second call of a callable can be legitimate
# (weak-type promotion settles after step 0); from here on it cannot
_STEADY_ORDINAL = 2

_forced = False


def enabled() -> bool:
    """Whether dispatch instrumentation is on (env read per call so
    tests can flip it; trainers bind their tracker at construction)."""
    return (_forced
            or os.environ.get("SLT_DISPATCH_DEBUG", "") not in ("", "0"))


def force(flag: bool) -> None:
    """In-process override of the env gate — bench legs measure their
    own compile counts without mutating the environment (the conftest
    session gate arms on the env var only, never on this)."""
    global _forced
    _forced = bool(flag)


_tokens = itertools.count(1)


def token() -> int:
    """Process-unique instance token for step-scope keys. ``id(self)``
    would recycle after gc: a successor allocated at the dead
    instance's address would inherit its ordinals and signature set,
    and the successor's legitimate first compile would be flagged as a
    steady-state recompile."""
    return next(_tokens)


class DispatchTracker:
    """Compile/transfer accounting shared by every runtime that
    attaches while the watchdog is on.

    Step scopes are keyed by whatever hashable the caller passes —
    runtimes use ``(self._ddtok, "split_step")`` with a :func:`token`
    so no two trainer instances ever share ordinals — and count a
    per-key LOCAL ordinal (never the wire step: a server resumed with
    ``resume_from=1000`` still compiles on its local first call)."""

    def __init__(self) -> None:
        self._mu = threading.Lock()
        self._tl = threading.local()
        self.compile_count = 0
        self.unexpected_d2h = 0
        self.violations: List[Dict[str, Any]] = []
        self._ordinals: Dict[Hashable, int] = {}
        self._sigs: Dict[Hashable, set] = {}
        self._flagged: set = set()

    # -- step scopes ------------------------------------------------- #

    def _stack(self) -> List[Dict[str, Any]]:
        st = getattr(self._tl, "stack", None)
        if st is None:
            st = self._tl.stack = []
        return st

    @contextlib.contextmanager
    def scope(self, key: Hashable, sig: Hashable = None,
              fresh: Optional[bool] = None):
        """Mark one dispatch of the callable identified by ``key``.

        ``sig`` is the call's input signature (shapes/dtypes); the first
        time each distinct signature shows up the scope is *fresh* and a
        compile inside it is legitimate at any ordinal. Callers that
        already track signatures (the coalescer's pow2-pad set) pass
        ``fresh`` explicitly instead."""
        with self._mu:
            ordinal = self._ordinals.get(key, 0)
            self._ordinals[key] = ordinal + 1
            if fresh is None:
                if sig is None:
                    fresh = ordinal == 0
                else:
                    seen = self._sigs.setdefault(key, set())
                    fresh = sig not in seen
                    seen.add(sig)
        rec = {"key": key, "ordinal": ordinal, "fresh": bool(fresh)}
        stack = self._stack()
        stack.append(rec)
        try:
            yield rec
        except RuntimeError as exc:
            # a transfer-guard trip inside the scope is an unexpected
            # D2H at a site nobody marked expected_d2h — count it, then
            # let it propagate (debug mode fails loudly)
            self.note_guard_error(exc)
            raise
        finally:
            stack.pop()

    # -- compile events ---------------------------------------------- #

    def on_compile_event(self, event: str, secs: float) -> None:
        """jax.monitoring event-duration listener. Fires (on the
        dispatching thread, synchronously) once per trace stage and once
        per backend compile — never on a cache hit."""
        if not event.startswith(_COMPILE_EVENT_PREFIX):
            return
        is_backend = event.endswith(_BACKEND_COMPILE_SUFFIX)
        if is_backend:
            with self._mu:
                self.compile_count += 1
        stack = self._stack()
        rec = stack[-1] if stack else None
        if rec is None:
            return  # setup/bench-harness compiles outside any step
        if is_backend:
            end = obs_trace.stamp()  # None unless something records
            if end is not None:
                # the event arrives when the compile is over
                obs_trace.span_at(spans.COMPILE, end - int(secs * 1e9),
                                  end, party="server",
                                  step=rec["ordinal"])
        if rec["ordinal"] < _STEADY_ORDINAL or rec["fresh"]:
            return
        mark = (rec["key"], rec["ordinal"])
        with self._mu:
            if mark in self._flagged:
                return
            self._flagged.add(mark)
            self._report({
                "kind": "steady-state-recompile",
                "key": rec["key"],
                "ordinal": rec["ordinal"],
                "event": event,
                "seconds": secs,
                "message": (
                    f"steady-state recompile: {event.rsplit('/', 1)[-1]} "
                    f"({secs * 1e3:.1f} ms) inside step scope "
                    f"{rec['key']!r} at local ordinal {rec['ordinal']} "
                    f"with a previously-seen signature — something in "
                    f"the call varies per step"),
            })

    # -- transfer guard ----------------------------------------------- #

    def note_guard_error(self, exc: BaseException) -> bool:
        """Recognize a ``jax.transfer_guard`` trip (``Disallowed
        device-to-host transfer``). Returns True when counted."""
        msg = str(exc)
        if "Disallowed" not in msg or "transfer" not in msg:
            return False
        with self._mu:
            self.unexpected_d2h += 1
            self._report({
                "kind": "unexpected-d2h",
                "message": f"unexpected device-to-host transfer: {msg}",
            })
        return True

    # -- reporting ----------------------------------------------------- #

    def _report(self, violation: Dict[str, Any]) -> None:
        # caller holds self._mu
        self.violations.append(violation)
        print(f"[slt-dispatch] {violation['message']}", file=sys.stderr)
        # flight-recorder dump trigger #1 (obs/flight.py): lazy import
        # keeps this module importable standalone; trip() never raises
        # and takes no locks, so it is safe under self._mu
        try:
            from split_learning_tpu.obs import flight as obs_flight
            obs_flight.trip("dispatch", violation["message"])
        except Exception:
            pass

    def gauges(self) -> Dict[str, float]:
        """The watchdog's /metrics contribution (runtimes fold this into
        their registry snapshot at scrape time; render_prometheus adds
        the ``slt_`` prefix)."""
        with self._mu:
            steady = sum(1 for v in self.violations
                         if v["kind"] == "steady-state-recompile")
            return {"compile_count": float(self.compile_count),
                    "unexpected_d2h_total": float(self.unexpected_d2h),
                    "steady_state_recompiles": float(steady)}

    def clear(self) -> None:
        with self._mu:
            self.compile_count = 0
            self.unexpected_d2h = 0
            self.violations.clear()
            self._ordinals.clear()
            self._sigs.clear()
            self._flagged.clear()


_default_tracker = DispatchTracker()


def tracker() -> DispatchTracker:
    """The process-wide tracker :func:`attach` hands to runtimes."""
    return _default_tracker


# ------------------------------------------------------------------ #
# listener / guard installation
# ------------------------------------------------------------------ #

_installed = False
_install_lock = threading.Lock()


def _on_event(event: str, secs: float, **_kw: Any) -> None:
    _default_tracker.on_compile_event(event, secs)


def install() -> None:
    """Register the compile-event listener and arm the transfer guard
    (idempotent). Separate from :func:`tracker` so tests can drive a
    private tracker without touching process-global state."""
    global _installed
    import jax
    with _install_lock:
        if _installed:
            return
        jax.monitoring.register_event_duration_secs_listener(_on_event)
        # inert on the CPU host-platform backend (zero-copy transfers
        # never reach the guard — module docstring); effective wherever
        # a real accelerator makes D2H a real transfer
        jax.config.update("jax_transfer_guard_device_to_host", "disallow")
        _installed = True


def uninstall() -> None:
    """Teardown for tests/bench: drop the listener and restore the
    permissive guard."""
    global _installed
    import jax
    with _install_lock:
        if not _installed:
            return
        jax.monitoring.unregister_event_duration_listener(_on_event)
        jax.config.update("jax_transfer_guard_device_to_host", "allow")
        _installed = False


def attach() -> Optional[DispatchTracker]:
    """What a runtime binds at construction: the installed process-wide
    tracker when the watchdog is on, ``None`` (the zero-overhead
    sentinel every hook gates on) otherwise."""
    if not enabled():
        return None
    install()
    return _default_tracker


# ------------------------------------------------------------------ #
# hot-path helpers (None-gated, shared nullcontext when off)
# ------------------------------------------------------------------ #

_NULL_CTX = contextlib.nullcontext()


def step_scope(t: Optional[DispatchTracker], key: Hashable,
               sig_fn: Optional[Callable[[], Hashable]] = None,
               fresh: Optional[bool] = None):
    """``with dispatch_debug.step_scope(self._dd, (self._ddtok, "x"), ...)``
    around the jitted call. ``sig_fn`` is only evaluated when the
    watchdog is on (signature tuples cost allocations)."""
    if t is None:
        return _NULL_CTX
    return t.scope(key, sig=sig_fn() if sig_fn is not None else None,
                   fresh=fresh)


def expected_d2h(t: Optional[DispatchTracker]):
    """Mark a sanctioned materialization site (the off-lock
    ``np.asarray``/``float`` drain): nested allow inside the armed
    guard, shared no-op when the watchdog is off."""
    if t is None:
        return _NULL_CTX
    import jax
    return jax.transfer_guard_device_to_host("allow")
