"""Cross-layer observability: per-step tracing, latency histograms,
Prometheus /metrics, Chrome-trace export.

Usage (in-process)::

    from split_learning_tpu import obs
    tracer = obs.enable()            # spans are recorded from here on
    ... run steps ...
    tracer.export_chrome("trace.json")   # Perfetto-loadable
    print(tracer.phase_summary())
    obs.disable()

Every span is made by ``obs.span(name, **attrs)`` (obs/trace.py): one
``jax.profiler.TraceAnnotation`` always, one record while recording.
Recording is on after ``obs.enable()`` and for as long as a
``jax.profiler`` session runs; ``obs.recorded()`` reads the explicit
tracer's records or the last session's. Off, a span is an annotation
and nothing else: no record, no lock, no payload key. Tracing adds no
synchronisation, on or off.

Over HTTP the server exposes ``GET /metrics`` (Prometheus text); in
process, ``ServerRuntime.metrics()`` returns the same snapshot as a
dict. See obs/trace.py for the span taxonomy.
"""

from split_learning_tpu.obs.metrics import (  # noqa: F401
    DEFAULT_BUCKETS, Histogram, Registry, render_prometheus)
from split_learning_tpu.obs.trace import (  # noqa: F401
    CLIENT_PHASES, CTX, Span, Tracer, disable, enable, enabled, get_tracer,
    maybe_enable_from_env, recorded, recorder, recording, self_times, span,
    span_at, stamp)
