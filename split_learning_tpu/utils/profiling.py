"""Tracing / profiling — the subsystem the reference lacks entirely
(SURVEY.md §5: the only timing signal is a per-10-step print,
``src/client_part.py:135-136``).

:func:`device_trace`: a context manager around ``jax.profiler`` emitting
an XLA trace viewable in TensorBoard/Perfetto, for on-chip analysis.
The program's own spans (obs/trace.py) are host events in that trace and
are recorded while the session runs: the phase accounting (compute vs
transport, the split that decides the north-star metric) is
``obs.recorder().fraction("transport")``.
"""

from __future__ import annotations

import contextlib
from typing import Iterator, Optional


@contextlib.contextmanager
def device_trace(log_dir: Optional[str]) -> Iterator[None]:
    """jax.profiler trace (no-op when log_dir is None)."""
    if not log_dir:
        yield
        return
    import jax
    with jax.profiler.trace(log_dir):
        yield
