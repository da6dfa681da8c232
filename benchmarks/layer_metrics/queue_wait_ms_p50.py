"""Median wait of a request in front of the server: for the lock on the
serialized path, from enqueue to the flusher's pickup under coalescing (the
window included).  The ``queue_wait`` span, one a request
(``runtime/server.py:ServerRuntime.split_step`` and ``_dispatch_group``).
Layer: runtime.  Moves reply_ms_p50."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _spans


def read(run: dict):
    return _spans.median_ms(run, "queue_wait", "server")
