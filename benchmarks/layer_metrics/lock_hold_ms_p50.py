"""Median time the server's lock is held for one dispatch: the ``dispatch``
span of party ``server``, one a request on the serialized path and one a group
under coalescing (``runtime/server.py:ServerRuntime.split_step`` and
``_dispatch_group``).  Layer: runtime.  Moves reply_ms_p50."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _spans


def read(run: dict):
    return _spans.median_ms(run, "dispatch", "server")
