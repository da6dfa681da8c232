"""Median time to bring the cut gradient to the host, off the lock: the
``d2h`` span of party ``server``, one a request on the serialized path and one
a group (on the waiter that redeems it) under coalescing
(``runtime/server.py:ServerRuntime.split_step`` and ``_GroupD2H._materialize``).
It waits for the device to finish the server step, so it holds the step's
device time.  Layer: transport.  Moves reply_ms_p50."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _spans


def read(run: dict):
    return _spans.median_ms(run, "d2h", "server")
