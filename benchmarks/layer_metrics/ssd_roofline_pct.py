"""Roofline share of the Mamba-2 recurrence's kernels (``ops/ssd.py``, the
chunked form; the program's device scope ``ssm_ssd``): the calls named
``ssd_fwd.<n>`` and ``ssd_bwd.<n>`` (the ``pallas_call``s carry their names
into the trace), one of each a Mamba-2 layer and step, together.  Costs from
``flops/<family>.py:ssd_fwd`` / ``ssd_bwd`` at ``ssd_shape``: the four
products a chunk (2.5 times that backward) and the operands and results in
their stored types, ``y`` and its cotangent in float32.  **Against
``peaks.json`` both calls are bound by bytes** (0.38 ms forward, 0.51
backward at the published sizes).  Where the trace has no such event (shapes
that do not fill the kernels' tiles run the plain form; a rehearsal has no
trace) or the family has no such layer there is nothing to read: ``None``.
Layer: kernels.  Moves mfu_pct."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _afmoe


def read(run: dict):
    trace, job, flops = run["trace"], run["job"], run["flops"]
    if trace is None or not hasattr(flops, "ssd_fwd"):
        return None
    shape = flops.ssd_shape(run["config"], job["rows_per_client"],
                            job["tokens_per_row"])
    return _afmoe.share("ssd", run, (
        _afmoe.calls_in_hbm(trace, flops.ssd_fwd(**shape), "ssd_fwd")
        + _afmoe.calls_in_hbm(trace, flops.ssd_bwd(**shape), "ssd_bwd")))
