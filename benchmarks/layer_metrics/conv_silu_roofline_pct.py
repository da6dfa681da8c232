"""Roofline share of the Mamba layers' convolution kernels
(``ops/causal_conv.py``: ``silu(bias + causal depthwise convolution)``; the
program's device scope ``ssm_conv``): the calls named ``conv_silu_fwd.<n>``
and ``conv_silu_bwd.<n>``, one of each a Mamba layer and step, together.
**Costed by bytes** (``flops/common.py:conv_silu_fwd`` / ``conv_silu_bwd``
at the family's ``conv_silu_shape``): two and three passes over ``[tokens,
channels]`` in the stored types.  Its nearer limit is the vector unit, for
which ``peaks.json`` has no peak, so the share reads low by that (PERF.md
section 3).  Where the trace has no such event (shapes that do not fill the
kernels' tiles run the shifted sum; a rehearsal has no trace) or the family
has no such layer there is nothing to read: ``None``.  Layer: kernels.
Moves mfu_pct."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _afmoe


def read(run: dict):
    trace, job, flops = run["trace"], run["job"], run["flops"]
    if trace is None or not hasattr(flops, "conv_silu_shape"):
        return None
    shape = flops.conv_silu_shape(run["config"], job["rows_per_client"],
                                  job["tokens_per_row"])
    return _afmoe.share("conv_silu", run, (
        _afmoe.calls_in_hbm(trace, flops.conv_silu_fwd(**shape), "conv_silu_fwd")
        + _afmoe.calls_in_hbm(trace, flops.conv_silu_bwd(**shape), "conv_silu_bwd")))
