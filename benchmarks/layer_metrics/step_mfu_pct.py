"""The whole step's share of the chip's peak, over the time the device was
busy: model operations of the steps the traced window trained (its
``step_total`` spans x the rows and tokens a step holds x
``flops/<family>.py:train_flops_per_token``, as ``mfu_pct`` counts a token)
over the union of the device's operations in the window (``busy_s``, the
mean a chip) times the chips' peak.  It stands beside the kernels' roofline
shares: a change that takes a kernel off the path leaves that kernel's share
silent, and this one still bounds what the step gained on the device.  Host
time is no part of it (what the host costs is ``device_idle_pct`` and
``mfu_pct``), so a profiler that slows the host does not move it
(``vitl16-fused-224``: PERF.md section 5), and it reads ``mfu_pct`` over the
busy share of the window.  ``None`` without a trace or the program's spans.
Layer: device programs.  Moves mfu_pct."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _spans


def read(run: dict):
    trace, job = run["trace"], run["job"]
    recs = _spans.records(run)
    if trace is None or recs is None or not trace["busy_s"]:
        return None
    steps = len(_spans.steps(recs))
    if not steps:
        return None
    tokens = steps * job["rows_per_client"] * job["tokens_per_row"]
    ops = tokens * run["flops"].train_flops_per_token(run["config"], job["tokens_per_row"])
    print(f"step_mfu: {steps} steps of {job['rows_per_client']} x {job['tokens_per_row']} "
          f"tokens, {trace['busy_s']:.6f} s busy on {trace['devices']} chips",
          file=sys.stderr)
    return 100.0 * ops / (trace["busy_s"] * trace["devices"] * run["peak"]["bf16_flops_per_s"])
