"""Roofline share of the selective-scan kernel (``ops/selective_scan.py``):
the calls named ``ssm_scan.<n>``, forward (6 operands) and backward (8)
together, costs from ``flops/phi4flash.py:scan_fwd`` / ``scan_bwd``.  Against
``peaks.json`` the kernel is bound by bytes; its nearer limit is the vector
unit, for which no peak is published, so the share reads low (PERF.md section
3).  Layer: kernels.  Moves mfu_pct."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _afmoe


def read(run: dict):
    trace, job, flops = run["trace"], run["job"], run["flops"]
    if trace is None or not hasattr(flops, "scan_fwd"):
        return None
    shape = flops.scan_shape(run["config"], job["rows_per_client"],
                             job["tokens_per_row"])
    return _afmoe.share("ssm_scan", run, [
        (_afmoe.events(trace, "ssm_scan", "tpu_custom_call/6"), flops.scan_fwd(**shape)),
        (_afmoe.events(trace, "ssm_scan", "tpu_custom_call/8"), flops.scan_bwd(**shape))])
