"""Shared by the phi4flash readers: the roofline share of the kernel calls
under one or more of the program's device scopes (``obs/spans.py``
``DEVICE_SCOPES``; a Pallas call inside a scope is named after it in the
trace, see ``_afmoe.py``).  A flash call under ``attn_window``, ``attn_full``
or ``attn_cross`` serves both softmax maps of a differential attention and
is costed at the true sizes (``flops/phi4flash.py``: QK^T at 64, PV at 128,
at the keys a query sees), so the zero-padded half of every QK^T the kernel
runs reads as time lost.  Where the trace has no such event (a program
without the scopes, a rehearsal without a trace) the reader returns
``None``."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _afmoe


def attention_share(run: dict, label: str, scopes: tuple, windowed: bool):
    trace, job, flops = run["trace"], run["job"], run["flops"]
    if trace is None or not hasattr(flops, "scan_fwd"):
        return None
    shape = flops.attention_shape(run["config"], job["rows_per_client"],
                                  job["tokens_per_row"])
    window = run["config"]["plan"]["kwargs"]["window"] if windowed else None
    parts = []
    for scope in scopes:
        # 3 operands: the forward; 6: the one-pass backward
        parts.append((_afmoe.events(trace, scope, "tpu_custom_call/3"),
                      flops.attn_fwd(**shape, window=window)))
        parts.append((_afmoe.events(trace, scope, "tpu_custom_call/6"),
                      flops.attn_bwd(**shape, window=window)))
    return _afmoe.share(label, run, parts)
