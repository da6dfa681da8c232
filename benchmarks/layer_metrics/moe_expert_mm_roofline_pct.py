"""Roofline share of the grouped expert products: every ``gmm`` call (a
layer's gate, up and down, forward and the rows' gradient) and every
``tgmm`` call (the weights' gradient, written in float32), at the pairs
expected under even routing (``flops/afmoe.py``; see ``_afmoe.py``).  A run
that routes more pairs here than expected reads low, one that routes fewer
reads high: ``scripts/afmoe_routing.py`` prints what a seed routes.
Layer: kernels.  Moves mfu_pct."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _afmoe


def read(run: dict):
    trace, job, flops = run["trace"], run["job"], run["flops"]
    if trace is None or not hasattr(flops, "expert_mm"):
        return None
    shape = flops.expert_mm_shape(run["config"], job["rows_per_client"],
                                  job["tokens_per_row"])
    every, seconds = _afmoe.events(trace, "gmm")
    weights = _afmoe.events(trace, "tgmm")
    rows = (every - weights[0], seconds - weights[1])
    return _afmoe.share("gmm", run, [
        (rows, flops.expert_mm(**shape)),
        (weights, flops.expert_mm(**shape, weight_itemsize=4))])
