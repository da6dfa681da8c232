"""Roofline share of the grouped expert products at the rows they really
held: every ``gmm`` call (a layer's gate, up and down, or an ungated
expert's up and down: forward, the recomputed forward and the rows'
gradient) and every ``tgmm`` call (the weights' gradient, written in
float32), each costed at ``2 x pairs x d_model x width``
(``flops/<family>.py:expert_mm`` at the cell's own ``expert_mm_shape``) with
**``pairs`` the mean that a routed layer held in a step of the window**,
read from the step's own ``counters_read`` records (``_routing.py``), not
the count even routing would send: a grouped product's work follows its
group sizes, whatever rung of rows the layer ran, so the share cannot pass
100 and does not read low by the routing's drift
(``moe_pairs_x_even_p50`` says how far the pairs stand from the even
count).  Where the trace or the records are missing (a rehearsal, a program
without the counters) there is nothing to read: ``None``.  Layer: kernels.
Moves mfu_pct."""

import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _afmoe
import _routing


def read(run: dict):
    trace, job, flops = run["trace"], run["job"], run["flops"]
    if trace is None or not hasattr(flops, "expert_mm"):
        return None
    found = _routing.samples(run)
    if found is None:
        return None
    shape = flops.expert_mm_shape(run["config"], job["rows_per_client"],
                                  job["tokens_per_row"])
    shape["pairs"] = statistics.fmean(pairs for pairs, _, _ in found)
    every, seconds = _afmoe.events(trace, "gmm")
    weights = _afmoe.events(trace, "tgmm")
    rows = (every - weights[0], seconds - weights[1])
    print(f"moe_expert_mm: {shape['pairs']:.1f} pairs a layer and step over "
          f"{len(found)} samples", file=sys.stderr)
    return _afmoe.share("moe_expert_mm", run, [
        (rows, flops.expert_mm(**shape)),
        (weights, flops.expert_mm(**shape, weight_itemsize=4))])
