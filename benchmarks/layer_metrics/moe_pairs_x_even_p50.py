"""Median over the window's (step, layer) samples of the pairs held here over
the pairs even routing would send (``_routing.py``): how far the routing has
drifted from the count ``mfu_pct`` costs the grouped products at
(``moe_expert_mm_roofline_pct`` costs its calls at the pairs held).  Layer: device programs.  Moves tokens_per_s."""

import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _routing


def read(run: dict):
    found = _routing.samples(run)
    if found is None:
        return None
    even = _routing.even_pairs(run)
    return statistics.median(p / even for p, _, _ in found)
