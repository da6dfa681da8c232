"""Median over the window's (step, layer) samples of the pairs held here over
the pairs even routing would send (``_routing.py``): the factor by which
``moe_expert_mm_roofline_pct``, which costs its calls at the even count, reads
low.  Layer: device programs.  Moves tokens_per_s."""

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
