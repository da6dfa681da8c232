"""Useful rows over rows run in the routed layers: over the window's (step,
layer) samples, the pairs held here summed over the rows of the rung each
layer ran (``_routing.py``).  A layer in its lowest rung fills it by its pairs
over twice the even share, one in the middle rung by its pairs over four even
shares; one that passed both runs the worst case and fills a quarter or less.  Layer: device programs.  Moves tokens_per_s."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _routing


def read(run: dict):
    found = _routing.samples(run)
    if found is None:
        return None
    return 100.0 * sum(p for p, _, _ in found) / sum(r for _, r, _ in found)
