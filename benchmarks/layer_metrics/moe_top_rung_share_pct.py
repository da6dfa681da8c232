"""Share of the window's (step, layer) samples whose routed layer ran a rung
above its ladder's lowest (``_routing.py``): how often the routing sent more
than twice the even share here and the layer paid the worst-case rows for it.
Layer: device programs.  Moves tokens_per_s."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _routing


def read(run: dict):
    found = _routing.samples(run)
    if found is None:
        return None
    return 100.0 * sum(rows > lowest for _, rows, lowest in found) / len(found)
