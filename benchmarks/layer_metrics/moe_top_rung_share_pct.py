"""Share of the window's (step, layer) samples whose routed layer ran its
ladder's last rung (``_routing.py``): how often the routing sent past what
every lower rung holds and the layer paid the worst-case rows for it.  A
sample on a middle rung (``models/afmoe.py:pair_rungs`` has one since PR 41)
is not counted: ``moe_rung_fill_pct`` reads what those cost.  Layer: device
programs.  Moves tokens_per_s."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _routing


def read(run: dict):
    found = _routing.samples(run)
    if found is None:
        return None
    return 100.0 * sum(rows == top for _, rows, top in found) / len(found)
