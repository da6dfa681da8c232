"""Bytes copied between host and device per step: the sum of ``bytes`` over
every ``h2d`` and ``d2h`` span of the window, both parties, over the window's
steps (client steps on the party path).  The copies are opened in
``runtime/client.py:SplitClientTrainer._train_step``,
``runtime/server.py:ServerRuntime.split_step`` / ``_dispatch_group`` /
``_GroupD2H._materialize`` and ``runtime/fused.py:FusedSplitTrainer._dispatch_step``.
Layer: transport.  Moves tokens_per_s."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _spans


def read(run: dict):
    recs = _spans.records(run)
    if recs is None:
        return None
    copies = _spans.named(recs, "h2d") + _spans.named(recs, "d2h")
    roots = _spans.steps(recs)
    if len(roots) < _spans.MIN_SPANS or not copies:
        return None
    return sum(r["attrs"].get("bytes", 0) for r in copies) / len(roots)
