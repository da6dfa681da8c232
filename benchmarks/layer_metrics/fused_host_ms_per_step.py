"""Median host time of a fused step before it waits for the loss:
``step_total`` - the ``loss_wait`` beneath it
(``runtime/fused.py:FusedSplitTrainer.train_step``): the inputs' copy and the
call of the jitted step.  Nothing to read on the party path.  Layer: runtime.
Moves tokens_per_s."""

import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _spans


def read(run: dict):
    recs = _spans.records(run)
    if recs is None:
        return None
    roots = _spans.steps(recs)
    if len(_spans.named(recs, "loss_wait")) < _spans.MIN_SPANS:
        return None
    waited = _spans.children(recs, roots, "loss_wait")
    return 1e3 * statistics.median(r["duration"] - waited[r["span_id"]] for r in roots)
