"""Share of the clients' steps spent outside the transport call: the sum of
(``step_total`` - the ``transport`` spans beneath it) over the sum of
``step_total``, over the window's client steps
(``runtime/client.py:SplitClientTrainer.train_step``).  What is left of a step
once the reply is taken out: forward, copies, backward, optimizer.  Nothing to
read on the fused path, which has no transport.  Layer: runtime.  Moves
tokens_per_s."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _spans


def read(run: dict):
    recs = _spans.records(run)
    if recs is None:
        return None
    roots = _spans.steps(recs)
    if len(roots) < _spans.MIN_SPANS or len(_spans.named(recs, "transport")) < _spans.MIN_SPANS:
        return None
    replied = _spans.children(recs, roots, "transport")
    whole = sum(r["duration"] for r in roots)
    return 100.0 * (whole - sum(replied.values())) / whole
