"""Flash forward calls a step in a looped model: the ``attn_full`` forward
events (``tpu_custom_call/3``, see ``_afmoe.py``) of the traced window over
the window's steps (the program's ``step_total`` spans, ``_spans.py``).  It
reads what the loop multiplies: ``flops/ouro.py:layer_applications`` (the
layers kept times the passes) where no pass's attention is made again in
the backward pass, twice that under a whole-layer ``remat`` of every pass.
Lower is better: a call above that count is recomputed work, which
``mfu_pct`` does not count.  Where there is no trace, no such event (a
program without the scope), no step span or a family that is no loop, there
is nothing to read: ``None``.  Layer: device programs.  Moves tokens_per_s."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _afmoe
import _spans


def read(run: dict):
    trace = run["trace"]
    if trace is None or not hasattr(run["flops"], "layer_applications"):
        return None
    recs = _spans.records(run)
    steps = len(_spans.steps(recs)) if recs else 0
    calls, _ = _afmoe.events(trace, "attn_full", "tpu_custom_call/3")
    if not calls or not steps:
        return None
    print(f"attn_full forward: {calls} calls in {steps} steps, "
          f"{run['flops'].layer_applications(run['config'])} layer "
          "applications a step", file=sys.stderr)
    return calls / steps
