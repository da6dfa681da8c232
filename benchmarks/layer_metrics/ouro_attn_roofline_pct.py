"""Roofline share of the flash kernels' calls of the ``ouro`` family's layers
(the program's device scope ``attn_full``; a Pallas call inside a scope is
named after it in the trace, see ``_afmoe.py``): causal over 16 query heads
on 16 key/value heads of 128 (group 1), no window, forward
(``tpu_custom_call/3``) and one-pass backward (``/6``) together, every call
of the loop's ``flops/ouro.py:layer_applications`` a step.  Costed at the
keys a query sees (``flops/ouro.py``, which takes ``flops/afmoe.py``'s costs
at this configuration's heads): trinity-mini's full layer's kernels at
another grouping.  Where the trace has no such event, or the configuration's
family has no such cost, there is nothing to read: ``None``.  Layer: kernels.
Moves mfu_pct."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _afmoe


def read(run: dict):
    return _afmoe.attention_share(run, "attn_full", windowed=False)
