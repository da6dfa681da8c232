"""Roofline share of the flash kernels' calls of the ``nemotron_h`` family's
attention layers (the program's device scope ``attn_full``; a Pallas call
inside a scope is named after it in the trace, see ``_afmoe.py``): causal
over 32 query heads on 2 key/value heads of 128, no window, forward
(``tpu_custom_call/3``) and one-pass backward (``/6``) together.  Costed at
the keys a query sees (``flops/nemotron_h.py``, which takes
``flops/afmoe.py``'s costs at this configuration's heads): the same kernels
at the same width as trinity-mini's full layer, at twice the group.  Where
the trace has no such event, or the configuration's family has no such cost,
there is nothing to read: ``None``.  Layer: kernels.  Moves mfu_pct."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _afmoe


def read(run: dict):
    return _afmoe.attention_share(run, "attn_full", windowed=False)
