"""Roofline share of the flash kernels' calls of the ``lfm2_moe`` family's
attention layers (the program's device scope ``attn_full``; a Pallas call
inside a scope is named after it in the trace, see ``_afmoe.py``): causal
over 32 query heads on 8 key/value heads, forward (``tpu_custom_call/3``) and
one-pass backward (``/6``) together.  Costed **at the true head width**
(``flops/lfm2_moe.py``: 64, at the keys a query sees), so the half of every
product that the kernel's 128 lanes spend on padding reads as time lost.
Where the trace has no such event, or the configuration's family has no
such cost, there is nothing to read: ``None``.  Layer: kernels.  Moves
mfu_pct."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _afmoe


def read(run: dict):
    return _afmoe.attention_share(run, "attn_full", windowed=False)
