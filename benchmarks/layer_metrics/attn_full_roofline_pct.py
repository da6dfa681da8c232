"""Roofline share of the flash kernels' calls on ``full_attention`` layers
(causal over grouped heads), forward and backward together (see
``_afmoe.py``).  Layer: kernels.  Moves mfu_pct."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _afmoe


def read(run: dict):
    return _afmoe.attention_share(run, "attn_full", windowed=False)
