"""Roofline share of the flash kernels' calls on window layers of a
differential attention (window 512, both maps in one call), forward and
backward together (see ``_phi4flash.py``).  Layer: kernels.  Moves mfu_pct."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _phi4flash


def read(run: dict):
    return _phi4flash.attention_share(run, "diffattn_window", ("attn_window",),
                                      windowed=True)
