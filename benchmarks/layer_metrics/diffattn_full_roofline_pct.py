"""Roofline share of the flash kernels' calls on the full and the cross
layers of a differential attention (causal over all keys; a cross layer
reads another layer's keys and values), forward and backward together (see
``_phi4flash.py``).  Layer: kernels.  Moves mfu_pct."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _phi4flash


def read(run: dict):
    return _phi4flash.attention_share(run, "diffattn_full",
                                      ("attn_full", "attn_cross"), windowed=False)
