"""Roofline share of the Pallas flash-attention backward kernels, one-pass
or split (see ``_flash.py``).  Layer: kernels.  Moves mfu_pct."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _flash
from flops import common

MARKERS = ("tpu_custom_call/6",)


def read(run: dict):
    return _flash.share(run, MARKERS, common.flash_bwd)
