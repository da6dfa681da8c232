"""Median host time of the client's optimizer step: the ``opt_apply`` span
(``runtime/client.py:SplitClientTrainer._train_step``).  Host time of the
thread: the device work it queues shows up where the thread next waits.
Layer: runtime.  Moves tokens_per_s."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _spans


def read(run: dict):
    return _spans.median_ms(run, "opt_apply", "client")
