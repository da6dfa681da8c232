"""Shared by the readers of the routed layer's step counters.

Since PR 35 the fused step hands out, for every routed layer
(``models/afmoe.py:RoutedExperts``, which JoyAI's layers and its prediction
module's block share), what only the device knew: the pairs each held expert
got and the row count of the rung the layer ran.  While the window's profiler
session runs, ``FusedSplitTrainer.train_step`` reads them once a step inside a
``counters_read`` span, whose attributes are the record: ``layers`` (module
paths), ``pairs`` (a list of per-expert counts a layer), ``rows`` (the rung's
row count a layer) and ``ladder`` (the static rungs a layer).  A sample is one
layer at one step.  A program without the counters (a parent of PR 35) records
no such span: ``None``, and the line leaves the metric out, as it does under
``MIN_SPANS`` samples."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(HERE)]
import _spans
import traffic


def samples(run: dict):
    """[(pairs held here, rows of the rung run, rows of the ladder's last
    rung)], one a (step, layer) of the window, or None."""
    recs = _spans.records(run)
    if recs is None:
        return None
    found = []
    for r in _spans.named(recs, "counters_read"):
        a = r["attrs"]
        found.extend((sum(pairs), rows, max(ladder)) for pairs, rows, ladder
                     in zip(a["pairs"], a["rows"], a["ladder"]))
    return found if len(found) >= _spans.MIN_SPANS else None


def even_pairs(run: dict) -> float:
    """Pairs a step sends to the experts held here under even routing: the
    count ``flops/<family>.py`` costs the grouped products at."""
    return traffic.tokens_per_step(run["job"]) * run["flops"].expected_pairs_per_token(
        run["config"]["plan"]["kwargs"])
