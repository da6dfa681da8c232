"""Shared by the afmoe readers: which device events belong to which part of
the layer, and the roofline share of a set of kernel calls.

The program wraps its parts in ``jax.named_scope`` (``obs/spans.py``
``DEVICE_SCOPES``), and a Pallas call inside a scope is named after it in
the trace: ``attn_window.<n>`` and ``attn_full.<n>`` for the flash kernels
(``trace_reduce.short_name`` adds ``tpu_custom_call/3`` for a forward call,
``/6`` for the one-pass backward), ``gmm.<n>`` and ``tgmm.<n>`` for the
grouped products (their own ``jit`` is the innermost name).  Least time is
per call, from ``flops/afmoe.py`` over the call's shapes, times the calls
counted in the trace; the share is that over the summed device time of those
events.  Where the trace has no such event (a program without the scopes, a
rehearsal without a trace) there is nothing to read and the reader says so
with ``None``."""

import sys

from flops import common


def events(trace: dict, *needles: str) -> tuple:
    """(calls, seconds) of the custom calls whose name holds every needle."""
    names = [n for n in trace["op_seconds"]
             if "custom-call" in n and all(x in n for x in needles)]
    return (sum(trace["op_counts"][n] for n in names),
            sum(trace["op_seconds"][n] for n in names))


def share(label: str, run: dict, parts: list) -> float | None:
    """``parts``: ((calls, seconds), (operations, bytes)) per kind of call."""
    calls = sum(c for (c, _), _ in parts)
    seconds = sum(s for (_, s), _ in parts)
    if not calls or not seconds:
        return None
    least, bounds = 0.0, []
    for (c, _), cost in parts:
        one, bound = common.least_seconds(*cost, run["peak"])
        least += c * one
        bounds.append(bound)
    print(f"{label}: {calls} calls, {seconds:.6f} s on the device, least "
          f"{least:.6f} s, bound by {'/'.join(bounds)}", file=sys.stderr)
    return 100.0 * least / seconds


def attention_share(run: dict, scope: str, windowed: bool) -> float | None:
    trace, job, flops = run["trace"], run["job"], run["flops"]
    if trace is None or not hasattr(flops, "attn_fwd"):
        return None
    shape = flops.attention_shape(run["config"], job["rows_per_client"],
                                  job["tokens_per_row"])
    window = run["config"]["plan"]["kwargs"]["window"] if windowed else None
    return share(scope, run, [
        (events(trace, scope, "tpu_custom_call/3"), flops.attn_fwd(**shape, window=window)),
        (events(trace, scope, "tpu_custom_call/6"), flops.attn_bwd(**shape, window=window))])
