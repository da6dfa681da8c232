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

import trace_reduce
from flops import common


def _calls(trace: dict, needles: tuple) -> list:
    """Names of the custom calls whose name holds every needle."""
    return [n for n in trace["op_seconds"]
            if "custom-call" in n and all(x in n for x in needles)]


def events(trace: dict, *needles: str) -> tuple:
    """(calls, seconds) of the custom calls whose name holds every needle."""
    names = _calls(trace, needles)
    return (sum(trace["op_counts"][n] for n in names),
            sum(trace["op_seconds"][n] for n in names))


def calls_in_hbm(trace: dict, cost: tuple, *needles: str) -> list:
    """``share``'s parts for the custom calls whose name holds every needle,
    one a name, for a kernel whose ``cost`` counts its bytes as the call's
    operands and results in their stored types (``flops/``'s ``ssd_*`` and
    ``conv_silu_*``: the count has to come to the arrays of the call's own
    HLO line, which ``tests/test_kernel_readers.py`` holds it to within 1 %
    on recorded lines).  The count is the one source of the bytes; the line
    says where they lie: what it marks as kept in the chip's fast memory
    (``trace_reduce.call_bytes``) the call does not move over HBM, and it
    comes off the count (``phi4flash-fused-t8192``'s convolution is handed
    ``x`` there and would read 118 % of a roofline that took it for HBM
    traffic: PERF.md section 3).  Both are on stderr for every name."""
    parts = []
    ops, counted = cost
    for name in _calls(trace, needles):
        listed, fast = trace_reduce.call_bytes(trace.get("calls", {}).get(name, "")) or (None, 0)
        print(f"{name}: {counted} bytes counted, {listed} in its line, {fast} of "
              f"them in fast memory", file=sys.stderr)
        parts.append(((trace["op_counts"][name], trace["op_seconds"][name]),
                      (ops, counted - fast)))
    return parts


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
