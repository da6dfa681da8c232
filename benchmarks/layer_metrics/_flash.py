"""Shared by the two flash-kernel readers: the roofline share of the kernel
events whose name holds one of ``markers`` (``trace_reduce.short_name`` says how the kernels are told apart).

Least time is per call, from the benchmark's own functions over the call's
shapes (``flops/``), times the calls counted in the trace; the share is that
over the summed device time of those events.  The rows a call sees are the
client's rows a step, so the reader has nothing to read where calls differ
in size (a coalescing server pads its groups)."""

import sys

from flops import common


def share(run: dict, markers: tuple, cost) -> float | None:
    trace, job = run["trace"], run["job"]
    if trace is None or job.get("coalesce_max", 1) > 1:
        return None
    names = [n for n in trace["op_seconds"] if any(m in n for m in markers)]
    seconds = sum(trace["op_seconds"][n] for n in names)
    calls = sum(trace["op_counts"][n] for n in names)
    if not calls:
        return None
    shape = run["flops"].attention_shape(run["config"], job["rows_per_client"],
                                         job["tokens_per_row"])
    least, bound = common.least_seconds(*cost(**shape), run["peak"])
    print(f"{markers[0]}: {calls} calls, {seconds:.6f} s on the device, least "
          f"{least * calls:.6f} s, bound by {bound}", file=sys.stderr)
    return 100.0 * least * calls / seconds
