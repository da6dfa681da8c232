"""From a profiler trace (``.xplane.pb``) to busy and idle time, time per
device operation, and idle gaps named by what the host was doing.

The trace is first flattened to plain data (``load``), so that the
arithmetic (``reduce``) can be checked on a small recorded trace kept
beside the tests.  Times are nanoseconds on the profiler's one clock.

Device planes are named ``/device:TPU:<n>``; a plane's ``XLA Ops`` line has
one event per operation that ran on that chip's core.  Host spans written
with ``jax.profiler.TraceAnnotation`` are events of the host planes' lines,
found by name.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"


def newest_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


_OPCODE = re.compile(r" ([a-z][a-z0-9_\-]*)\(")
_SHAPE = re.compile(r"= \(?([a-z0-9]+\[[0-9,]*\])")


def short_name(text: str) -> str:
    """A device event is named by its whole HLO line; keep the result's
    name, the opcode and the first result shape.  A custom call also keeps
    its target and how many operands it has, which is what tells the Pallas
    kernels apart (the program gives them no names): the flash forward is a
    ``tpu_custom_call`` with 3 operands (q, k, v), each backward kernel one
    with 6 (q, k, v, do, lse, delta)."""
    lhs, sep, rest = text.partition(" = ")
    if not sep:
        return text[:96]
    opcode = _OPCODE.search(" " + rest)
    shape = _SHAPE.search("= " + rest)
    parts = [lhs, opcode.group(1) if opcode else "?", shape.group(1) if shape else ""]
    if parts[1] == "custom-call":
        target = re.search(r'custom_call_target="([^"]+)"', rest)
        operands = rest.split("custom-call(", 1)[1].split("), custom_call_target")[0]
        parts.append(f"{target.group(1) if target else '?'}/{operands.count(' %') + operands.startswith('%')}")
    return " ".join(p for p in parts if p)


def load(path: str, host_names: tuple) -> dict:
    """Flatten: ``{"devices": {plane: [[name, start, dur], ...]},
    "host": [[name, start, dur], ...]}`` with only the host events whose
    name is in ``host_names`` kept."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    keep = set(host_names) | {WINDOW_SPAN}
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        [short_name(e.name), int(e.start_ns), int(e.duration_ns)]
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, int(e.start_ns), int(e.duration_ns)]
                            for e in line.events if e.name in keep)
    return {"devices": devices, "host": host}


def slice_of(trace: dict, events: int) -> dict:
    """A small piece of a trace to keep: per device plane the ``events``
    operations from the middle of the trace, and the host spans that touch
    that stretch."""
    devices = {}
    for plane, ev in trace["devices"].items():
        ev = sorted(ev, key=lambda e: e[1])
        mid = max(0, len(ev) // 2 - events // 2)
        devices[plane] = ev[mid:mid + events]
    lo = min(e[1] for ev in devices.values() for e in ev)
    hi = max(e[1] + e[2] for ev in devices.values() for e in ev)
    host = [h for h in trace["host"] if h[1] < hi and h[1] + h[2] > lo]
    return {"devices": devices, "host": host}


def union(intervals: list) -> list:
    """Merge ``[start, end)`` pairs that touch or overlap."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _clip(events: list, lo: int, hi: int) -> list:
    out = []
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((name, a, b))
    return out


def _cover(host: list, at: int) -> str:
    """The host span that covers ``at``; the latest-started one if several."""
    best = None
    for name, start, dur in host:
        if name != WINDOW_SPAN and start <= at < start + dur:
            if best is None or start > best[1]:
                best = (name, start)
    return best[0] if best else "uncovered"


def reduce(trace: dict, devices: int | None = None, top: int = 10) -> dict:
    """Busy seconds (mean over the device planes), the window, seconds per
    operation name (summed over planes) and idle seconds per covering host
    span (mean over planes).  The window is the ``bench.window`` host span
    if the trace has one, else first start to last end of the device
    operations.  ``devices`` is how many chips the cell used: a chip that
    ran nothing has no plane and counts as idle."""
    planes = trace["devices"]
    if not planes or not any(planes.values()):
        raise ValueError("no operation ran on a device in the traced window")
    spans = [h for h in trace["host"] if h[0] == WINDOW_SPAN]
    if spans:
        lo = min(s[1] for s in spans)
        hi = max(s[1] + s[2] for s in spans)
    else:
        lo = min(e[1] for ev in planes.values() for e in ev)
        hi = max(e[1] + e[2] for ev in planes.values() for e in ev)
    n = max(devices or 0, len(planes))
    busy_ns, ops, counts, gaps = 0, {}, {}, {}
    for events in planes.values():
        clipped = _clip(events, lo, hi)
        for name, a, b in clipped:
            ops[name] = ops.get(name, 0) + (b - a)
            counts[name] = counts.get(name, 0) + 1
        merged = union([[a, b] for _, a, b in clipped])
        busy_ns += sum(b - a for a, b in merged)
        edges = [lo] + [t for pair in merged for t in pair] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                name = _cover(trace["host"], (a + b) // 2)
                gaps[name] = gaps.get(name, 0) + (b - a)
    if n > len(planes):
        gaps["uncovered"] = gaps.get("uncovered", 0) + (n - len(planes)) * (hi - lo)
    rank = lambda d, scale: sorted(
        ([k, v * scale] for k, v in d.items()), key=lambda kv: -kv[1])[:top]
    return {"busy_s": busy_ns / n * 1e-9, "window_s": (hi - lo) * 1e-9,
            "op_seconds": {k: v * 1e-9 for k, v in ops.items()},
            "op_counts": counts,
            "device_ops": rank(ops, 1e-9), "idle_gaps": rank(gaps, 1e-9 / n)}
