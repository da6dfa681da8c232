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

import bisect
import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
# an event of one of these opcodes lies over the operations of the body it
# ran (a routed layer's ``lax.switch`` is a ``conditional``), which have
# events of their own: it is busy time like any other, and no operation
CONTAINERS = ("conditional", "while", "call")


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


_ARRAY = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]\{([^}]*)\}(?: (%[\w.\-]+))?")
_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "s16": 2, "u16": 2,
             "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8}
# a layout's memory space: ``S(1)`` is the chip's fast memory, where XLA's
# memory-space assignment may keep (or prefetch) an array that fits; an
# array with no such mark lies in HBM
_FAST = re.compile(r"S\([1-9][0-9]*\)")


def call_bytes(text: str) -> tuple | None:
    """(bytes of a custom call's results and operands, bytes of those that
    lie in the chip's fast memory), from the call's own HLO line (an event's
    whole name): every array at its type's size, an operand the call is
    handed twice counted once.  The first is what a count of the call's
    operands in their stored types has to come to; the second is the part
    of it that the call does not move over HBM.  None where the line is no
    custom call."""
    head, sep, rest = text.partition(" custom-call(")
    if not sep:
        return None
    operands = rest.split("), custom_call_target")[0]
    total, fast, seen = 0, 0, set()
    for dtype, dims, layout, name in _ARRAY.findall(head.partition(" = ")[2] + " " + operands):
        if dtype not in _ITEMSIZE or name in seen:
            continue
        if name:
            seen.add(name)
        size = _ITEMSIZE[dtype]
        for d in dims.split(","):
            size *= int(d) if d else 1
        total += size
        if _FAST.search(layout):
            fast += size
    return total, fast


def load(path: str, host_names: tuple) -> dict:
    """Flatten: ``{"devices": {plane: [[name, start, dur], ...]},
    "host": [[name, start, dur], ...], "calls": {name: HLO line}}`` with only
    the host events whose name is in ``host_names`` kept, and the whole line
    of each custom call (a kernel) beside its short name."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    keep = set(host_names) | {WINDOW_SPAN}
    devices, host, calls = {}, [], {}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    events = devices[plane.name] = []
                    for e in line.events:
                        name = short_name(e.name)
                        events.append([name, int(e.start_ns), int(e.duration_ns)])
                        if " custom-call " in name:
                            calls.setdefault(name, e.name)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, int(e.start_ns), int(e.duration_ns)]
                            for e in line.events if e.name in keep)
    return {"devices": devices, "host": host, "calls": calls}


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


def is_container(name: str) -> bool:
    """Whether a ``short_name`` is that of a container (its second word is
    the opcode)."""
    words = name.split(" ", 2)
    return len(words) > 1 and words[1] in CONTAINERS


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


def _coverer(host: list):
    """``at -> name`` of the host span that covers ``at``; the latest-started
    one if several (of spans that start together, the first in ``host``).
    The spans are sorted once and looked up by bisection: a party cell's
    window has thousands of the program's spans and as many gaps."""
    spans = sorted((h for h in host if h[0] != WINDOW_SPAN), key=lambda h: h[1])
    starts = [h[1] for h in spans]

    def cover(at: int) -> str:
        found = None
        for i in range(bisect.bisect_right(starts, at) - 1, -1, -1):
            name, start, dur = spans[i]
            if found is not None and start < found[1]:
                break
            if at < start + dur:
                found = (name, start)
        return found[0] if found else "uncovered"

    return cover


def reduce(trace: dict, devices: int | None = None, top: int = 10) -> dict:
    """Busy seconds (mean over the ``devices`` chips), the window, seconds per
    operation name (summed over planes), the kernels' HLO lines as ``load``
    kept them and idle seconds per covering host span (mean over planes).
    Every event counts as busy time; a container (``CONTAINERS``) is left out
    of the operations' seconds, counts and the ``device_ops`` ranking, where
    it would count its body twice and hide the body's operations behind its
    own name.  The window is the ``bench.window`` host span if the trace has
    one, else first start to last end of the device operations.  ``devices``
    is how many chips the cell used: a chip that ran nothing has no plane and
    counts as idle."""
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
    cover = _coverer(trace["host"])
    for events in planes.values():
        clipped = _clip(events, lo, hi)
        for name, a, b in clipped:
            if not is_container(name):
                ops[name] = ops.get(name, 0) + (b - a)
                counts[name] = counts.get(name, 0) + 1
        merged = union([[a, b] for _, a, b in clipped])
        busy_ns += sum(b - a for a, b in merged)
        edges = [lo] + [t for pair in merged for t in pair] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                name = cover((a + b) // 2)
                gaps[name] = gaps.get(name, 0) + (b - a)
    if n > len(planes):
        gaps["uncovered"] = gaps.get("uncovered", 0) + (n - len(planes)) * (hi - lo)
    rank = lambda d, scale: sorted(
        ([k, v * scale] for k, v in d.items()), key=lambda kv: -kv[1])[:top]
    return {"busy_s": busy_ns / n * 1e-9, "window_s": (hi - lo) * 1e-9, "devices": n,
            "op_seconds": {k: v * 1e-9 for k, v in ops.items()},
            "op_counts": counts, "calls": trace.get("calls", {}),
            "device_ops": rank(ops, 1e-9), "idle_gaps": rank(gaps, 1e-9 / n)}
