"""Shared by the readers of the program's own spans (``obs/trace.py``).

The program records one span a phase while a profiler session runs, on the
profiler's clock; ``run.py`` opens its session around the window and nothing
else, so the last session's records are the window's.  The readers take them
from ``obs.recorded()`` and not from the xplane: a host event there has
the span's name and times (``trace_reduce`` names the idle gaps by them) and
none of its attributes or its parent, and ``run.py`` deletes the trace
before a reader runs.  A program without the recorder (a parent of
PR 24) has nothing to read: ``None``, and the line leaves the metric out.

A record is a dict with ``name``, ``party`` (``client`` or ``server``),
``span_id``, ``parent_id``, ``duration`` in seconds and ``attrs`` (``bytes``
on the ``h2d`` and ``d2h`` copies).  A reader has nothing to read under
``MIN_SPANS`` spans of its name."""

import statistics
import sys

MIN_SPANS = 5
_logged = False


def program_records():
    """What the program recorded in its last profiler session, or None."""
    try:
        from split_learning_tpu import obs
        return obs.recorded() or None
    except (ImportError, AttributeError):
        return None


def records(run: dict):
    """The window's span records: ``run["spans"]`` where a test hands them
    over, else the program's; None where there are none."""
    recs = run["spans"] if "spans" in run else program_records()
    if not recs:
        return None
    _log(recs)
    return recs


def named(recs: list, name: str, party=None) -> list:
    return [r for r in recs if r["name"] == name
            and (party is None or r["party"] == party)]


def median_ms(run: dict, name: str, party=None):
    """Median duration in ms of the window's spans of that name, or None."""
    recs = records(run)
    if recs is None:
        return None
    found = named(recs, name, party)
    if len(found) < MIN_SPANS:
        return None
    return 1e3 * statistics.median(r["duration"] for r in found)


def steps(recs: list) -> list:
    """The window's steps: a client's ``step_total`` on the party path, the
    trainer's on the fused one."""
    return named(recs, "step_total")


def children(recs: list, parents: list, name: str) -> dict:
    """Seconds of the ``name`` spans beneath each of ``parents``, by the
    parent's span id (0.0 where it has none)."""
    beneath = {p["span_id"]: 0.0 for p in parents}
    for r in named(recs, name):
        if r["parent_id"] in beneath:
            beneath[r["parent_id"]] += r["duration"]
    return beneath


def _log(recs: list) -> None:
    """Once a process, on stderr: the window by span, for whoever reads the
    run (count, median and sum by party and name; what ``step_total`` does
    not hand to a child)."""
    global _logged
    if _logged:
        return
    _logged = True
    groups = {}
    for r in recs:
        groups.setdefault((r["party"], r["name"]), []).append(r["duration"])
    for (party, name), xs in sorted(groups.items()):
        print(f"spans {party:6s} {name:12s} n {len(xs):4d} median "
              f"{1e3 * statistics.median(xs):9.3f} ms sum {sum(xs):8.4f} s",
              file=sys.stderr)
    roots = steps(recs)
    if roots:
        ids = {r["span_id"] for r in roots}
        inside = sum(r["duration"] for r in recs if r["parent_id"] in ids)
        total = sum(r["duration"] for r in roots)
        print(f"spans step_total self time {100 * (1 - inside / total):.2f} % "
              f"of {total:.4f} s in {len(roots)} steps", file=sys.stderr)
