"""One whole benchmark window of a party cell with the recorder on, and when
each client's request reached the server's coalescer in every round.

    python scripts/party_arrivals.py [--workload vitl16-party-224] --seed N
        [--seconds 20] [--window-ms W] [--table chiprun_out/<name>.json]

Runs ``benchmarks/run.py``'s own ``main`` (the cell's weights, batches, check
steps, warm-up and untraced window) under ``obs.enable()``: no profiler
session, the recorder alone. The benchmark's result line comes first (its
``tokens_per_s`` is the rate with recording on); then one JSON line from the
window's records. A ``queue_wait`` span runs from a request's enqueue (stamped
on the client's thread in ``ServerRuntime.split_step``) to its group's pickup
by the flusher, so its start is the arrival and its end names the group:

- ``arrival_offset_ms_by_rank``: how long after a round's first arrival the
  second, third, ... came (median, quartiles, max over the rounds): the
  number a hold's length is judged against;
- ``arrival_offset_ms_by_client`` and ``first_arrivals_by_client``: whether
  it is always the same client that is late;
- ``groups_a_round`` and ``group_sizes``: how the rounds were cut;
- ``queue_wait_ms_by_rank``, ``server_d2h_ms_by_bytes`` (one span a group,
  keyed by the bytes it brought back: its wait for the server step on the
  device), ``reply_ms_by_group`` (a client's ``transport`` span by its
  group's place in the round), and ``round_ms`` (the ``round`` spans).

``--window-ms`` builds the cell's server with another
``coalesce_window_ms`` than the constructor's default (which the benchmark
always runs): how the rounds are cut where the window catches more, or fewer,
of a round's requests. ``step_s_by_bucket`` is what the window coalescer had
measured when it closed (``runtime/coalesce.py``; empty before PR 46).

It needs nothing of the program that PR 24 did not bring, so the same file
runs in a ``git archive`` of an older commit. On the CPU
(``JAX_PLATFORMS=cpu``) it is the rehearsal's sizes.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))


def spread(values: list) -> dict:
    """Median, quartiles and extremes of ``values`` (milliseconds)."""
    if len(values) < 4:
        return {"n": len(values), "values": sorted(values)}
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return {"n": len(values), "min": min(values), "q1": q1, "median": q2,
            "q3": q3, "max": max(values)}


def reduce(records: list, first_round: int) -> tuple:
    """(summary, per-round rows) of the rounds from ``first_round`` on."""
    by = {}
    for r in records:
        by.setdefault((r["name"], r["party"]), []).append(r)
    waits = {}
    for r in by.get(("queue_wait", "server"), []):
        if r["step"] >= first_round:
            waits.setdefault(r["step"], []).append(r)
    replies = {(r["tid"], r["step"]): 1e3 * r["duration"]
               for r in by.get(("transport", "client"), [])}
    rows = []
    for step, rs in sorted(waits.items()):
        rs.sort(key=lambda r: r["start_ns"])
        t0 = rs[0]["start_ns"]
        pickups = sorted({r["end_ns"] for r in rs})
        rows.append({
            "round": step, "clients": [r["tid"] for r in rs],
            "offset_ms": [1e-6 * (r["start_ns"] - t0) for r in rs],
            "queue_wait_ms": [1e-6 * r["dur_ns"] for r in rs],
            "group": [pickups.index(r["end_ns"]) for r in rs],
            "reply_ms": [replies.get((r["tid"], step)) for r in rs]})
    if not rows:
        return {"rounds": 0}, rows
    ranks = range(max(len(r["clients"]) for r in rows))
    clients = sorted({c for r in rows for c in r["clients"]})

    def by_rank(what: str) -> list:
        return [spread([r[what][k] for r in rows if k < len(r[what])])
                for k in ranks]

    sizes = [r["group"].count(g) for r in rows for g in set(r["group"])]
    by_group = {}
    for r in rows:
        for g, ms in zip(r["group"], r["reply_ms"]):
            if ms is not None:
                by_group.setdefault(g, []).append(ms)
    d2h = {}
    for r in by.get(("d2h", "server"), []):
        if r["step"] >= first_round:
            d2h.setdefault(r["attrs"].get("bytes", 0), []).append(
                1e3 * r["duration"])
    return {
        "rounds": len(rows),
        "arrival_offset_ms_by_rank": by_rank("offset_ms"),
        "arrival_offset_ms_by_client": {
            c: spread([o for r in rows for cid, o in
                       zip(r["clients"], r["offset_ms"]) if cid == c])
            for c in clients},
        "first_arrivals_by_client": {
            c: sum(1 for r in rows if r["clients"][0] == c) for c in clients},
        "groups_a_round": {n: [len(set(r["group"])) for r in rows].count(n)
                           for n in sorted({len(set(r["group"])) for r in rows})},
        "group_sizes": {n: sizes.count(n) for n in sorted(set(sizes))},
        "queue_wait_ms_by_rank": by_rank("queue_wait_ms"),
        "reply_ms_by_group": {g: spread(ms) for g, ms in sorted(by_group.items())},
        "server_d2h_ms_by_bytes": {b: spread(ms) for b, ms in sorted(d2h.items())},
        "round_ms": spread([1e3 * r["duration"] for r in by.get(("round", None), [])
                            if r["step"] is not None and r["step"] >= first_round]),
    }, rows


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", default="vitl16-party-224")
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--seconds", type=float, default=20.0)
    parser.add_argument("--window-ms", type=float, default=None)
    parser.add_argument("--table", default=None)
    args = parser.parse_args()

    import run
    import traffic
    from split_learning_tpu import obs
    _, cell, _ = run.load_cell(args.workload)
    job = traffic.load(cell["traffic"])
    if args.window_ms is not None:
        import functools
        from paths import party
        party.ServerRuntime = functools.partial(
            party.ServerRuntime, coalesce_window_ms=args.window_ms)
    from split_learning_tpu.runtime.coalesce import RequestCoalescer
    measured, close = {}, RequestCoalescer.close

    def closing(self, *a, **k):
        measured.update({bucket: list(v) for (_, bucket), v
                         in getattr(self, "_served", {}).items()})
        return close(self, *a, **k)

    RequestCoalescer.close = closing
    tracer = obs.enable()
    sys.argv = [sys.argv[0], "--workload", args.workload, "--seed", str(args.seed),
                "--seconds", str(args.seconds), "--trace", "0"]
    try:
        code = run.main()
    finally:
        obs.disable()
    if code:
        return code
    # the check steps, the warm-up's two gated rounds and its one free round
    summary, rows = reduce(tracer.spans(), job["check_steps"] + 3)
    summary["step_s_by_bucket"] = measured
    if args.table:
        os.makedirs(os.path.dirname(os.path.abspath(args.table)), exist_ok=True)
        with open(args.table, "w") as f:
            json.dump({"workload": args.workload, "seed": args.seed,
                       "summary": summary, "rows": rows}, f)
    print(json.dumps({"workload": args.workload, "seed": args.seed, **summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
