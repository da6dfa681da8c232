#!/usr/bin/env python
"""Summarize a Chrome-trace file (obs/trace.py export) into the
north-star compute-vs-wire fraction table.

Input: the trace written by ``slt train --trace PATH`` /
``slt serve --trace PATH`` / ``Tracer.export_chrome`` — a Chrome trace
event array, one event per line. Parsing is tolerant: a partially
written file (live run, crashed run) loads line-by-line, so the report
can run against a job that is still training.

Output: per-phase count/total/mean/p50/p90 table; the client-level
phase mix (client_fwd / transport / client_bwd / opt_apply — the same
denominator as ``Tracer.fraction``, so ``transport_fraction`` here
reproduces ``obs.recorder().fraction('transport')`` on the same run); the
transport decomposition (encode / wire / server queue_wait + dispatch);
and a per-step accounting check (client phases summed vs the measured
``step_total`` wall clock — the 10%-agreement acceptance gate of the
tracing PR).

Run: python scripts/trace_report.py artifacts/trace.json [--json]
Also: python scripts/trace_report.py --schedules slt-check-report.json
summarizes an slt-check explorer report (``--check --report PATH``):
per scenario, schedules explored vs pruned (sleep-set pruning ratio),
the max preemption depth reached, and any invariant violations with
their replayable schedule ids.

Stdlib-only (no jax, no numpy): usable on any box the trace file lands
on.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Dict, List

# the span taxonomy's single home is obs/spans.py (slt-lint SLT003);
# this script also runs standalone, without the package importable, so
# it falls back to a literal copy that tests/test_analysis.py pins
# byte-equal to the registry. "d2h" exists only on async-dispatch
# servers (PR 5) — totals.get(..., 0.0) below keeps traces from older
# runs parsing (and reporting 0) without it, the tolerant-parser
# contract.
try:
    from split_learning_tpu.obs.spans import (CLIENT_PHASES, COMPILE,
                                              DEFERRED_APPLY, MESH_META,
                                              REPLY_GRAD, STAGE_META,
                                              TRANSPORT_SUB)
except ImportError:
    CLIENT_PHASES = ("client_fwd", "transport", "client_bwd", "opt_apply")
    TRANSPORT_SUB = ("encode", "wire", "queue_wait", "dispatch", "d2h")
    COMPILE = "xla_compile"
    REPLY_GRAD = "reply_grad"
    DEFERRED_APPLY = "deferred_apply"
    MESH_META = "mesh_meta"
    STAGE_META = "stage_meta"


def load_events(path: str) -> List[Dict[str, Any]]:
    """Whole-file JSON array first; fall back to per-line parsing (a
    live/truncated export: strip array brackets and trailing commas,
    skip any line that does not parse)."""
    with open(path) as f:
        text = f.read()
    try:
        data = json.loads(text)
        if isinstance(data, dict):  # {"traceEvents": [...]} container
            data = data.get("traceEvents", [])
        return [e for e in data if isinstance(e, dict)]
    except json.JSONDecodeError:
        pass
    events = []
    for line in text.splitlines():
        line = line.strip().rstrip(",")
        if line in ("", "[", "]"):
            continue
        try:
            ev = json.loads(line)
        except json.JSONDecodeError:
            continue  # torn tail line of a live file
        if isinstance(ev, dict):
            events.append(ev)
    return events


def _percentile(sorted_xs: List[float], q: float) -> float:
    if not sorted_xs:
        return 0.0
    idx = (len(sorted_xs) - 1) * q / 100.0
    lo = int(idx)
    hi = min(lo + 1, len(sorted_xs) - 1)
    return sorted_xs[lo] + (sorted_xs[hi] - sorted_xs[lo]) * (idx - lo)


def tenant_queue_waits(events: List[Dict[str, Any]],
                       tenants: int) -> Dict[str, Any]:
    """Per-tenant ``queue_wait`` tail table. Server spans carry the
    client id as the Chrome ``tid`` field, and the admission layer's
    tenant mapping is ``client_id % tenants`` (runtime/admission.py
    default) — so a multi-tenant fleet trace splits into per-tenant
    queue-wait distributions with no extra instrumentation. Tolerant:
    spans with a missing/non-numeric tid land in tenant 0."""
    by_tenant: Dict[int, List[float]] = {t: [] for t in range(tenants)}
    for e in events:
        if e.get("ph") != "X" or e.get("name") != "queue_wait":
            continue
        try:
            tid = int(e.get("tid", 0))
        except (TypeError, ValueError):
            tid = 0
        by_tenant[tid % tenants].append(float(e.get("dur", 0.0)) / 1e6)
    table = {}
    for t, xs in sorted(by_tenant.items()):
        xs = sorted(xs)
        table[str(t)] = {
            "count": len(xs),
            "mean_ms": (sum(xs) / len(xs) * 1e3) if xs else 0.0,
            "p50_ms": _percentile(xs, 50) * 1e3,
            "p99_ms": _percentile(xs, 99) * 1e3,
        }
    return table


def summarize(events: List[Dict[str, Any]],
              tenants: int = 0) -> Dict[str, Any]:
    spans = [e for e in events if e.get("ph") == "X"]
    by_phase: Dict[str, List[float]] = {}
    for e in spans:
        by_phase.setdefault(e.get("name", "?"), []).append(
            float(e.get("dur", 0.0)) / 1e6)  # µs -> s

    table = {}
    for name, xs in sorted(by_phase.items()):
        xs = sorted(xs)
        table[name] = {
            "count": len(xs),
            "total_s": sum(xs),
            "mean_ms": sum(xs) / len(xs) * 1e3,
            "p50_ms": _percentile(xs, 50) * 1e3,
            "p90_ms": _percentile(xs, 90) * 1e3,
        }

    totals = {name: row["total_s"] for name, row in table.items()}
    denom = sum(totals.get(p, 0.0) for p in CLIENT_PHASES)
    client_mix = {p: (totals.get(p, 0.0) / denom if denom else 0.0)
                  for p in CLIENT_PHASES}
    tsub = {p: totals.get(p, 0.0) for p in TRANSPORT_SUB}

    # accounting check: per step (trace_id), client phases vs step_total
    per_step: Dict[str, Dict[str, float]] = {}
    for e in spans:
        tid = (e.get("args") or {}).get("trace_id")
        if tid is None:
            continue
        slot = per_step.setdefault(tid, {})
        name = e.get("name", "?")
        slot[name] = slot.get(name, 0.0) + float(e.get("dur", 0.0)) / 1e6
    ratios = []
    for slot in per_step.values():
        wall = slot.get("step_total", 0.0)
        if wall <= 0:
            continue
        ratios.append(sum(slot.get(p, 0.0) for p in CLIENT_PHASES) / wall)
    coverage = sum(ratios) / len(ratios) if ratios else None

    # compile events (obs/dispatch_debug.py under SLT_DISPATCH_DEBUG=1):
    # args.step carries the step scope's local ordinal, so "steady"
    # (ordinal >= 2) compiles are the recompile storm this table makes
    # visible. Tolerant: absent/non-numeric step fields count as
    # non-steady instead of raising.
    compile_durs: List[float] = []
    steady_compiles = 0
    for e in spans:
        if e.get("name") != COMPILE:
            continue
        compile_durs.append(float(e.get("dur", 0.0)) / 1e6)
        try:
            step = int((e.get("args") or {}).get("step", -1))
        except (TypeError, ValueError):
            step = -1
        if step >= 2:
            steady_compiles += 1
    compile_summary = {
        "count": len(compile_durs),
        "total_s": sum(compile_durs),
        "max_ms": max(compile_durs) * 1e3 if compile_durs else 0.0,
        "steady_state_count": steady_compiles,
    }

    # reply-latency vs step-latency breakdown (PR 10, --decouple-bwd):
    # on a decoupled server the client-visible reply window is the
    # reply_grad span; the deferred_apply spans are the weight updates
    # that left the critical path. The "coupled-equivalent" step cost is
    # reply p50 + the apply cost amortized per reply — what each reply
    # WOULD have carried had the update stayed fused. Only emitted when
    # reply_grad spans exist, so coupled traces render unchanged.
    decoupled = None
    reply_xs = sorted(by_phase.get(REPLY_GRAD, []))
    if reply_xs:
        apply_xs = sorted(by_phase.get(DEFERRED_APPLY, []))
        apply_total = sum(apply_xs)
        reply_p50 = _percentile(reply_xs, 50)
        amortized = apply_total / len(reply_xs)
        step_equiv = reply_p50 + amortized
        decoupled = {
            "replies": len(reply_xs),
            "applies": len(apply_xs),
            "reply_p50_ms": reply_p50 * 1e3,
            "reply_p90_ms": _percentile(reply_xs, 90) * 1e3,
            "apply_total_s": apply_total,
            "apply_amortized_ms": amortized * 1e3,
            "step_equivalent_p50_ms": step_equiv * 1e3,
            "reply_over_step": (reply_p50 / step_equiv
                                if step_equiv > 0 else 0.0),
        }

    # mesh/MFU sidecar (PR 11, sharded server): export_chrome(metadata=
    # ServerRuntime.trace_metadata()) rides as one ph:"M" event named
    # MESH_META. Absent on unsharded/old traces -> section not rendered
    # (the decoupled_bwd conditional-section contract). Tolerant: a
    # malformed args payload (not a dict) is treated as absent.
    mesh_meta = None
    for e in events:
        if e.get("ph") == "M" and e.get("name") == MESH_META:
            args_d = e.get("args")
            if isinstance(args_d, dict):
                mesh_meta = args_d
            break

    # pipeline sidecar (PR 14, K-stage MPMD chain): export_chrome(
    # stage_metadata=PipelineRunner.trace_metadata()) rides as one
    # ph:"M" event named STAGE_META. Absent on 1-cut/old traces -> the
    # section is not rendered, same contract as the mesh sidecar.
    # Tolerant: a malformed args payload (not a dict) is treated as
    # absent, and a "stages" entry that is not a list renders as empty.
    stage_meta = None
    for e in events:
        if e.get("ph") == "M" and e.get("name") == STAGE_META:
            args_d = e.get("args")
            if isinstance(args_d, dict):
                stage_meta = args_d
            break

    rep = {
        "events": len(events),
        "spans": len(spans),
        "steps_with_wall_clock": len(ratios),
        "phases": table,
        "client_phase_mix": client_mix,
        "transport_fraction": client_mix.get("transport", 0.0),
        "transport_decomposition_s": tsub,
        "compile": compile_summary,
        "decoupled_bwd": decoupled,
        "mesh": mesh_meta,
        "pipeline": stage_meta,
        "span_sum_over_wall_clock": coverage,
    }
    if tenants > 0:
        rep["tenant_queue_wait"] = tenant_queue_waits(events, tenants)
    return rep


def render(rep: Dict[str, Any]) -> str:
    lines = []
    lines.append(f"{'phase':<12} {'count':>6} {'total_s':>9} "
                 f"{'mean_ms':>9} {'p50_ms':>9} {'p90_ms':>9}")
    for name, row in rep["phases"].items():
        lines.append(
            f"{name:<12} {row['count']:>6d} {row['total_s']:>9.4f} "
            f"{row['mean_ms']:>9.3f} {row['p50_ms']:>9.3f} "
            f"{row['p90_ms']:>9.3f}")
    lines.append("")
    lines.append("client phase mix (compute vs wire, the north-star split):")
    for name, frac in rep["client_phase_mix"].items():
        lines.append(f"  {name:<12} {frac:>7.1%}")
    lines.append(f"  -> transport fraction: "
                 f"{rep['transport_fraction']:.3f} "
                 f"(== Tracer.fraction('transport'))")
    lines.append("")
    lines.append("transport decomposition (total seconds):")
    for name, s in rep["transport_decomposition_s"].items():
        lines.append(f"  {name:<12} {s:>9.4f}")
    comp = rep.get("compile") or {}
    if comp.get("count"):
        lines.append("")
        lines.append(
            f"xla compiles: {comp['count']} "
            f"({comp['total_s']:.4f}s total, max {comp['max_ms']:.3f}ms); "
            f"steady-state (step >= 2): {comp['steady_state_count']}"
            + ("  <-- recompile storm"
               if comp["steady_state_count"] else ""))
    dec = rep.get("decoupled_bwd")
    if dec:
        lines.append("")
        lines.append("decoupled backward (2BP) — reply vs step latency:")
        lines.append(
            f"  replies: {dec['replies']}  "
            f"deferred applies: {dec['applies']}")
        lines.append(
            f"  reply p50: {dec['reply_p50_ms']:.3f}ms  "
            f"p90: {dec['reply_p90_ms']:.3f}ms")
        lines.append(
            f"  apply amortized/reply: {dec['apply_amortized_ms']:.3f}ms "
            f"({dec['apply_total_s']:.4f}s total off the critical path)")
        lines.append(
            f"  coupled-equivalent step p50: "
            f"{dec['step_equivalent_p50_ms']:.3f}ms  "
            f"-> reply/step ratio: {dec['reply_over_step']:.2f}")
    mesh = rep.get("mesh")
    if mesh:
        lines.append("")
        info = mesh.get("mesh") or {}
        shape = ", ".join(f"{k}={v}" for k, v in info.items())
        lines.append(f"sharded server (pjit) — mesh: {shape or '?'}")
        gb = mesh.get("gather_bytes")
        if gb is not None:
            lines.append(f"  sharded-gather D2H bytes: {int(gb)}")
        peak = mesh.get("peak_flops_per_device")
        lines.append(
            "  peak flops/device: " +
            (f"{peak:.3e}" if peak
             else "unknown (CPU backend) — MFU not computable"))
        progs = mesh.get("programs") or {}
        if progs:
            lines.append(f"  {'program':<16} {'calls':>6} {'gflops':>9} "
                         f"{'disp_s':>8} {'gflop/s':>9} {'mfu':>7}")
            for name, row in sorted(progs.items()):
                rate = row.get("model_flops_per_sec")
                m = row.get("mfu")
                rate_col = f"{rate / 1e9:>9.3f}" if rate else f"{'-':>9}"
                mfu_col = f"{m:>7.1%}" if m is not None else f"{'-':>7}"
                lines.append(
                    f"  {name:<16} {int(row.get('calls', 0)):>6d} "
                    f"{float(row.get('model_flops', 0.0)) / 1e9:>9.3f} "
                    f"{float(row.get('dispatch_s', 0.0)):>8.4f} "
                    f"{rate_col} {mfu_col}")
    pipe = rep.get("pipeline")
    if pipe:
        lines.append("")
        sched = pipe.get("schedule")
        sched_note = f", schedule {sched}" if sched else ""
        lines.append(
            f"MPMD pipeline — {pipe.get('num_stages', '?')} stages, "
            f"M={pipe.get('microbatches', '?')} microbatches, "
            f"{pipe.get('ticks_per_step', '?')} ticks/step over "
            f"{pipe.get('steps', '?')} steps{sched_note}")
        stages = pipe.get("stages")
        if isinstance(stages, list) and stages:
            # gpipe/1f1b columns render the per-schedule ideal side by
            # side; sidecars predating PR 16 carry neither (nor a
            # schedule), so every new column falls back to '-'
            lines.append(f"  {'stage':>5} {'sched':>6} {'bubble':>8} "
                         f"{'gpipe':>8} {'1f1b':>8} "
                         f"{'reply_p50':>10} {'hops':>6} {'applyQ':>7} "
                         f"{'ratio':>7} {'density':>8} "
                         f"{'mesh':>9} {'mfu':>6}")
            for row in stages:
                if not isinstance(row, dict):
                    continue
                sched_col = f"{str(row.get('schedule') or '-'):>6}"
                bub = row.get("bubble_fraction")
                bub_col = f"{bub:>8.1%}" if bub is not None else f"{'-':>8}"

                def _theo(key, row=row):
                    # old sidecars: one 'bubble_theoretical' for both
                    t = row.get(key, row.get("bubble_theoretical"))
                    return f"{t:>8.1%}" if t is not None else f"{'-':>8}"

                gpipe_col = _theo("bubble_theoretical_gpipe")
                onefb_col = _theo("bubble_theoretical_1f1b")
                p50 = row.get("reply_p50_ms")
                p50_col = (f"{p50:>8.3f}ms" if p50 is not None
                           else f"{'-':>10}")
                depth = row.get("deferred_apply_depth")
                depth_col = (f"{int(depth):>7d}" if depth is not None
                             else f"{'-':>7}")
                # compressed hop wire columns (PR 18): cumulative
                # raw/wire ratio and the controller's current density —
                # dense or pre-PR-18 sidecars carry neither, '-'
                ratio = row.get("compression_ratio")
                ratio_col = (f"{ratio:>6.1f}x" if ratio is not None
                             else f"{'-':>7}")
                dens = row.get("density")
                dens_col = (f"{dens:>8.3f}" if dens is not None
                            else f"{'-':>8}")
                # per-stage mesh + MFU (ISSUE 20 composed topologies):
                # mesh renders as dataxmodel; pre-ISSUE-20 sidecars
                # carry neither and fall back to '-'
                mesh = row.get("mesh")
                if isinstance(mesh, dict):
                    mesh_col = (f"{int(mesh.get('data', 1))}x"
                                f"{int(mesh.get('model', 1))}").rjust(9)
                else:
                    mesh_col = f"{'-':>9}"
                smfu = row.get("mfu")
                smfu_col = (f"{smfu:>6.1%}" if smfu is not None
                            else f"{'-':>6}")
                lines.append(
                    f"  {int(row.get('stage', 0)):>5d} {sched_col} "
                    f"{bub_col} {gpipe_col} {onefb_col} {p50_col} "
                    f"{int(row.get('hop_calls', 0)):>6d} {depth_col} "
                    f"{ratio_col} {dens_col} {mesh_col} {smfu_col}")
        dc = pipe.get("density")
        if isinstance(dc, dict) and dc.get("windows_closed"):
            lines.append(
                f"  adaptive density: {dc.get('windows_closed')} windows "
                f"(budget {dc.get('budget_nats')} nats / "
                f"{dc.get('window')}-step window), "
                f"final {dc.get('densities')}")
    tqw = rep.get("tenant_queue_wait")
    if tqw:
        lines.append("")
        lines.append("per-tenant queue wait (client_id % tenants):")
        lines.append(f"  {'tenant':<8} {'count':>6} {'mean_ms':>9} "
                     f"{'p50_ms':>9} {'p99_ms':>9}")
        for t, row in tqw.items():
            lines.append(
                f"  {t:<8} {row['count']:>6d} {row['mean_ms']:>9.3f} "
                f"{row['p50_ms']:>9.3f} {row['p99_ms']:>9.3f}")
    cov = rep["span_sum_over_wall_clock"]
    if cov is not None:
        lines.append("")
        lines.append(
            f"accounting: client spans sum to {cov:.1%} of step_total "
            f"wall clock over {rep['steps_with_wall_clock']} steps "
            f"(acceptance gate: within 10%)")
    return "\n".join(lines)


def summarize_schedules(path: str) -> Dict[str, Any]:
    """Digest an slt-check explorer report (the ``--check --report``
    JSON) into the per-scenario exploration table. Tolerant of skipped
    scenarios (``{"skipped": ...}`` entries) and absent keys, so a
    report from an older/newer checker still renders."""
    with open(path) as f:
        rep = json.load(f)
    scenarios = rep.get("scenarios", {})
    table: Dict[str, Any] = {}
    totals = {"schedules": 0, "pruned": 0, "violations": 0, "skipped": 0}
    for name, e in sorted(scenarios.items()):
        if "skipped" in e:
            table[name] = {"skipped": e["skipped"]}
            totals["skipped"] += 1
            continue
        row = {
            "schedules": int(e.get("schedules", 0)),
            "pruned": int(e.get("pruned", 0)),
            "pruning_ratio": float(e.get("pruning_ratio", 0.0)),
            "max_preemptions": int(e.get("max_preemptions", 0)),
            "exhausted": bool(e.get("exhausted", False)),
            "violations": list(e.get("violations", ())),
        }
        if e.get("crash"):
            # slt-crash (PR 12) entries: interleavings x crash points
            row["crash"] = True
            row["bases"] = int(e.get("bases", 0))
            row["crash_schedules"] = int(e.get("crash_schedules", 0))
        table[name] = row
        totals["schedules"] += row["schedules"]
        totals["pruned"] += row["pruned"]
        totals["violations"] += len(row["violations"])
    return {"scenarios": table, "totals": totals}


def render_schedules(rep: Dict[str, Any]) -> str:
    lines = []
    lines.append(f"{'scenario':<26} {'scheds':>7} {'pruned':>7} "
                 f"{'prune%':>7} {'maxPre':>7}  note")
    for name, row in rep["scenarios"].items():
        if "skipped" in row:
            lines.append(f"{name:<26} {'-':>7} {'-':>7} {'-':>7} {'-':>7}"
                         f"  skipped (requires {row['skipped']})")
            continue
        note = "exhausted" if row["exhausted"] else "budget-capped"
        if row["violations"]:
            note += f", {len(row['violations'])} VIOLATION(S)"
        lines.append(
            f"{name:<26} {row['schedules']:>7d} {row['pruned']:>7d} "
            f"{row['pruning_ratio']:>7.1%} {row['max_preemptions']:>7d}"
            f"  {note}")
    crash_rows = {name: row for name, row in rep["scenarios"].items()
                  if row.get("crash")}
    if crash_rows:
        lines.append("")
        lines.append("crash-restart schedules (interleavings x crash "
                     "points, recovery re-run from durable state):")
        lines.append(f"  {'scenario':<26} {'bases':>6} {'crash':>6} "
                     f"{'scheds':>7} {'prune%':>7}")
        for name, row in crash_rows.items():
            lines.append(
                f"  {name:<26} {row['bases']:>6d} "
                f"{row['crash_schedules']:>6d} {row['schedules']:>7d} "
                f"{row['pruning_ratio']:>7.1%}")
    t = rep["totals"]
    lines.append("")
    lines.append(
        f"total: {t['schedules']} schedules explored, {t['pruned']} "
        f"pruned (sleep sets / preemption bound), "
        f"{t['violations']} violation(s), {t['skipped']} skipped")
    for name, row in rep["scenarios"].items():
        for v in row.get("violations", ()):
            lines.append(
                f"  VIOLATION [{v.get('invariant', '?')}] {name}: "
                f"{v.get('message', '')}  "
                f"(replay: python -m split_learning_tpu.analysis "
                f"--schedule {v.get('schedule_id', '?')})")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", nargs="?", default=None,
                    help="Chrome-trace file (obs export)")
    ap.add_argument("--json", action="store_true",
                    help="emit the report as JSON instead of the table")
    ap.add_argument("--tenants", type=int, default=0,
                    help="split server queue_wait spans into N tenants "
                         "(client_id %% N) and add a per-tenant tail "
                         "table")
    ap.add_argument("--schedules", default=None, metavar="PATH",
                    help="summarize an slt-check explorer report "
                         "(--check --report PATH) instead of / in "
                         "addition to a trace")
    args = ap.parse_args(argv)
    if args.trace is None and args.schedules is None:
        ap.error("give a trace file and/or --schedules PATH")
    if args.schedules:
        srep = summarize_schedules(args.schedules)
        try:
            print(json.dumps(srep, indent=2) if args.json
                  else render_schedules(srep))
        except BrokenPipeError:
            return 0
        if args.trace is None:
            return 0
        print()
    events = load_events(args.trace)
    if not events:
        print(f"[trace_report] no events parsed from {args.trace}",
              file=sys.stderr)
        return 1
    rep = summarize(events, tenants=max(args.tenants, 0))
    try:
        print(json.dumps(rep, indent=2) if args.json else render(rep))
    except BrokenPipeError:  # | head
        return 0
    return 0


if __name__ == "__main__":
    sys.exit(main())
