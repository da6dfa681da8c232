"""What the program's device scopes cost a step, from a traced benchmark run.

    python scripts/scope_time.py --workload lfm2-moe-fused-t8192 --seed N
        [--scopes short_conv,attn_full,moe_route,moe_experts]
    python scripts/scope_time.py --workload nemotronh-moe-fused-t8192 --seed N
        [--scopes ssm_ssd,ssm_conv,moe_shared] [--top 8]
    python scripts/scope_time.py --workload ouro-loop-fused-t8192 --seed N
        [--scopes attn_full] [--top 8]

Runs ``benchmarks/run.py``'s own ``main`` with ``--trace 1`` and, before the
run deletes its trace, reads the xplane once more: the benchmark's reduction
names a device event by its HLO result and opcode, so an XLA fusion loses the
``jax.named_scope`` it was traced under (PERF.md section 7, item 8b). The
instruction's ``op_name`` still holds it (``jit(_step)/.../short_conv/mul``;
in the backward pass ``transpose(jvp(short_conv))/...``): the script takes it
from the event's own text where the profiler put it there, and else from the
compiled step's HLO text (``FusedSplitTrainer._step`` compiled once more,
from the cache), by the instruction's name. The benchmark's result
line comes first; then one JSON line: for each scope (``obs/spans.py``
``DEVICE_SCOPES`` by default) the events whose ``op_name`` holds it, their
device milliseconds a step of the window, and their share of the device's
busy time (with ``--top N`` also the scope's N longest operations). A fusion
counts under the scope of its root operation, and a
``conditional`` covers its branch's operations, so the routed layer's scopes
count twice what ran inside a rung: read those from the grouped products'
own metrics. Only a chip run has device events; on the CPU
(``JAX_PLATFORMS=cpu``) the second line says so.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))


_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT )?%?([\w.\-]+) = .*metadata=\{[^}]*op_name="([^"]*)"', re.M)


def op_names(hlo_text: str) -> dict:
    """``{instruction name: op_name}`` of a compiled module's text."""
    return dict(_INSTRUCTION.findall(hlo_text))


def scope_seconds(path: str, scopes: tuple, step_span: str, names: dict,
                  top: int = 0) -> dict:
    """Per scope ``{"events", "ms_per_step", "share_of_busy_pct"}`` over the
    ``bench.window`` of the xplane at ``path``, and with ``top`` its longest
    operations (``trace_reduce.short_name``, milliseconds a step);
    ``names`` is :func:`op_names` of the step that ran."""
    from jax.profiler import ProfileData

    import trace_reduce
    data = ProfileData.from_file(path)
    lo = hi = None
    steps = 0
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == trace_reduce.WINDOW_SPAN:
                        lo, hi = int(e.start_ns), int(e.start_ns + e.duration_ns)
                    steps += e.name == step_span
    found = {s: [0, 0] for s in scopes}
    ops = {s: {} for s in scopes}
    busy, named = [], 0
    for plane in data.planes:
        if not plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            if line.name != trace_reduce.OPS_LINE:
                continue
            for e in line.events:
                a, b = int(e.start_ns), int(e.start_ns + e.duration_ns)
                if lo is not None:
                    a, b = max(a, lo), min(b, hi)
                if b <= a:
                    continue
                busy.append([a, b])
                op_name = names.get(e.name.partition(" = ")[0].lstrip("%"), "")
                named += bool(op_name) or "op_name=" in e.name
                text = e.name + " " + op_name
                for scope in scopes:
                    if scope in text:
                        found[scope][0] += 1
                        found[scope][1] += b - a
                        short = trace_reduce.short_name(e.name)
                        ops[scope][short] = ops[scope].get(short, 0) + b - a
    busy_ns = sum(b - a for a, b in trace_reduce.union(busy))

    def longest(scope: str) -> dict:
        ranked = sorted(ops[scope].items(), key=lambda kv: -kv[1])[:top]
        return {"top": [[name, ns * 1e-6 / max(steps, 1)]
                        for name, ns in ranked]} if top else {}

    return {"steps": steps, "events": len(busy), "events_with_an_op_name": named,
            "busy_ms_per_step": busy_ns * 1e-6 / max(steps, 1),
            "scopes": {s: {"events": n, "ms_per_step": ns * 1e-6 / max(steps, 1),
                           "share_of_busy_pct": 100.0 * ns / max(busy_ns, 1),
                           **longest(s)}
                       for s, (n, ns) in found.items()}}


def main() -> int:
    from split_learning_tpu.obs import spans
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", default="lfm2-moe-fused-t8192")
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--scopes", default=",".join(spans.DEVICE_SCOPES))
    parser.add_argument("--top", type=int, default=0,
                        help="a scope's longest operations to name")
    args = parser.parse_args()
    scopes = tuple(s for s in args.scopes.split(",") if s)

    import run
    import trace_reduce
    import traffic
    _, cell, _ = run.load_cell(args.workload)
    step_span = f"{traffic.load(cell['traffic'])['path']}.train_step"
    import paths.fused
    read, kept = {}, {}
    reduce_trace = run.reduce_trace

    class Keeping(paths.fused.Driver):
        """The benchmark's driver, which also keeps the text of the step it
        runs: compiled for the first batch, before the window."""

        def step(self, batch) -> list:
            if not kept:
                (x, y), = batch
                trainer = self.trainer
                kept.update(op_names(trainer._step.lower(
                    trainer.state, x, y).compile().as_text()))
            return super().step(batch)

    def and_the_scopes(trace_dir, workload, chips):
        read.update(scope_seconds(trace_reduce.newest_xplane(trace_dir),
                                  scopes, step_span, kept, args.top))
        return reduce_trace(trace_dir, workload, chips)

    paths.fused.Driver = Keeping
    run.reduce_trace = and_the_scopes
    sys.argv = [sys.argv[0], "--workload", args.workload, "--seed",
                str(args.seed), "--seconds", "4", "--trace", "1"]
    code = run.main()
    if code:
        return code
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      **(read or {"scopes": None,
                                  "why": "no device trace: a CPU rehearsal"})}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
