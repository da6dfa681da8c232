"""One whole benchmark window of a routed cell with the recorder on, and what
its step counters say.

    python scripts/routed_window.py --workload trinity-mini-fused-t8192 --seed N
        [--seconds 20] [--table chiprun_out/<name>.json]

(any cell whose plan holds ``models/afmoe.py``'s ``RoutedExperts``: the
``joyai-flash``, ``lfm2-moe`` and ``nemotronh-moe`` cells too)

Runs ``benchmarks/run.py``'s own ``main`` (the cell's weights, batches, check
steps and untraced window) under ``obs.enable()``: no profiler session, the
recorder alone, so the step is the untraced one plus one ``counters_read`` a
step (``runtime/fused.py``). The benchmark's result line comes first (its
``tokens_per_s`` is the rate with recording on); then one JSON line from the
window's records: for each routed layer its steps by the rung it ran (one
count a rung of its ladder, lowest first, whatever the ladder's length), the
window step at which it first ran each rung above its lowest, its pairs over
the even share at the first and the last step and its fullest expert's share
of them; the (step, layer) samples by rung over all layers; the median step
time by how many layers ran each rung (``"3/1/0"``: three on the lowest, one
on the next, none on the top); what reading the counters cost. ``--table``
keeps the per-step rows. On the CPU (``JAX_PLATFORMS=cpu``) it is the
rehearsal's sizes, where the ladder has one rung.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))


def reduce(records: list, before: int, even: float) -> tuple:
    """(summary, per-step rows) of the window: the records' steps but the
    first ``before`` (the check steps and the warm-up step). A row's
    ``rung`` is, layer by layer, the index in the layer's ladder of the
    rows it ran."""
    steps = [r for r in records if r["name"] == "step_total"][before:]
    reads = {r["parent_id"]: r for r in records if r["name"] == "counters_read"}
    rows, layers = [], []
    for k, step in enumerate(s for s in steps if s["span_id"] in reads):
        read = reads[step["span_id"]]
        a = read["attrs"]
        layers = a["layers"]
        rows.append({"step": k, "ms": 1e3 * step["duration"],
                     "read_ms": 1e3 * read["duration"], "pairs": a["pairs"],
                     "rows": a["rows"],
                     "rung": [l.index(r)
                              for r, l in zip(a["rows"], a["ladder"])]})
    if not rows:
        return {"steps": len(steps), "layers": []}, rows
    ladders = a["ladder"]       # pair_rungs': smallest first
    by_layer = {}
    for i, layer in enumerate(layers):
        ran = [r["rung"][i] for r in rows]
        held = [sum(r["pairs"][i]) for r in rows]
        by_layer[layer] = {
            "ladder": ladders[i],
            "steps_by_rung": [ran.count(n) for n in range(len(ladders[i]))],
            "first_step_on_rung": [
                next((k for k, n in enumerate(ran) if n >= rung), None)
                for rung in range(1, len(ladders[i]))],
            "pairs_x_even_first": held[0] / even,
            "pairs_x_even_last": held[-1] / even,
            "pairs_x_even_max": max(held) / even,
            "fullest_expert_share_first": max(rows[0]["pairs"][i]) / max(held[0], 1),
            "fullest_expert_share_last": max(rows[-1]["pairs"][i]) / max(held[-1], 1)}
    longest = max(len(l) for l in ladders)
    by_rungs = {}
    for r in rows:
        key = "/".join(str(r["rung"].count(n)) for n in range(longest))
        by_rungs.setdefault(key, []).append(r["ms"])
    sampled = [n for r in rows for n in r["rung"]]
    return {
        "steps": len(rows), "layers": by_layer, "ladder": ladders[0],
        "even_pairs": even,
        "step_ms_by_layers_on_each_rung": {
            key: {"steps": len(ms), "median_ms": statistics.median(ms)}
            for key, ms in sorted(by_rungs.items(), reverse=True)},
        "step_ms_median": statistics.median(r["ms"] for r in rows),
        "samples": len(sampled),
        "samples_by_rung": [sampled.count(n) for n in range(longest)],
        "counters_read_ms_p50": statistics.median(r["read_ms"] for r in rows),
        "counters_read_ms_max": max(r["read_ms"] for r in rows)}, rows


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", default="trinity-mini-fused-t8192")
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--seconds", type=float, default=20.0)
    parser.add_argument("--table", default=None)
    args = parser.parse_args()

    import run
    import traffic
    from split_learning_tpu import obs
    _, cell, config = run.load_cell(args.workload)
    job = traffic.load(cell["traffic"])
    tracer = obs.enable()
    sys.argv = [sys.argv[0], "--workload", args.workload, "--seed", str(args.seed),
                "--seconds", str(args.seconds), "--trace", "0"]
    try:
        code = run.main()
    finally:
        obs.disable()
    if code:
        return code
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        config, job = run.rehearsal_sizes(config, job)
    flops = importlib.import_module(f"flops.{config['family']}")
    even = traffic.tokens_per_step(job) * flops.expected_pairs_per_token(
        config["plan"]["kwargs"])
    summary, rows = reduce(tracer.spans(), job["check_steps"] + 1, even)
    if args.table:
        os.makedirs(os.path.dirname(os.path.abspath(args.table)), exist_ok=True)
        with open(args.table, "w") as f:
            json.dump({"workload": args.workload, "seed": args.seed,
                       "summary": summary, "rows": rows}, f)
    print(json.dumps({"workload": args.workload, "seed": args.seed, **summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
