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
window's records: for each routed layer the window step at which it first ran
a rung above its lowest and the share of steps it spent there, its pairs over
the even share at the first and the last step and its fullest expert's share
of them; the median step time by the number of layers above the lowest rung;
what a rung of twice the lowest would have held; what reading the counters
cost. ``--table`` keeps the per-step rows. On the CPU (``JAX_PLATFORMS=cpu``)
it is the rehearsal's sizes, where the ladder has one rung.
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
    first ``before`` (the check steps and the warm-up step)."""
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
                     "up": [r > min(l) for r, l in zip(a["rows"], a["ladder"])]})
    if not rows:
        return {"steps": len(steps), "layers": []}, rows
    low = [min(l) for l in a["ladder"]]
    by_layer = {}
    for i, layer in enumerate(layers):
        up = [r["up"][i] for r in rows]
        held = [sum(r["pairs"][i]) for r in rows]
        by_layer[layer] = {
            "first_step_up": up.index(True) if any(up) else None,
            "share_of_steps_up": sum(up) / len(up),
            "pairs_x_even_first": held[0] / even,
            "pairs_x_even_last": held[-1] / even,
            "pairs_x_even_max": max(held) / even,
            "fullest_expert_share_first": max(rows[0]["pairs"][i]) / max(held[0], 1),
            "fullest_expert_share_last": max(rows[-1]["pairs"][i]) / max(held[-1], 1)}
    by_up = {}
    for r in rows:
        by_up.setdefault(sum(r["up"]), []).append(r["ms"])
    samples = [(sum(p), lo) for r in rows for p, lo in zip(r["pairs"], low)]
    over = [(p, lo) for p, lo in samples if p > lo]
    return {
        "steps": len(rows), "layers": by_layer, "ladder": a["ladder"][0],
        "even_pairs": even,
        "step_ms_by_layers_up": {str(n): {"steps": len(ms), "median_ms": statistics.median(ms)}
                                 for n, ms in sorted(by_up.items())},
        "samples": len(samples), "samples_over_lowest": len(over),
        "of_them_within_twice_lowest": sum(p <= 2 * lo for p, lo in over),
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
