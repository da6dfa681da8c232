#!/usr/bin/env python
"""Are the program's spans on the profiler's clock?

Runs a few two-party steps (the split CNN, LocalTransport) under a
``jax.profiler`` session on whatever device JAX finds, then opens the
session's ``.xplane.pb`` and, for every span name, lays the recorded
starts (obs/trace.py, ``time.time_ns()``) beside the starts of the
annotations of that name in the trace (``profile_start_time`` of the
``Task Environment`` plane + the event's offset). Prints the largest
distance per name and, for one step, the device operations that ran
between that step's bounds — the program's spans as host events beside
the device's own. Exits 1 if any distance passes ``--tolerance-us``.

Run: python scripts/span_clock_check.py [--steps 8] [--tolerance-us 50]
(on the chip: ``chiprun -- python scripts/span_clock_check.py``).
"""

from __future__ import annotations

import argparse
import glob
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--tolerance-us", type=float, default=50.0)
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    from jax.profiler import ProfileData

    from split_learning_tpu import obs
    from split_learning_tpu.models import get_plan
    from split_learning_tpu.runtime import ServerRuntime, SplitClientTrainer
    from split_learning_tpu.transport import LocalTransport
    from split_learning_tpu.utils import Config

    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind}")
    cfg = Config(mode="split", batch_size=64)
    plan = get_plan(mode="split")
    rs = np.random.RandomState(0)
    x = rs.randn(64, 28, 28, 1).astype(np.float32)
    y = rs.randint(0, 10, (64,)).astype(np.int64)
    server = ServerRuntime(plan, cfg, jax.random.PRNGKey(0), x)
    client = SplitClientTrainer(plan, cfg, jax.random.PRNGKey(0),
                                LocalTransport(server))
    for i in range(3):  # compile outside the session
        client.train_step(x, y, i)
    log_dir = tempfile.mkdtemp(prefix="slt-clock-")
    with jax.profiler.trace(log_dir):
        for i in range(3, 3 + args.steps):
            client.train_step(x, y, i)
        jax.block_until_ready(client.state)
    recs = obs.recorded()
    path, = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    data = ProfileData.from_file(path)
    origin = dict(data.find_plane_with_name("Task Environment").stats)[
        "profile_start_time"]
    host = [(e.name, origin + int(e.start_ns), int(e.duration_ns))
            for p in data.planes if p.name.startswith("/host:")
            for line in p.lines for e in line.events]
    device = [(p.name, e.name, origin + int(e.start_ns), int(e.duration_ns))
              for p in data.planes if p.name.startswith("/device:")
              for line in p.lines if line.name == "XLA Ops"
              for e in line.events]
    worst = 0.0
    for name in sorted({r["name"] for r in recs}):
        rec = sorted(r["start_ns"] for r in recs if r["name"] == name)
        ann = sorted(s for n, s, _ in host if n == name)
        if len(ann) != len(rec):
            print(f"{name:12s} recorded {len(rec)} annotated {len(ann)}"
                  "  (counts differ)")
            worst = float("inf")
            continue
        far = max(abs(a - r) for a, r in zip(ann, rec)) / 1e3
        worst = max(worst, far)
        print(f"{name:12s} n {len(rec):3d}  recorded start against the "
              f"annotation's: at most {far:8.3f} us apart")
    step = sorted((r for r in recs if r["name"] == "step_total"),
                  key=lambda r: r["start_ns"])[len(recs) and 1]
    inside = sorted((r for r in recs
                     if step["start_ns"] <= r["start_ns"] <= step["end_ns"]),
                    key=lambda r: r["start_ns"])
    ops = [d for d in device
           if step["start_ns"] <= d[2] <= step["end_ns"]]
    print(f"one step ({step['trace_id']}), {step['dur_ns'] / 1e6:.3f} ms, "
          f"{len(ops)} device operations between its bounds "
          f"({len(device)} in the session):")
    for r in inside:
        n = sum(1 for d in ops if r["start_ns"] <= d[2] <= r["end_ns"])
        print(f"  +{(r['start_ns'] - step['start_ns']) / 1e3:9.1f} us "
              f"{r['party']:6s} {r['name']:12s} {r['dur_ns'] / 1e3:9.1f} us"
              f"  device ops started inside: {n}")
    for d in ops[:5]:
        print(f"  device +{(d[2] - step['start_ns']) / 1e3:9.1f} us "
              f"{d[3] / 1e3:8.1f} us {d[1][:70]}")
    print(f"largest distance {worst:.3f} us, tolerance "
          f"{args.tolerance_us:.0f} us")
    return 0 if worst <= args.tolerance_us else 1


if __name__ == "__main__":
    sys.exit(main())
