#!/usr/bin/env python
"""Measure autoregressive decode throughput on the attached TPU chip:
KV-cache decode vs the O(T^2) re-forward path (runtime/generate.py).

Writes artifacts/bench_tpu_decode_<date>.json. The measurement runs as a
`bench.py --role decode` subprocess (one process holds the chip at a
time, so this parent stays off JAX) so it carries bench.py's
linearity gate and leg record.

Usage:
    python scripts/measure_decode.py [--quick] [--out PATH]
"""

from __future__ import annotations

import argparse
import datetime
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from bench import _run_subprocess  # noqa: E402 — the one subprocess protocol


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    date = datetime.date.today().isoformat()
    out_path = args.out or os.path.join(
        REPO, "artifacts", f"bench_tpu_decode_{date}.json")

    leg, out = _run_subprocess("decode", args.quick, {}, timeout=1700,
                               capture=True)
    if out == "timeout":
        rec = {"status": "timeout"}
    elif leg is None:
        rec = {"status": "error",
               "detail": (out.stderr + out.stdout)[-800:]}
    else:
        rec = leg
        rec["status"] = "ok" if leg.get("valid") else "invalid"

    artifact = {
        "provenance": {
            "date": date,
            "command": "python scripts/measure_decode.py"
                       + (" --quick" if args.quick else ""),
            "note": "KV-cache vs re-forward greedy decode, bf16 LM "
                    "(d_model 256, 2 heads); windows close on a host "
                    "transfer of the generated tokens",
        },
        "decode": rec,
    }
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(artifact, f, indent=1)
    print(json.dumps(rec))
    print(f"[decode] wrote {out_path}", file=sys.stderr)


if __name__ == "__main__":
    main()
