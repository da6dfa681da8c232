#!/usr/bin/env python
"""Measure the long-context transformer on the attached TPU chip:
dense vs Pallas-flash attention across context lengths, plus the
memory-ceiling probe (the T where the dense path stops compiling).

Writes artifacts/bench_tpu_transformer_<date>.json. Each leg is a
`bench.py --role fused` subprocess (one process holds the chip at a time, so
this parent stays off JAX and runs legs one after another), so every
number carries bench.py's own publication gate (util <= 1, work-scaling
window) and its full leg record.

Usage:
    python scripts/measure_long_context.py [--quick] [--out PATH]
"""

from __future__ import annotations

import argparse
import datetime
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from bench import _run_subprocess  # noqa: E402 — the one subprocess protocol

_ANSI = re.compile(r"\x1b\[[0-9;]*m")

# (seq_len, batch, attn, quick_leg) — batch drops as T grows so the
# *linear* activations fit; the point is the attention term
MATRIX = [
    (256, 64, "full", False),
    (256, 64, "flash", False),
    (1024, 64, "full", False),
    (1024, 64, "flash", False),
    (4096, 16, "full", True),
    (4096, 16, "flash", True),
    (8192, 16, "full", True),    # above the speed crossover (pinned at
    (8192, 16, "flash", True),   # T=1024 since 2026-08-01, _FLASH_SPEED_T)
    (16384, 16, "full", True),   # expected: dense OOM (P = 16 GiB > HBM)
    (16384, 16, "flash", True),
]


def run_leg(seq: int, batch: int, attn: str, quick: bool,
            timeout: float) -> dict:
    env = {"SLT_BENCH_MODEL": "transformer",
           "SLT_BENCH_DTYPE": "bfloat16",
           "SLT_BENCH_SEQ": str(seq),
           "SLT_BENCH_BATCH": str(batch),
           "SLT_BENCH_ATTN": attn}
    leg, out = _run_subprocess("fused", quick, env, timeout, capture=True)
    if out == "timeout":
        return {"seq_len": seq, "batch": batch, "attn": attn,
                "status": "timeout", "timeout_s": timeout}
    if leg is not None and out.returncode == 0:
        leg["status"] = "ok" if leg.get("valid") else "invalid"
        return leg
    err = _ANSI.sub("", out.stderr + out.stdout)
    marker = "Ran out of memory in memory space hbm"
    rec = {"seq_len": seq, "batch": batch, "attn": attn,
           "status": "oom" if marker in err else "error"}
    if marker in err:
        # keep just the sentence that states the ceiling
        start = err.index(marker)
        rec["detail"] = err[start:start + 200].splitlines()[0]
    else:
        rec["detail"] = err[-500:]
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="run every leg in --quick mode")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    date = datetime.date.today().isoformat()
    out_path = args.out or os.path.join(
        REPO, "artifacts", f"bench_tpu_transformer_{date}.json")
    legs = []
    for seq, batch, attn, quick_leg in MATRIX:
        quick = args.quick or quick_leg
        timeout = 1700 if seq >= 4096 else 900
        print(f"[long-context] T={seq} b={batch} attn={attn} "
              f"(quick={quick})...", file=sys.stderr, flush=True)
        leg = run_leg(seq, batch, attn, quick, timeout)
        print(f"[long-context]   -> {leg.get('status')} "
              f"{leg.get('steps_per_sec', '')}", file=sys.stderr, flush=True)
        legs.append(leg)

    doc = {
        "date": date,
        "what": ("Long-context split transformer on one TPU chip: dense "
                 "(XLA) vs Pallas-flash attention (ops/flash_attention.py), "
                 "d_model 256, 2 heads (head_dim 128), bf16, "
                 "bench.py fused role per leg (gated: util<=1 + "
                 "work-scaling window)"),
        "legs": legs,
    }
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1)
    print(out_path)


if __name__ == "__main__":
    main()
