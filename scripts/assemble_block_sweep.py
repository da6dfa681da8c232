#!/usr/bin/env python
"""Fold window-runner flash block-sweep legs into a committed artifact
(VERDICT r4 #8: `_pick_block`'s 512 edge was chosen from ONE
measurement; its ceiling is unexplored).

The runner's ``sweep.T{seq}.b{batch}.flash.blk{block}`` legs re-run the
standard transformer flash leg with ``SLT_FLASH_BLOCK`` pinned; the
incumbent 512-edge numbers come from the main ``T{seq}...flash`` legs
of the same jsonl. This script tabulates steps/sec per (seq_len, block
edge), marks each shape's winner, and — when a non-incumbent edge wins
by more than the noise margin — says exactly what `_pick_block` should
adopt. Adoption stays a HUMAN edit (one constant with an evidence
note), the same discipline as `_FLASH_SPEED_T`.

Usage: python scripts/assemble_block_sweep.py [--out PATH]
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RUNS = os.path.join(REPO, "artifacts", "tpu_window_runs.jsonl")
sys.path.insert(0, REPO)

_SWEEP = re.compile(r"^sweep\.T(\d+)\.b(\d+)\.flash\.blk(\d+)$")
_MAIN = re.compile(r"^T(\d+)\.b(\d+)\.flash\.(q|full)$")


def _incumbent_block(seq: int) -> int:
    """What `_pick_block` itself chooses for this T — imported, never
    re-derived, so the artifact can't misattribute a main-leg number
    to a block edge the kernel didn't use. The env override is masked
    (and restored) rather than popped: assembling must not mutate the
    caller's environment."""
    from split_learning_tpu.ops.flash_attention import _pick_block
    saved = os.environ.pop("SLT_FLASH_BLOCK", None)
    try:
        return _pick_block(seq)
    finally:
        if saved is not None:
            os.environ["SLT_FLASH_BLOCK"] = saved


def _legacy_block(seq: int) -> int:
    """The default edge for jsonl records that PREDATE bench.py's
    ``flash_block`` field (everything before the 2026-08-01 morning
    window): the pre-sweep picker started at 512, so that is the edge
    those kernels actually compiled with. Frozen here — today's
    `_pick_block` starts at 1024 (adopted from this very sweep) and
    must not be used to label yesterday's runs."""
    b = 512
    tp128 = seq if seq % 128 == 0 else seq + 128 - seq % 128
    while b > 128 and tp128 % b:
        b //= 2
    return b


# best-vs-median spread of healthy window legs runs ~5-10%; a winner
# must clear the incumbent by more than that to justify a re-pin
NOISE_MARGIN = 0.10


def load_records():
    with open(RUNS) as f:
        return [json.loads(line) for line in f if line.strip()]


def _valid_tpu(rec):
    r = rec.get("result")
    return (rec.get("status") == "ok" and r and r.get("valid", False)
            and r.get("platform") == "tpu")


def collect(records):
    """{(seq_len, batch): {block_edge: best steps/sec}} — sweep legs
    give the non-default edges, the newest main flash leg gives the
    incumbent (its block read off `_pick_block`, not re-derived).
    Keyed by batch too: steps/sec at different batch sizes are not
    comparable, so they never share a row."""
    table: dict[tuple[int, int], dict[int, float]] = {}
    for rec in records:
        if not _valid_tpu(rec):
            continue
        m = _SWEEP.match(rec.get("leg", ""))
        if m:
            seq, batch, blk = (int(g) for g in m.groups())
        else:
            m = _MAIN.match(rec.get("leg", ""))
            if not m:
                continue
            seq, batch = int(m.group(1)), int(m.group(2))
            # the edge the kernel ACTUALLY ran with, frozen into the
            # record by bench.py at measurement time; records predating
            # that field get the frozen pre-sweep default they really
            # compiled with, never today's picker
            blk = rec["result"].get("flash_block") or _legacy_block(seq)
        sps = rec["result"]["steps_per_sec"]
        cur = table.setdefault((seq, batch), {})
        cur[blk] = max(cur.get(blk, 0.0), sps)
    return table


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(
        REPO, "artifacts", "flash_block_sweep.json"))
    args = ap.parse_args()
    table = collect(load_records())
    if not table:
        raise SystemExit("no gate-passing flash legs in " + RUNS)

    shapes = []
    recommendations = []
    for seq, batch in sorted(table):
        edges = table[(seq, batch)]
        winner = max(edges, key=edges.get)
        incumbent = _incumbent_block(seq)
        row = {"seq_len": seq, "batch": batch,
               "steps_per_sec_by_block": {str(k): round(v, 3)
                                          for k, v in sorted(edges.items())},
               "winner_block": winner,
               "incumbent_block": incumbent,
               "swept": len(edges) > 1}
        if (len(edges) > 1 and winner != incumbent
                and incumbent in edges
                and edges[winner] > edges[incumbent] * (1 + NOISE_MARGIN)):
            row["recommend"] = (
                f"_pick_block should prefer {winner} at T={seq}: "
                f"{edges[winner]:.2f} vs {edges[incumbent]:.2f} steps/s "
                f"(+{edges[winner] / edges[incumbent] - 1:.0%})")
            recommendations.append(row["recommend"])
        shapes.append(row)

    art = {
        "provenance": {
            "date": time.strftime("%Y-%m-%d"),
            "command": "scripts/assemble_block_sweep.py (the sweep.* "
                       "legs of artifacts/tpu_window_runs.jsonl)",
            "noise_margin": NOISE_MARGIN,
        },
        "shapes": shapes,
        "recommendations": recommendations,
    }
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(art, f, indent=1)
    print(json.dumps({"shapes": len(shapes),
                      "swept": sum(1 for s in shapes if s["swept"]),
                      "recommendations": recommendations,
                      "artifact": args.out}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
