"""The grouped products alone on the chip, at a routed cell's call shapes,
under several tilings (``ops/grouped_matmul._tiles``).

    chiprun -- python scripts/gmm_tiles_chip.py [--workload CELL ...]
        [--width-tiles 512 768 1024 1536] [--row-tiles 256 384]
        [--table chiprun_out/<window>.json ...]

A routed layer runs six distinct kernel calls a step: for gate / up
(``[rows, d_model] x [d_model, width]``) and for down (``[rows, width] x
[width, d_model]``) the forward ``gmm``, the rows' gradient (``gmm`` with
``rhs`` transposed) and the weights' gradient (``tgmm``, float32).  Each is
timed here under the module's own tiling, under the rule before PR 38
(``min(dimension, 1024)``), and under every ``--width-tiles`` edge no wider
than the expert width put on it (the model dimension keeps the module's tile;
the gradients' tilings follow through ``_bwd_tiles``); then, at the module's ``k`` and ``n``
tiles, under every ``--row-tiles`` edge in place of ``_TM`` (for the record:
``models/afmoe.pair_rungs`` counts in ``_TM``, so the module does not take
them).

Group sizes are a routed layer's at one early and one late step of a window
by the step's own counters: from a ``--table`` (what ``scripts/routed_window.py
--table`` wrote for the cell: the (step, layer) sample nearest the median
pairs of the window's first and of its last 25 steps, run over that sample's
rung of rows) or else from ``RECORDED`` below.  One JSON line a reading:
median milliseconds a call (``CHAIN`` calls on distinct operands in one
program, so that no dispatch gap is timed), its share of the roofline at the
pairs the groups hold (``benchmarks/flops/afmoe.py:expert_mm``, the
benchmark's cost), ``tile_fill``, the active row-tile visits, and the largest
difference from the module's tiling relative to the largest entry; then a
line a (cell, step, tiling) with the twelve calls of a layer summed.  A tiling
Mosaic refuses reads ``"error"``.  Exits 1 without a TPU or if a difference
passes 2e-2.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

CHAIN = 8
# calls of each kind a layer and step: gate and up share a shape, and the
# forward runs again in the backward pass (models/afmoe.py recomputes it)
PER_LAYER = {"up.fwd": 4, "up.d_rows": 2, "up.d_weights": 2,
             "down.fwd": 2, "down.d_rows": 1, "down.d_weights": 1}
# a routed layer's pairs by held expert and the rung of rows it ran, picked
# by ``pick`` from one 20 s window a cell under scripts/routed_window.py
# (my chip runs: lfm2 seed 2147483752, PR 37; trinity-mini seed 2147483862
# and joyai-flash seed 2147483872, PR 38: their late samples stay in the low
# rung, as most of their window's do)
RECORDED = {
    "trinity-mini-fused-t8192": {
        "early": {"rows": 8192, "sizes": [150, 155, 339, 830, 99, 1264, 972, 599]},
        "late": {"rows": 8192, "sizes": [720, 104, 2110, 809, 314, 528, 3200, 241]}},
    "joyai-flash-fused-t8192": {
        "early": {"rows": 4096, "sizes": [298, 252, 70, 326, 143, 626, 161, 21]},
        "late": {"rows": 4096, "sizes": [185, 766, 703, 536, 572, 336, 130, 232]}},
    "lfm2-moe-fused-t8192": {
        "early": {"rows": 8192, "sizes": [462, 1142, 882, 905, 330, 692, 586, 835]},
        "late": {"rows": 32768, "sizes": [1708, 2498, 1455, 2404, 2426, 2104, 1949, 2984]}},
}


def pick(rows: list) -> dict:
    """The early and the late sample of a window's per-step rows."""
    def nearest(steps):
        samples = [(sum(p), p, r) for s in steps
                   for p, r in zip(s["pairs"], s["rows"])]
        middle = statistics.median(total for total, _, _ in samples)
        _, sizes, rung = min(samples, key=lambda s: abs(s[0] - middle))
        return {"rows": rung, "sizes": sizes}
    return {"early": nearest(rows[:25]), "late": nearest(rows[-25:])}


def visits(sizes: list, tm: int) -> int:
    """Row tiles of ``tm`` the kernels visit: a tile once a group in it."""
    starts = itertools.accumulate(sizes, initial=0)
    return sum(-(-(start % tm + size) // tm)
               for start, size in zip(starts, sizes) if size)


def tilings(gm, rows: int, d: int, width: int, width_tile=None, tm=None) -> dict:
    """Call name -> (tm, tk, tn) of a layer's six calls, the expert width
    in tiles of ``width_tile`` and the rows in tiles of ``tm`` where given."""
    found = {}
    for name, (k, n) in (("up", (d, width)), ("down", (width, d))):
        tiling = list(gm._tiles(rows, k, n))
        if width_tile:
            tiling[1 if name == "down" else 2] = width_tile
        if tm:
            tiling[0] = tm
        d_rows, d_weights = gm._bwd_tiles(tuple(tiling))
        found.update({name + ".fwd": tuple(tiling), name + ".d_rows": d_rows,
                      name + ".d_weights": d_weights})
    return found


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", nargs="+", default=["lfm2-moe-fused-t8192"])
    parser.add_argument("--width-tiles", nargs="*", type=int, default=[512, 768, 1024, 1536])
    parser.add_argument("--row-tiles", nargs="*", type=int, default=[256, 384])
    parser.add_argument("--table", nargs="*", default=[])
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import run
    from flops.afmoe import expert_mm   # the one cost every routed family shares
    from flops.common import least_seconds
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm
    from split_learning_tpu.ops import grouped_matmul as gm
    from split_learning_tpu.ops.common import pad_axis, round_up, use_interpret
    device = jax.devices()[0]
    if device.platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    with open(os.path.join(ROOT, "benchmarks", "peaks.json")) as f:
        peak = json.load(f)[device.device_kind]
    worst = 0.0
    tables = {}
    for path in args.table:
        with open(path) as f:
            record = json.load(f)
        tables[record["workload"]] = pick(record["rows"])

    def median_ms(fn, operands, n=10):
        jax.block_until_ready(fn(*operands))
        times = []
        for _ in range(n):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*operands))
            times.append(1e3 * (time.perf_counter() - t0))
        return statistics.median(times) / CHAIN

    def call(kind, tiling):
        """One program of CHAIN calls of a kernel, each on its own rows."""
        interpret = use_interpret()
        if kind == "fwd":
            one = lambda a, w, gs: gmm(a, w, gs, jnp.bfloat16, tiling,
                                       interpret=interpret)
        elif kind == "d_rows":
            one = lambda a, w, gs: gmm(a, w, gs, jnp.bfloat16, tiling,
                                       transpose_rhs=True, interpret=interpret)
        else:
            one = lambda a, b, gs: tgmm(a.swapaxes(0, 1), b, gs, jnp.float32,
                                        tiling, num_actual_groups=gs.shape[0],
                                        interpret=interpret)
        return jax.jit(lambda firsts, second, gs: tuple(
            one(first, second, gs) for first in firsts))

    def operands(kind, rows, padded, k, n, held, key):
        """(CHAIN first operands, the shared second) of a kernel's calls for
        a forward product ``[rows, k] x [held, k, n]``, the rows padded with
        zeros to whole row tiles: the same values under every tiling."""
        ks = jax.random.split(key, CHAIN + 1)
        rows_of = lambda key, width: pad_axis(jax.random.normal(
            key, (rows, width), jnp.bfloat16), 0, padded)
        weights = jax.random.normal(ks[-1], (held, k, n), jnp.bfloat16) * 0.02
        if kind == "fwd":
            return tuple(rows_of(key, k) for key in ks[:-1]), weights
        if kind == "d_rows":
            return tuple(rows_of(key, n) for key in ks[:-1]), weights
        return tuple(rows_of(key, k) for key in ks[:-1]), rows_of(ks[-1], n)

    for workload in args.workload:
        _, _, config = run.load_cell(workload)
        kw = config["plan"]["kwargs"]
        d, width, held = kw["d_model"], kw["expert_width"], kw["experts_held"]
        steps = tables.get(workload) or RECORDED[workload]
        for when, sample in steps.items():
            rows, sizes = sample["rows"], sample["sizes"]
            pairs = sum(sizes)
            gs = jnp.asarray(sizes, jnp.int32)
            print(json.dumps({"cell": workload, "step": when, "rows": rows,
                              "pairs": pairs, "sizes": sizes}), flush=True)
            own = tilings(gm, rows, d, width)
            old = min(width, 1024)      # the rule before PR 38
            choices = {"module": own}
            for t in dict.fromkeys([old] + args.width_tiles):
                if t <= width:
                    choices[f"width tile {t}" + (" (before PR 38)" if t == old else "")] = \
                        tilings(gm, rows, d, width, width_tile=t)
            for tm in args.row_tiles:
                choices[f"row tile {tm}"] = tilings(gm, rows, d, width, tm=tm)
            choices = {label: chosen for label, chosen in choices.items()
                       if label == "module" or chosen != own}
            base = {}
            for label, chosen in choices.items():
                layer_ms, failed = 0.0, False
                for name, tiling in chosen.items():
                    shape, kind = name.split(".", 1)
                    k, n = (d, width) if shape == "up" else (width, d)
                    padded = round_up(rows, tiling[0])
                    firsts, second = operands(kind, rows, padded, k, n, held,
                                              jax.random.PRNGKey(len(name)))
                    ops, moved = expert_mm(
                        pairs, held, d, width,
                        weight_itemsize=4 if kind == "d_weights" else 2)
                    least, bound = least_seconds(ops, moved, peak)
                    # the kernel's own k and n: the rows' gradient swaps them
                    kk, nn = (n, k) if kind == "d_rows" else (k, n)
                    line = {"cell": workload, "step": when, "choice": label,
                            "call": name, "tiling": tiling,
                            "tile_fill": gm.tile_fill(padded, kk, nn, tiling),
                            "row_tile_visits": visits(sizes, tiling[0])}
                    try:
                        fn = call(kind, tiling)
                        out = fn(firsts, second, gs)[0].astype(jnp.float32)
                        if kind != "d_weights":     # rows past the groups: unwritten
                            out = out[:pairs]
                        ms = median_ms(fn, (firsts, second, gs))
                    except Exception as e:  # Mosaic refuses the tiling
                        line["error"] = str(e).strip().splitlines()[0][:200]
                        failed = True
                        print(json.dumps(line), flush=True)
                        continue
                    if label == "module":
                        base[name] = out
                    err = float(jnp.abs(out - base[name]).max()
                                / jnp.abs(base[name]).max())
                    worst = max(worst, err)
                    layer_ms += PER_LAYER[name] * ms
                    line.update(ms=ms, least_ms=1e3 * least, bound=bound,
                                roofline_pct=100 * 1e3 * least / ms, err=err)
                    print(json.dumps(line), flush=True)
                if not failed:
                    print(json.dumps({"cell": workload, "step": when,
                                      "choice": label,
                                      "layer_ms_12_calls": layer_ms}),
                          flush=True)
    print(json.dumps({"worst_err": worst}))
    return 0 if worst < 2e-2 else 1


if __name__ == "__main__":
    sys.exit(main())
