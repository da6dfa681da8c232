"""Do a routed layer's rungs give the same bits when compiled alone?

    chiprun -- python scripts/rung_bits_chip.py 8192 8 2048 1024 8 128 1
    (tokens, experts a token, d_model, expert width, experts held, experts in
    all, gated 0|1: Trinity-Mini's layer; Nemotron-H's is 8192 6 2688 1856 8 128 0)

One layer's forward and backward bodies (``models/afmoe._rung``) at every rung
of ``pair_rungs`` that holds the pairs of one seeded, near-even routing, on the
same operands; one JSON line: for the output and each gradient, how many
elements differ from the top rung's and by how much. PR 40 read them equal bit
for bit on the chip (but the router weights' float32 gradient of the ungated
form, one ulp apart in 4.6 % of its elements); inside a whole step the rungs
do differ in the last bfloat16 bit (``scripts/ladder_numbers_chip.py``). Runs
anywhere; only a chip run says what the chip's compiler does.
"""

from __future__ import annotations

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from split_learning_tpu.models import afmoe


def main(n, k, d, width, held, total, gated=True, seed=0) -> None:
    key = jax.random.split(jax.random.PRNGKey(seed), 8)
    m = jax.random.normal(key[0], (n, d), jnp.bfloat16)
    router = 0.02 * jax.random.normal(key[1], (d, total))
    chosen, weights = afmoe.route(m.astype(jnp.float32), router, jnp.zeros(total), k, 2.5)
    order, inverse, sizes = afmoe.held_pairs(chosen, 0, held)
    shapes = ([(held, d, width)] if gated else []) + [(held, d, width), (held, width, d)]
    mats = [0.02 * jax.random.normal(key[2 + i], s) for i, s in enumerate(shapes)]
    g = jax.random.normal(key[6], (n, d), jnp.bfloat16)
    rungs = afmoe.pair_rungs(n * k, held, total)
    filled = int(sizes.sum())
    names = ["y", "d_m", "d_weights"] + ["d_gate", "d_up", "d_down"][3 - len(mats):]
    outs = {}
    for rows in rungs:
        if filled > rows:
            continue
        fwd, bwd = afmoe._rung(rows, gated)
        ops = (m, order, inverse, sizes, weights, *mats)
        outs[rows] = jax.device_get((fwd(*ops), *bwd(ops, g)))
    base = outs[rungs[-1]]
    report = {"device": jax.devices()[0].device_kind, "shape": [n, k, d, width, held, total, gated],
              "rungs": list(rungs), "filled": filled, "against_top": {}}
    for rows, o in outs.items():
        if rows == rungs[-1]:
            continue
        per = {}
        for name, a, b in zip(names, o, base):
            a32, b32 = np.asarray(a, np.float32), np.asarray(b, np.float32)
            per[name] = {"differing": int((a32 != b32).sum()), "of": int(a32.size),
                         "max_abs_diff": float(np.abs(a32 - b32).max()),
                         "max_abs": float(np.abs(b32).max())}
        report["against_top"][str(rows)] = per
    print(json.dumps(report))


if __name__ == "__main__":
    args = [int(a) for a in sys.argv[1:]]
    main(*args[:6], gated=bool(args[6]) if len(args) > 6 else True)
