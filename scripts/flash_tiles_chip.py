"""The flash kernels alone on the chip, at the shapes the benchmark's cells
call them with, at several sub-tile edges (``ops/flash_attention._TILE``).

    chiprun -- python scripts/flash_tiles_chip.py [TILE ...]

An edge of 1024 is one tile a block: the whole-pair body on every pair, which
is what the kernels did before PR 31. For each shape and edge: the median
milliseconds a call of the forward and of forward + backward (eight calls
chained in one program, so that no dispatch gap is timed), the largest
difference of the output and of the three gradients from the 1024 edge
(relative to its largest entry), and ``live_tile_share``. Then the host
seconds to trace and lower GPT-2's 24 attention calls with their gradient
at each edge (what every process pays in ``setup_s``). One JSON line a
reading. Exits 1 if a difference passes 2e-2 (bfloat16 outputs) or there
is no TPU.
"""

from __future__ import annotations

import importlib
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CHAIN = 8   # calls timed as one program, each fed the one before's output

# name: batch * heads, T, window, query heads a key/value head
SHAPES = {
    "gpt2 causal T1024": (128, 1024, None, 1),
    "trinity window 2048 T8192": (32, 8192, 2048, 8),
    "trinity full T8192": (32, 8192, None, 8),
    "phi4flash window 512 T8192": (40, 8192, 512, 2),
    "phi4flash full T8192": (40, 8192, None, 2),
}


def main() -> int:
    import jax
    import jax.numpy as jnp
    fa = importlib.import_module("split_learning_tpu.ops.flash_attention")
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    tiles = [1024] + [int(a) for a in sys.argv[1:] or ("512", "256")]
    worst = 0.0

    def build(tile, bh, t, window, group):
        fa._TILE = tile
        fa._make_flash.cache_clear()
        return fa._make_flash(bh, t, 128, True, "bfloat16", 1024,
                              onepass=True, window=window, group=group)

    def chained(fn):   # CHAIN calls in one program: no dispatch gap is timed
        def chain(q, k, v):
            for _ in range(CHAIN):
                q = fn(q, k, v)
            return q
        return chain

    def with_grad(fn):
        return jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2)))

    def median_ms(fn, args, n=10):
        jax.block_until_ready(fn(*args))
        times = []
        for _ in range(n):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            times.append(1e3 * (time.perf_counter() - t0))
        return statistics.median(times)

    for name, (bh, t, window, group) in SHAPES.items():
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        args = (jax.random.normal(ks[0], (bh, t, 128), jnp.bfloat16),
                jax.random.normal(ks[1], (bh // group, t, 128), jnp.bfloat16),
                jax.random.normal(ks[2], (bh // group, t, 128), jnp.bfloat16))
        base = None
        for tile in tiles:
            fn = build(tile, bh, t, window, group)
            got = (jax.jit(fn)(*args),) + with_grad(fn)(*args)
            got = [x.astype(jnp.float32) for x in got]
            base = base or got
            errs = [float(jnp.abs(g - b).max() / jnp.abs(b).max())
                    for g, b in zip(got, base)]
            worst = max(worst, *errs)
            print(json.dumps({
                "shape": name, "tile": tile,
                "live_tile_share": fa.live_tile_share(
                    t, 1024, True, window, tile=tile),
                "fwd_ms": median_ms(jax.jit(chained(fn)), args) / CHAIN,
                "fwd_bwd_ms": median_ms(with_grad(chained(fn)), args) / CHAIN,
                "err_o_dq_dk_dv": errs}), flush=True)

    q = jax.ShapeDtypeStruct((128, 1024, 128), jnp.bfloat16)
    for tile in tiles:
        fn = build(tile, 128, 1024, None, 1)

        def stack(q, k, v):
            for _ in range(24):
                q = fn(q, k, v)
            return jnp.sum(q.astype(jnp.float32))

        seconds = []
        for _ in range(3):
            t0 = time.perf_counter()
            jax.jit(jax.grad(stack, argnums=(0, 1, 2))).lower(q, q, q)
            seconds.append(time.perf_counter() - t0)
        print(json.dumps({"trace_and_lower_24_calls_s": min(seconds),
                          "tile": tile}), flush=True)
    return 0 if worst < 2e-2 else 1


if __name__ == "__main__":
    sys.exit(main())
