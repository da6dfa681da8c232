"""The flash kernels alone on the chip, at the shapes the benchmark's cells
call them with, at several sub-tile edges (``ops/flash_attention._TILE``).

    chiprun -- python scripts/flash_tiles_chip.py [TILE ...]

An edge of 1024 is one tile a block: no pair runs in sub-tiles, which is what
the kernels did before PR 31. For each shape and edge: the median
milliseconds a call of the forward and of forward + backward (eight calls
chained in one program, so that no dispatch gap is timed), the largest
difference of the output and of the three gradients from the 1024 edge
(relative to its largest entry), ``live_tile_share``, ``fetched_pair_share``
and ``unmasked_pair_share`` (a tree from before PR 33 has the first alone),
and at the module's own edge a digest of the forward's output and logsumexp
bytes: two trees whose digests agree give the same forward to the last bit.
Then the host seconds to trace and lower GPT-2's 24 attention calls with
their gradient at each edge (what every process pays in ``setup_s``). One
JSON line a reading. Exits 1 if a difference passes 2e-2 (bfloat16 outputs)
or there is no TPU.
"""

from __future__ import annotations

import hashlib
import importlib
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CHAIN = 8   # calls timed as one program, each fed the one before's output

# name: batch * heads, T, window, query heads a key/value head, key width,
# value width
SHAPES = {
    "gpt2 causal T1024": (128, 1024, None, 1, 128, 128),
    "trinity window 2048 T8192": (32, 8192, 2048, 8, 128, 128),
    "trinity full T8192": (32, 8192, None, 8, 128, 128),
    "phi4flash window 512 T8192": (40, 8192, 512, 2, 128, 128),
    "phi4flash full T8192": (40, 8192, None, 2, 128, 128),
    "joyai latent T8192": (32, 8192, None, 1, 192, 128),
}


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    fa = importlib.import_module("split_learning_tpu.ops.flash_attention")
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    tiles = [1024] + [int(a) for a in sys.argv[1:] or ("512", "256")]
    shipped = fa._TILE
    worst = 0.0

    def build(tile, bh, t, window, group, d=128, d_v=128, **kw):
        fa._TILE = tile
        fa._make_flash.cache_clear()
        widths = {} if d_v == d else {"d_v": d_v}
        return fa._make_flash(bh, t, d, True, "bfloat16", 1024, onepass=True,
                              window=window, group=group, **widths, **kw)

    def chained(fn, into_v=False):
        """CHAIN calls in one program, so that no dispatch gap is timed,
        each fed the one before's output: as its queries, or as its values
        where those are of another width (``into_v``)."""
        def chain(q, k, v):
            for _ in range(CHAIN):
                if into_v:
                    v = fn(q, k, v)
                else:
                    q = fn(q, k, v)
            return v if into_v else q
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

    def digest(x):
        return hashlib.sha256(np.asarray(x).tobytes()).hexdigest()[:16]

    for name, (bh, t, window, group, d, d_v) in SHAPES.items():
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        args = (jax.random.normal(ks[0], (bh, t, d), jnp.bfloat16),
                jax.random.normal(ks[1], (bh // group, t, d), jnp.bfloat16),
                jax.random.normal(ks[2], (bh // group, t, d_v), jnp.bfloat16))
        base = None
        for tile in tiles:
            fn = build(tile, bh, t, window, group, d, d_v)
            got = (jax.jit(fn)(*args),) + with_grad(fn)(*args)
            got = [x.astype(jnp.float32) for x in got]
            base = base or got
            errs = [float(jnp.abs(g - b).max() / jnp.abs(b).max())
                    for g, b in zip(got, base)]
            worst = max(worst, *errs)
            line = {"shape": name, "tile": tile}
            shares = {"live_tile_share": {"tile": tile},
                      "fetched_pair_share": {}, "unmasked_pair_share": {}}
            line.update({share: getattr(fa, share)(t, 1024, True, window, **kw)
                         for share, kw in shares.items() if hasattr(fa, share)})
            chain = chained(fn, into_v=d_v != d)
            line.update(
                fwd_ms=median_ms(jax.jit(chain), args) / CHAIN,
                fwd_bwd_ms=median_ms(with_grad(chain), args) / CHAIN,
                err_o_dq_dk_dv=errs)
            if tile == shipped:
                o, lse = jax.jit(build(tile, bh, t, window, group, d, d_v,
                                       with_lse=True))(*args)
                line.update(o_sha256=digest(o), lse_sha256=digest(lse))
            print(json.dumps(line), flush=True)

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
