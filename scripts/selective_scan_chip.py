"""The selective-scan kernel on the chip: against the plain ``lax.scan``
form at a length that form can run, then its time at the benchmark's sizes.

    chiprun -- python scripts/selective_scan_chip.py

Prints one JSON line: the largest error of the output and of each of the
six gradients (relative to the plain form's largest entry), and the median
milliseconds of the forward and of forward + backward at ``[1, 8192,
5120]`` with 16 states. Exits 1 if an error passes 1e-4 or there is no TPU.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def operands(jax, jnp, batch, t, d_inner, d_state, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(ks[0], (batch, t, d_inner)),
            jax.nn.softplus(jax.random.normal(ks[1], (batch, t, d_inner))),
            -jnp.exp(0.5 * jax.random.normal(ks[2], (d_inner, d_state))),
            jax.random.normal(ks[3], (batch, t, d_state)),
            jax.random.normal(ks[4], (batch, t, d_state)),
            jax.random.normal(ks[5], (d_inner,)))


def main() -> int:
    import jax
    import jax.numpy as jnp
    from split_learning_tpu.ops.selective_scan import (
        selective_scan, selective_scan_reference)
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    out = {"device": jax.devices()[0].device_kind, "errors": {}}
    args = operands(jax, jnp, 2, 1000, 5120, 16, 0)
    w = jax.random.normal(jax.random.PRNGKey(7), args[0].shape)

    def both(fn):
        return jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(fn(*a) * w), argnums=tuple(range(6))))(*args)

    (_, got), (_, want) = both(selective_scan), both(selective_scan_reference)
    y, y_ref = (jax.jit(f)(*args) for f in (selective_scan,
                                            selective_scan_reference))
    rel = lambda u, v: float(jnp.abs(u - v).max() / jnp.abs(v).max())
    out["errors"]["y"] = rel(y, y_ref)
    for name, u, v in zip(("x", "delta", "a", "b", "c", "d_skip"), got, want):
        out["errors"][name] = rel(u, v)

    args = operands(jax, jnp, 1, 8192, 5120, 16, 1)
    fwd = jax.jit(selective_scan)
    grad = jax.jit(jax.grad(lambda *a: jnp.sum(selective_scan(*a)),
                            argnums=tuple(range(6))))
    for name, fn in (("fwd_ms", fwd), ("fwd_bwd_ms", grad)):
        jax.block_until_ready(fn(*args))
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            times.append(1e3 * (time.perf_counter() - t0))
        out[name] = statistics.median(times)
    print(json.dumps(out))
    return 0 if max(out["errors"].values()) < 1e-4 else 1


if __name__ == "__main__":
    sys.exit(main())
