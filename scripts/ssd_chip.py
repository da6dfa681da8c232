"""The Mamba-2 recurrence's kernels on the chip, alone: against the plain
form and the recurrence itself, then their time at the benchmark's sizes
beside the plain form's and beside the other way to carry the state.

    chiprun -- python scripts/ssd_chip.py

One JSON line. ``errors``: at ``[2, 1000, 16, 64]`` in bfloat16 (2 groups,
state 128, chunks of 128: a padded last chunk), for the output and each of
the six gradients, the distance (norm over norm) of the kernels and of the
plain form from ``ssd_reference`` and from one another. ``ms``: at ``[1,
8192, 64, 64]`` (8 groups, state 128, chunks of 128; one layer of
``nemotronh-moe-fused-t8192``), the median host-clock milliseconds of a
call among ten sent back to back, and under a profiler session the device's
busy milliseconds a call with its five longest operations, for

- ``kernels_fwd`` / ``kernels_fwd_bwd``: ``ssd_chunked`` as it runs (the
  state carried in VMEM over the grid's chunk axis);
- ``plain_fwd`` / ``plain_fwd_bwd``: the plain form at the same sizes;
- ``scan_between_fwd``: the other form of the forward, built here from the
  same bodies: a kernel that writes every chunk's added state in float32, a
  ``lax.scan`` over the chunks, a kernel that reads the states it leaves.

Exits 1 where the kernels lie further from the recurrence than twice the
plain form's distance (and more than 1e-3), or there is no TPU.
"""

from __future__ import annotations

import functools
import json
import os
import statistics
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

CHUNK = 128


def operands(jax, jnp, batch, t, heads, head_dim, groups, state, seed):
    """A layer's operands as the model hands them over: ``dt`` after its
    softplus around 0.1, ``-a`` in ``[1, 16]``."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    bf = jnp.bfloat16
    return (jax.random.normal(ks[0], (batch, t, heads, head_dim)).astype(bf),
            jax.nn.softplus(jax.random.normal(ks[1], (batch, t, heads)) - 2.0),
            -jnp.exp(jax.random.uniform(ks[2], (heads,), maxval=2.77)),
            (0.3 * jax.random.normal(ks[3], (batch, t, groups, state))).astype(bf),
            (0.3 * jax.random.normal(ks[4], (batch, t, groups, state))).astype(bf),
            jax.random.normal(ks[5], (heads,)))


def scan_between(jax, jnp, ssd):
    """``ssd_chunked``'s forward with the state carried by XLA between two
    kernels (whole chunks, shapes that fill the tiles)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def added_kernel(p, x_ref, dtc_ref, lc_ref, b_ref, c_ref, added_ref):
        added_ref[0, 0, 0] = ssd._added(
            ssd._chunk_parts(x_ref, dtc_ref, lc_ref, b_ref, c_ref, p))

    def y_kernel(p, x_ref, dtc_ref, lc_ref, lr_ref, b_ref, c_ref, skip_ref,
                 before_ref, y_ref):
        y_ref[0] = ssd._chunk_y(
            p, ssd._chunk_parts(x_ref, dtc_ref, lc_ref, b_ref, c_ref, p),
            lc_ref[0, 0, 0], lr_ref[0, 0, 0], before_ref[0, 0, 0], skip_ref[0])

    def run(x, dt, a, b, c, d_skip, chunk):
        bsz, t, h, p = x.shape
        g, n = b.shape[2:]
        r, chunks, rp = h // g, t // chunk, h // g * p
        f32 = jnp.float32
        fold = lambda v, *rest: v.reshape(bsz, chunks, chunk, *rest)
        x2, dtc, lc, lr, b2, c2, skip_w = ssd._kernel_operands(
            fold(x, g, r, p), fold(dt.astype(f32), g, r),
            a.astype(f32).reshape(g, r), fold(b, g, n), fold(c, g, n),
            d_skip.astype(f32).reshape(g, r))
        seq = lambda width: pl.BlockSpec(
            (1, chunk, width), lambda z, i, k: (z, k, i))
        five = lambda *block: pl.BlockSpec(
            (1, 1, 1, *block), lambda z, i, k: (z, i, k, 0, 0))
        params = pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3,
            vmem_limit_bytes=ssd._VMEM_LIMIT)
        added = pl.pallas_call(
            functools.partial(added_kernel, p),
            out_shape=jax.ShapeDtypeStruct((bsz, g, chunks, n, rp), f32),
            grid=(bsz, g, chunks),
            in_specs=[seq(rp), five(chunk, r), five(chunk, r), seq(n), seq(n)],
            out_specs=five(n, rp), compiler_params=params,
            interpret=ssd.use_interpret(), name="ssd_added",
        )(x2, dtc, lc, b2, c2)
        kept = jnp.repeat(jnp.exp(lc[:, :, :, -1]), p, axis=-1)

        def carry(state, chunk_):
            k, new = chunk_
            return state * k[:, :, None] + new, state

        _, before = jax.lax.scan(
            carry, jnp.zeros_like(added[:, :, 0]),
            (jnp.moveaxis(kept, 2, 0), jnp.moveaxis(added, 2, 0)))
        before = jnp.moveaxis(before, 0, 2).astype(x.dtype)
        y = pl.pallas_call(
            functools.partial(y_kernel, p),
            out_shape=jax.ShapeDtypeStruct((bsz, t, h * p), f32),
            grid=(bsz, g, chunks),
            in_specs=[seq(rp), five(chunk, r), five(chunk, r), five(r, chunk),
                      seq(n), seq(n),
                      pl.BlockSpec((1, 1, rp), lambda z, i, k: (i, 0, 0)),
                      five(n, rp)],
            out_specs=seq(rp), compiler_params=params,
            interpret=ssd.use_interpret(), name="ssd_y",
        )(x2, dtc, lc, lr, b2, c2, skip_w, before)
        return y.reshape(x.shape)

    return run


def device_ms(jax, fn, args, calls: int = 5) -> dict:
    """The device's busy milliseconds a call of ``fn`` under a profiler
    session, and its five longest operations."""
    from jax.profiler import ProfileData

    import trace_reduce
    with tempfile.TemporaryDirectory() as where:
        with jax.profiler.trace(where):
            for _ in range(calls):
                out = fn(*args)
            jax.block_until_ready(out)
        data = ProfileData.from_file(trace_reduce.newest_xplane(where))
    busy, ops = [], {}
    for plane in data.planes:
        if not plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            if line.name != trace_reduce.OPS_LINE:
                continue
            for e in line.events:
                busy.append([int(e.start_ns), int(e.start_ns + e.duration_ns)])
                name = trace_reduce.short_name(e.name)
                ops[name] = ops.get(name, 0) + int(e.duration_ns)
    total = sum(hi - lo for lo, hi in trace_reduce.union(busy))
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:5]
    return {"busy": total * 1e-6 / calls, "events": len(busy) // calls,
            "top": [[name, ns * 1e-6 / calls] for name, ns in top]}


def host_ms(jax, fn, args, calls: int = 10) -> float:
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        times.append(1e3 * (time.perf_counter() - t0) / calls)
    return statistics.median(times)


def main() -> int:
    from unittest import mock

    import jax
    import jax.numpy as jnp
    from split_learning_tpu.ops import ssd
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    out = {"device": jax.devices()[0].device_kind, "errors": {}, "ms": {}}
    kernels = lambda *o: ssd.ssd_chunked(*o, CHUNK)

    def plain(*o):
        with mock.patch.object(ssd, "fills_tiles", lambda *sizes: False):
            return ssd.ssd_chunked(*o, CHUNK)

    args = operands(jax, jnp, 2, 1000, 16, 64, 2, 128, 0)
    w = jax.random.normal(jax.random.PRNGKey(7), args[0].shape)

    def both(fn):
        y, grads = jax.jit(jax.value_and_grad(
            lambda *a: (lambda y: (jnp.sum(y * w), y))(fn(*a)),
            argnums=tuple(range(6)), has_aux=True))(*args)
        return (y[1], *grads)

    got, flat, want = both(kernels), both(plain), both(ssd.ssd_reference)
    far = lambda u, v: float(
        jnp.linalg.norm((u - v).astype(jnp.float32).ravel())
        / jnp.linalg.norm(v.astype(jnp.float32).ravel()))
    bad = False
    for name, k, q, r in zip(("y", "x", "dt", "a", "b", "c", "d_skip"),
                             got, flat, want):
        e = {"kernels_to_recurrence": far(k, r), "plain_to_recurrence": far(q, r),
             "kernels_to_plain": far(k, q)}
        bad |= e["kernels_to_recurrence"] > max(2 * e["plain_to_recurrence"], 1e-3)
        out["errors"][name] = e

    args = operands(jax, jnp, 1, 8192, 64, 64, 8, 128, 1)
    w = jax.random.normal(jax.random.PRNGKey(8), args[0].shape)
    grad = lambda fn: jax.jit(jax.grad(
        lambda *a: jnp.sum(fn(*a) * w), argnums=tuple(range(6))))
    between = scan_between(jax, jnp, ssd)
    forms = {"kernels_fwd": jax.jit(kernels), "kernels_fwd_bwd": grad(kernels),
             "plain_fwd": jax.jit(plain), "plain_fwd_bwd": grad(plain),
             "scan_between_fwd": jax.jit(lambda *o: between(*o, CHUNK))}
    out["scan_between_to_kernels"] = far(forms["scan_between_fwd"](*args),
                                         forms["kernels_fwd"](*args))
    for name, fn in forms.items():
        out["ms"][name] = {"host": host_ms(jax, fn, args),
                           **device_ms(jax, fn, args)}
    print(json.dumps(out))
    return int(bad)


if __name__ == "__main__":
    sys.exit(main())
