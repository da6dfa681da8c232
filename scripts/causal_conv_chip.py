"""The Mamba layers' convolution-and-silu kernels (ops/causal_conv.py) on the
chip, alone, beside the plain form: one layer of each cell that runs them.

    chiprun -- python scripts/causal_conv_chip.py
        [--strips 32,16 --tokens 512,256 --channels 512,128]

One JSON line. For ``nemotronh`` (``x`` bfloat16 ``[1, 8192, 6144]``, four
taps, the result bfloat16) and ``phi4flash`` (``[1, 8192, 5120]``, the
result float32):

- ``forward_equal``: whether the kernels' ``y`` is the plain form's to the
  last bit, and ``forward_unequal``: how many elements are not;
- ``gradients``: the greatest difference of ``dx``, ``dtaps`` and ``dbias``
  from the plain form's over the plain form's greatest entry;
- ``ms``: for ``kernels_fwd``, ``kernels_bwd`` (the backward kernel and the
  sums behind it), ``plain_fwd`` and ``plain_fwd_bwd`` (``jax``'s own
  backward cannot run without its forward) the median host-clock
  milliseconds of a call among ten sent back to back, and under a profiler
  session the device's busy milliseconds a call with its five longest
  operations;
- ``least_ms`` and ``roofline_pct`` of the two kernels: the bytes they have
  to move (forward ``x`` and ``y`` once, backward ``dy``, ``x`` and ``dx``
  once, each in its stored type) over benchmarks/peaks.json's bytes a
  second, and that over the kernel's own device time.

With ``--strips``, ``--tokens`` or ``--channels`` also ``tilings``: the two
kernels' device milliseconds under every tiling named (the module's
constants patched; what PERF.md's Findings, PR 48, chose from; a tiling that
Mosaic refuses reads ``error``).

Exits 1 where a gradient lies further than 1e-2 (``dx`` in bfloat16: one
rounding of 2^-8) or 1e-4 (the float32 sums) from the plain form's, or
there is no TPU.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
sys.path.insert(0, os.path.join(ROOT, "scripts"))

T, TAPS = 8192, 4
LAYERS = {"nemotronh": (6144, "bfloat16"), "phi4flash": (5120, "float32")}


def operands(jax, jnp, channels: int, y_dtype, seed: int):
    """A layer's operands as the model hands them over: the first product's
    output in bfloat16, taps around the published initialiser's +-1/2, and
    a cotangent in the result's type."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (1, T, channels)).astype(jnp.bfloat16),
            jax.random.uniform(ks[1], (TAPS, channels), minval=-0.5, maxval=0.5),
            0.1 * jax.random.normal(ks[2], (channels,)),
            jax.random.normal(ks[3], (1, T, channels)).astype(y_dtype))


def forms(jax, conv, y_dtype) -> dict:
    """The four timed calls, each ``(x, taps, bias, dy) ->`` arrays."""
    kernels = lambda x, taps, bias: conv.conv_silu(x, taps, bias, y_dtype)

    def plain(x, taps, bias):
        with mock.patch.object(conv, "fills_tiles", lambda *a: False):
            return conv.conv_silu(x, taps, bias, y_dtype)

    def backward(fn):
        return lambda x, taps, bias, dy: jax.vjp(fn, x, taps, bias)[1](dy)

    return {"kernels_fwd": jax.jit(lambda x, taps, bias, dy: kernels(x, taps, bias)),
            "kernels_bwd": jax.jit(backward(kernels)),
            "plain_fwd": jax.jit(lambda x, taps, bias, dy: plain(x, taps, bias)),
            "plain_fwd_bwd": jax.jit(backward(plain))}


def kernel_ms(reading: dict, name: str) -> float:
    return sum(ms for op, ms in reading["top"] if name in op)


def main() -> int:
    import jax
    import jax.numpy as jnp
    from split_learning_tpu.ops import causal_conv as conv
    from ssd_chip import device_ms, host_ms
    parser = argparse.ArgumentParser()
    for name in ("strips", "tokens", "channels"):
        parser.add_argument(f"--{name}", default="")
    args = parser.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    with open(os.path.join(ROOT, "benchmarks", "peaks.json")) as f:
        peak = json.load(f)[jax.devices()[0].device_kind]["hbm_bytes_per_s"]
    out = {"device": jax.devices()[0].device_kind}
    bad = False
    for seed, (layer, (channels, y_name)) in enumerate(LAYERS.items()):
        y_dtype = jnp.dtype(y_name)
        ops = operands(jax, jnp, channels, y_dtype, seed)
        fns = forms(jax, conv, y_dtype)
        y, want = fns["kernels_fwd"](*ops), fns["plain_fwd"](*ops)
        unequal = int(jnp.sum(y != want))
        gradients = {}
        for name, got, ref in zip(("dx", "dtaps", "dbias"),
                                  fns["kernels_bwd"](*ops),
                                  fns["plain_fwd_bwd"](*ops)):
            got, ref = got.astype(jnp.float32), ref.astype(jnp.float32)
            gradients[name] = float(jnp.abs(got - ref).max() / jnp.abs(ref).max())
            bad |= gradients[name] > (1e-2 if name == "dx" else 1e-4)
        ms = {name: {"host": host_ms(jax, fn, ops), **device_ms(jax, fn, ops)}
              for name, fn in fns.items()}
        size = T * channels
        least = {"conv_silu_fwd": size * (2 + y_dtype.itemsize) / peak * 1e3,
                 "conv_silu_bwd": size * (4 + y_dtype.itemsize) / peak * 1e3}
        took = {"conv_silu_fwd": kernel_ms(ms["kernels_fwd"], "conv_silu_fwd"),
                "conv_silu_bwd": kernel_ms(ms["kernels_bwd"], "conv_silu_bwd")}
        out[layer] = {
            "forward_equal": unequal == 0, "forward_unequal": unequal,
            "gradients": gradients, "ms": ms, "least_ms": least,
            "roofline_pct": {k: 100.0 * least[k] / took[k] if took[k] else None
                             for k in least}}
        tilings = [dict(zip(("_STRIP", "TOKENS", "_CHANNELS"), sizes))
                   for sizes in itertools.product(*(
                       [int(v) for v in given.split(",")] if given else [now]
                       for given, now in ((args.strips, conv._STRIP),
                                          (args.tokens, conv.TOKENS),
                                          (args.channels, conv._CHANNELS))))]
        for tiling in tilings if len(tilings) > 1 else []:
            with mock.patch.multiple(conv, **tiling):
                conv._make_conv_silu.cache_clear()
                fns = forms(jax, conv, y_dtype)
                try:
                    took = {kernel: kernel_ms(device_ms(jax, fns[form], ops),
                                              kernel)
                            for form, kernel in (
                                ("kernels_fwd", "conv_silu_fwd"),
                                ("kernels_bwd", "conv_silu_bwd"))}
                except Exception as e:      # a tiling that Mosaic refuses
                    took = {"error": str(e)[:200]}
                out[layer].setdefault("tilings", []).append(
                    {**tiling, **took})
            conv._make_conv_silu.cache_clear()
    print(json.dumps(out))
    return int(bad)


if __name__ == "__main__":
    sys.exit(main())
