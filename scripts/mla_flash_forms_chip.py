"""Latent attention's flash calls alone on the chip: which widths to run.

    chiprun -- python scripts/mla_flash_forms_chip.py

A latent-attention head has queries and keys of 192 and values of 128
(JoyAI-LLM-Flash, 32 heads, T 8192, causal). Two parts, one JSON line a
reading, milliseconds a call over eight calls chained in one program:

1. The kernels that ship (``ops/flash_attention.py``), forward and forward +
   backward, at four pairs of widths: **192 / 128** (keys padded to 256
   lanes, values at 128: what the model calls), **256 / 256** (every operand
   padded to 256, which is what a kernel with one head width would run),
   **128 / 128** (the time of one 128-lane product each, a floor) and
   **256 / 128** (the same lanes as 192 / 128 without the padding step).
2. The two forms the scores can take, in one plain forward kernel (whole
   blocks of 1024, online softmax, no sub-tiles), so that nothing but the
   form differs: **(a)** one QK^T over 256 lanes, ``[k_n | k_r | 0]`` a head;
   **(b)** two products, ``q_n k_n^T`` at 128 and ``q_r k_r^T`` at 128 (64
   padded), the one ``k_r`` block read from a ``[1, T, 128]`` array that all
   32 heads share. Their outputs are compared.

Exits 1 without a TPU or if (a) and (b) differ by more than 2e-2.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CHAIN, HEADS, T, BLOCK = 8, 32, 8192, 1024
NEG = -1e30


def plain_forward(form: str):
    """Causal flash forward over whole 1024-blocks; ``form`` "a" takes
    (q [H, T, 256], k [H, T, 256], v [H, T, 128]), "b" takes (q_n, q_r
    [H, T, 128], k_n [H, T, 128], k_r [1, T, 128], v)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    scale = 192 ** -0.5
    n = T // BLOCK

    def kernel(*refs):
        *ins, o_ref, acc, m_ref, l_ref = refs
        i, j = pl.program_id(1), pl.program_id(2)

        @pl.when(j == 0)
        def _():
            acc[:] = jnp.zeros_like(acc)
            m_ref[:] = jnp.full_like(m_ref, NEG)
            l_ref[:] = jnp.zeros_like(l_ref)

        @pl.when(j <= i)
        def _():
            dot = lambda a, b: jax.lax.dot_general(
                a, b, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            if form == "a":
                q, k, v = ins
                s = dot(q[0], k[0])
            else:
                qn, qr, kn, kr, v = ins
                s = dot(qn[0], kn[0]) + dot(qr[0], kr[0])
            s = s * scale
            rows = i * BLOCK + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = j * BLOCK + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            ok = rows >= cols
            s = jnp.where(ok, s, NEG)
            m = m_ref[:, 0]
            m_new = jnp.maximum(m, jnp.max(s, axis=1))
            p = jnp.where(ok, jnp.exp(s - m_new[:, None]), 0.0)
            corr = jnp.exp(m - m_new)
            l_ref[:] = l_ref[:] * corr[:, None] + jnp.sum(p, axis=1)[:, None]
            acc[:] = acc[:] * corr[:, None] + jax.lax.dot_general(
                p.astype(v.dtype), v[0], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[:] = jnp.broadcast_to(m_new[:, None], m_ref.shape)

        @pl.when(j == n - 1)
        def _():
            o_ref[0] = (acc[:] / l_ref[:, :1]).astype(o_ref.dtype)

    spec = lambda lanes, idx: pl.BlockSpec((1, BLOCK, lanes), idx,
                                           memory_space=pltpu.VMEM)
    rows = lambda b, i, j: (b, i, 0)
    keys = lambda b, i, j: (b, jnp.minimum(j, i), 0)    # no fetch past the diagonal
    shared = lambda b, i, j: (0, jnp.minimum(j, i), 0)
    if form == "a":
        in_specs = [spec(256, rows), spec(256, keys), spec(128, keys)]
    else:
        in_specs = [spec(128, rows), spec(128, rows), spec(128, keys),
                    spec(128, shared), spec(128, keys)]
    return pl.pallas_call(
        kernel, grid=(HEADS, n, n), in_specs=in_specs,
        out_specs=spec(128, rows),
        out_shape=jax.ShapeDtypeStruct((HEADS, T, 128), jnp.bfloat16),
        scratch_shapes=[pltpu.VMEM((BLOCK, 128), jnp.float32),
                        pltpu.VMEM((BLOCK, 128), jnp.float32),
                        pltpu.VMEM((BLOCK, 128), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=96 * 1024 * 1024))


def main() -> int:
    import jax
    import jax.numpy as jnp
    from split_learning_tpu.ops.flash_attention import flash_attention
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1

    def median_ms(fn, args, n=10):
        jax.block_until_ready(fn(*args))
        times = []
        for _ in range(n):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            times.append(1e3 * (time.perf_counter() - t0))
        return statistics.median(times) / CHAIN

    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    normal = lambda k, *shape: jax.random.normal(k, shape, jnp.bfloat16)

    # -- 1: the kernels that ship, by widths ------------------------------ #
    def chained(q, k, v):   # the output is a value's shape: feed it back
        for _ in range(CHAIN):
            v = flash_attention(q, k, v, causal=True)
        return v

    grad = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
        chained(q, k, v).astype(jnp.float32) ** 2), argnums=(0, 1, 2)))
    for d, d_v in ((192, 128), (256, 256), (128, 128), (256, 128)):
        args = (normal(ks[0], 1, T, HEADS, d), normal(ks[1], 1, T, HEADS, d),
                normal(ks[2], 1, T, HEADS, d_v))
        print(json.dumps({
            "kernels": "ops/flash_attention.py", "qk_width": d, "v_width": d_v,
            "fwd_ms": median_ms(jax.jit(chained), args),
            "fwd_bwd_ms": median_ms(grad, args)}), flush=True)

    # -- 2: the two forms of the scores, one plain forward kernel --------- #
    q_n, k_n, v = (normal(k, HEADS, T, 128) for k in ks[:3])
    pad = lambda x: jnp.pad(x, ((0, 0), (0, 0), (0, 64)))
    q_r, k_r = pad(normal(ks[3], HEADS, T, 64)), pad(normal(ks[4], 1, T, 64))
    a_args = (jnp.concatenate([q_n, q_r[..., :64], jnp.zeros_like(q_r[..., :64])], -1),
              jnp.concatenate([k_n, jnp.broadcast_to(k_r, q_r.shape)], -1), v)
    b_args = (q_n, q_r, k_n, k_r, v)
    outs = {}
    for form, args in (("a", a_args), ("b", b_args)):
        call = plain_forward(form)

        def chain(*args, call=call):
            *rest, v = args
            for _ in range(CHAIN):
                v = call(*rest, v)
            return v

        outs[form] = jax.jit(call)(*args).astype(jnp.float32)
        print(json.dumps({"kernel": "plain forward, whole blocks", "form": form,
                          "fwd_ms": median_ms(jax.jit(chain), args)}), flush=True)
    # the shipped forward on the same numbers
    fold = lambda x: jnp.transpose(x, (1, 0, 2))[None]
    shipped = flash_attention(fold(a_args[0][..., :192]), fold(a_args[1][..., :192]),
                              fold(v), causal=True)[0].transpose(1, 0, 2)
    top = float(jnp.abs(outs["a"]).max())
    gaps = {"a_minus_b": float(jnp.abs(outs["a"] - outs["b"]).max()) / top,
            "a_minus_shipped": float(jnp.abs(
                outs["a"] - shipped.astype(jnp.float32)).max()) / top}
    print(json.dumps(gaps), flush=True)
    return 0 if max(gaps.values()) < 2e-2 else 1


if __name__ == "__main__":
    sys.exit(main())
