"""State-space duality — the recurrence of a Mamba-2 layer in its chunked
form: four batched matrix products a chunk and one short scan over the
chunks' states, plain ``jax.numpy``, differentiated by ``jax`` itself.

For every sequence and head ``h`` of ``H`` (head ``h`` reads group ``h //
(H / G)`` of ``b`` and ``c``; ``a[h] < 0`` is one scalar a head, the state
``S`` is ``[P, N]`` a head)::

    S_t = exp(dt_t[h] * a[h]) * S_{t-1} + dt_t[h] * x_t[h] b_t^T,   S_{-1} = 0
    y_t[h] = S_t c_t + d_skip[h] * x_t[h]

:func:`ssd_reference` is that recurrence token by token (``lax.scan``):
what the tests hold the chunked form against, and what nothing calls on
the chip. ops/selective_scan.py computes Mamba-1's recurrence (a decay a
channel *and* state, a state of 16 a channel) on the vector unit at about
29 operations a channel, state and token; a state of ``128 x 4096`` a token
is 6.4 times PF's work there, with not one matrix product in it. One
scalar decay a head is what makes the products possible.

**The chunked form** (:func:`ssd_chunked`), over chunks of ``L`` tokens
with ``l_i = sum_{j <= i} dt_j a`` inside a chunk (so every ``exp`` below
has an argument ``<= 0``):

1. inside a chunk, ``Y_diag = ((C B^T) * exp(l_i - l_j)[i >= j]) (dt x)``:
   the scores ``C B^T`` once a group (``[L, N] x [N, L]``), the decays once
   a head, their product against the chunk's ``[L, P]`` inputs;
2. the state a chunk adds, ``sum_j exp(l_end - l_j) (dt x)_j b_j^T`` (``[P,
   L] x [L, N]`` a head);
3. the states carried from chunk to chunk by ``exp(l_end)``: a ``lax.scan``
   of ``T / L`` steps over ``[H, P, N]``, elementwise;
4. what the carried state gives a chunk's tokens, ``Y_off = exp(l_i) C_i
   S_prev`` (``[L, N] x [N, P]`` a head).

``dt``, ``a``, the cumulative sums, every ``exp`` and the carried states are
float32; the four products' operands are in ``x``'s type (bfloat16 on the
chip) and accumulate in float32. ``T`` pads to whole chunks with ``dt = 0``
(a token that leaves the state as it is and adds nothing). What the form
writes besides the products: the decays ``[T / L, H, L, L]`` in float32 and
their product with the scores in the compute type, a layer.
:func:`ssd_product_flops` counts the four products from the shapes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from split_learning_tpu.obs import spans
from split_learning_tpu.ops.common import pad_axis, round_up

_F32 = jnp.float32


def ssd_product_flops(t: int, heads: int, head_dim: int, groups: int,
                      state: int, chunk: int) -> int:
    """Operations of the chunked form's four products over ``t`` tokens,
    forward: the scores once a group, their product with the chunk's
    inputs, the chunk states and the carried state's part, once a head."""
    return 2 * t * (groups * chunk * state + heads * chunk * head_dim
                    + 2 * heads * head_dim * state)


def ssd_chunked(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                c: jax.Array, d_skip: jax.Array, chunk: int) -> jax.Array:
    """``x [B, T, H, P]``, ``dt [B, T, H]`` (after its softplus), ``a [H]``,
    ``b`` and ``c`` ``[B, T, G, N]``, ``d_skip [H]`` -> ``y [B, T, H, P]``
    in float32 (the module header)."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2:]
    if h % g:
        raise ValueError(f"{g} groups do not divide {h} heads")
    r, dtype = h // g, x.dtype
    chunks = round_up(t, chunk) // chunk
    # [B, chunks, L, ...], the heads by group
    fold = lambda v, *rest: pad_axis(v, 1, chunks * chunk).reshape(
        bsz, chunks, chunk, *rest)
    x, dt = fold(x, g, r, p), fold(dt.astype(_F32), g, r)
    b, c = fold(b, g, n), fold(c, g, n)
    with jax.named_scope(spans.SSM_SSD):
        # l [B, chunks, G, R, L]: the log of the decay since the chunk began
        l = jnp.cumsum(dt * a.astype(_F32).reshape(g, r), axis=2)
        l = l.transpose(0, 1, 3, 4, 2)
        dx = x.astype(_F32) * dt[..., None]
        # 1. inside a chunk
        scores = jnp.einsum("zclgn,zcsgn->zcgls", c, b,
                            preferred_element_type=_F32)
        since = l[..., :, None] - l[..., None, :]
        live = jnp.tril(jnp.ones((chunk, chunk), bool))
        decay = jnp.exp(jnp.where(live, since, -jnp.inf))
        y = jnp.einsum("zcgrls,zcsgrp->zclgrp",
                       (scores[:, :, :, None] * decay).astype(dtype),
                       dx.astype(dtype), preferred_element_type=_F32)
        # 2. the state each chunk adds, [B, chunks, G, R, P, N]
        to_end = jnp.exp(l[..., -1:] - l).transpose(0, 1, 4, 2, 3)
        added = jnp.einsum("zcsgn,zcsgrp->zcgrpn", b,
                           (dx * to_end[..., None]).astype(dtype),
                           preferred_element_type=_F32)

        # 3. the state each chunk starts from
        def carry(state, chunk_):
            kept, new = chunk_
            return state * kept[..., None, None] + new, state

        _, before = jax.lax.scan(
            carry, jnp.zeros_like(added[:, 0]),
            (jnp.exp(l[..., -1]).swapaxes(0, 1), added.swapaxes(0, 1)))
        # 4. what it gives the chunk's tokens
        y = y + jnp.einsum(
            "zclgn,zcgrpn->zclgrp", c, before.swapaxes(0, 1).astype(dtype),
            preferred_element_type=_F32
        ) * jnp.exp(l).transpose(0, 1, 4, 2, 3)[..., None]
        y = y + d_skip.astype(_F32).reshape(g, r, 1) * x.astype(_F32)
    return y.reshape(bsz, chunks * chunk, h, p)[:, :t]


def ssd_reference(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                  c: jax.Array, d_skip: jax.Array) -> jax.Array:
    """The recurrence itself, one token a step, float32 (the module
    header); the shapes are :func:`ssd_chunked`'s."""
    h, g = x.shape[2], b.shape[2]
    f32 = lambda v: v.astype(_F32)
    by_head = lambda v: jnp.repeat(f32(v), h // g, axis=2).swapaxes(0, 1)
    x, dt, a, d_skip = f32(x), f32(dt), f32(a), f32(d_skip)

    def step(state, token):
        x_t, dt_t, b_t, c_t = token      # [B, H, P], [B, H], [B, H, N] twice
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None])
        y_t = jnp.einsum("zhpn,zhn->zhp", state, c_t,
                         precision=jax.lax.Precision.HIGHEST)
        return state, y_t + d_skip[:, None] * x_t

    start = jnp.zeros(x.shape[:1] + x.shape[2:] + b.shape[3:], _F32)
    _, y = jax.lax.scan(step, start, (x.swapaxes(0, 1), dt.swapaxes(0, 1),
                                      by_head(b), by_head(c)))
    return y.swapaxes(0, 1)
