"""Sequence/context-parallel attention — ring and Ulysses forms.

The reference has no attention and no sequence axis at all (SURVEY.md §5
"Long-context / sequence parallelism: absent — definitively"); this module
is the framework's long-context extension beyond reference capability, so
the split-transformer family (models/transformer.py) can train on
sequences longer than one chip's HBM allows.

Both forms shard the sequence axis of ``[B, T, H, D]`` activations over a
``seq`` mesh axis and exchange only what the math requires over ICI:

- **Ring attention** (:func:`ring_attention`): each rank keeps its query
  block resident and the K/V blocks rotate around the ring via
  ``lax.ppermute``, one neighbor hop per step — the flash-attention
  online-softmax recurrence (running max ``m``, denominator ``l``,
  unnormalized accumulator ``o``) makes the partial results exact, so the
  full ``T x T`` score matrix never materializes on any chip and per-chip
  attention memory is O(T_local^2). Communication is nearest-neighbor
  only, which is exactly what the TPU torus is built for.
- **Ulysses attention** (:func:`ulysses_attention`): two
  ``lax.all_to_all`` transposes swap the sharded axis — in: sequence
  shards -> head shards, run dense per-head attention on the full
  sequence, out: heads -> sequence. Fewer, larger collectives; requires
  ``H % seq_shards == 0``.

Everything is pure ``jnp`` inside ``shard_map``, so ``jax.grad``
differentiates straight through (the cotangent of a ``ppermute`` is the
inverse ``ppermute``; of an ``all_to_all``, the reverse ``all_to_all``)
and the same code runs on the 8-virtual-device CPU test mesh
(tests/test_ring_attention.py asserts fwd+grad equivalence vs
:func:`full_attention`).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from split_learning_tpu.ops.common import NEG_BIG as _NEG_BIG
from split_learning_tpu.parallel.mesh import DATA_AXIS, SEQ_AXIS


def full_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   causal: bool = False,
                   window: Optional[int] = None) -> jax.Array:
    """Plain dense softmax attention, ``[B, T, H, D] -> [B, T, H, D]``.

    The single-device reference semantics both parallel forms must
    reproduce; also the path the transformer uses with no ``seq`` mesh
    axis. ``k``/``v`` may hold fewer heads (``[B, T, H_kv, D]``): query
    head ``n`` reads key/value head ``n // (H // H_kv)``. ``window``
    (with ``causal``) keeps keys ``0 <= i - j < window``: the dense
    banded path that serves the CPU and that the windowed flash
    kernels are tested against.
    """
    if window is not None and not causal:
        raise ValueError("window is a causal band: it needs causal=True")
    h, h_kv = q.shape[2], k.shape[2]
    if h != h_kv:
        k = jnp.repeat(k, h // h_kv, axis=2)
        v = jnp.repeat(v, h // h_kv, axis=2)
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        behind = jnp.arange(tq)[:, None] - jnp.arange(tk)[None, :]
        mask = behind >= 0
        if window is not None:
            mask &= behind < window
        s = jnp.where(mask[None, None], s, _NEG_BIG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _ring_attention_local(q: jax.Array, k: jax.Array, v: jax.Array,
                          axis_name: str, causal: bool,
                          striped: bool = False) -> jax.Array:
    """Per-rank body (inside shard_map): q stays, k/v rotate n times.

    ``striped``: the caller laid tokens out round-robin (global position
    of local row j on rank r is ``j*n + r`` instead of ``r*t_local + j``
    — :func:`stripe_permutation`); only the position formulas change,
    the online-softmax recurrence is identical."""
    n = lax.psum(1, axis_name)          # ring size (static under shard_map)
    rank = lax.axis_index(axis_name)
    b, t_local, h, d = q.shape
    scale = d ** -0.5

    def positions(r):
        idx = jnp.arange(t_local)
        return idx * n + r if striped else r * t_local + idx

    q_pos = positions(rank)

    # accumulators in [B, H, Tq] / [B, H, Tq, D] layout so the softmax
    # reductions run over the trailing (lane) dim
    o0 = jnp.zeros((b, h, t_local, d), jnp.float32)
    l0 = jnp.zeros((b, h, t_local), jnp.float32)
    m0 = jnp.full((b, h, t_local), _NEG_BIG, jnp.float32)
    perm = [(j, (j + 1) % n) for j in range(n)]

    def accumulate(o, l, m, kb, vb, i):
        # after i forward rotations this rank holds the block that
        # started on rank - i (mod n)
        src = (rank - i) % n
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kb,
                       preferred_element_type=jnp.float32) * scale
        k_pos = positions(src)
        mask = None
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]       # [Tq, Tk]
            s = jnp.where(mask[None, None], s, _NEG_BIG)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # rebase then zero fully-masked entries: exp(_NEG_BIG - _NEG_BIG)
        # would be 1, so masking must be re-applied after the exp
        p = jnp.exp(s - m_new[..., None])
        if causal:
            p = jnp.where(mask[None, None], p, 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        o = o * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, vb,
            preferred_element_type=jnp.float32)
        return o, l, m_new

    def step(carry, i):
        o, l, m, kb, vb = carry
        o, l, m = accumulate(o, l, m, kb, vb, i)
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        return (o, l, m, kb, vb), None

    # n-1 (compute, rotate) steps, then the last block needs no rotation
    # — n-1 ppermute hops total, and a 1-rank ring never communicates
    (o, l, m, kb, vb), _ = lax.scan(
        step, (o0, l0, m0, k, v), jnp.arange(n - 1))
    o, l, _ = accumulate(o, l, m, kb, vb, n - 1)
    out = o / l[..., None]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)  # [B,Tq,H,D]


def _ring_flash_local(q: jax.Array, k: jax.Array, v: jax.Array,
                      axis_name: str, causal: bool,
                      striped: bool = False) -> jax.Array:
    """Per-rank body with the Pallas flash kernel as the block compute:
    q stays resident, K/V rotate, and each (q block, K/V block) pair
    runs :func:`flash_attention_with_lse` — so nothing O(T_local^2)
    ever materializes on any rank and the multi-chip path inherits the
    single-chip flash memory ceiling (per-rank attention memory is
    O(T_local * D)). Partial results are *normalized* (o, lse) pairs
    that merge exactly in log space; both the merge and the kernel are
    differentiable, so ``jax.grad`` flows through the whole ring."""
    from split_learning_tpu.ops.flash_attention import (
        flash_attention_with_lse)

    n = lax.psum(1, axis_name)
    rank = lax.axis_index(axis_name)
    b, t_local, h, d = q.shape
    o0 = jnp.zeros((b, t_local, h, d), jnp.float32)
    lse0 = jnp.full((b, t_local, h), _NEG_BIG, jnp.float32)
    perm = [(j, (j + 1) % n) for j in range(n)]

    def merge(o1, lse1, o2, lse2):
        m = jnp.maximum(lse1, lse2)
        a1 = jnp.exp(lse1 - m)
        a2 = jnp.exp(lse2 - m)
        denom = a1 + a2
        o = (o1 * a1[..., None]
             + o2.astype(jnp.float32) * a2[..., None]) / denom[..., None]
        return o, m + jnp.log(denom)

    def block_attn(kb, vb, i):
        if not causal:
            return flash_attention_with_lse(q, kb, vb, causal=False)
        src = (rank - i) % n

        def past(args):
            return flash_attention_with_lse(*args, causal=False)

        def diag(args):
            return flash_attention_with_lse(*args, causal=True)

        def strict(args):
            return flash_attention_with_lse(*args, causal=True,
                                            strict=True)

        def future(args):
            return (jnp.zeros((b, t_local, h, d), q.dtype),
                    jnp.full((b, t_local, h), _NEG_BIG, jnp.float32))

        if striped:
            # striped positions (j*n + r) collapse every hop's global
            # mask to a LOCAL triangle: src <= rank -> causal,
            # src > rank -> strict causal (diagonal excluded). Each hop
            # is ~half-masked and the kernel's block skipping keeps the
            # per-hop cost ~half, on every rank — the balance that makes
            # striping worth its four permutes (contiguous causal idles
            # rank 0 for n-1 of its n lockstep hops).
            return lax.cond(src > rank, strict, diag, (q, kb, vb))
        # contiguous: strictly-past ranks attend unmasked, the diagonal
        # block masks elementwise, strictly-future blocks contribute
        # nothing (lax.switch executes one branch — dead hops cost no
        # FLOPs, but the lockstep ring still waits on the busiest rank)
        idx = jnp.where(src < rank, 0, jnp.where(src == rank, 1, 2))
        return lax.switch(idx, [past, diag, future], (q, kb, vb))

    def step(carry, i):
        o, lse, kb, vb = carry
        ob, lseb = block_attn(kb, vb, i)
        o, lse = merge(o, lse, ob, lseb)
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        return (o, lse, kb, vb), None

    (o, lse, kb, vb), _ = lax.scan(
        step, (o0, lse0, k, v), jnp.arange(n - 1))
    ob, lseb = block_attn(kb, vb, n - 1)
    o, _ = merge(o, lse, ob, lseb)
    return o.astype(q.dtype)                       # already [B, Tq, H, D]


def stripe_permutation(t: int, n: int) -> np.ndarray:
    """Index permutation mapping the natural token order to the striped
    ring layout: shard r's contiguous slot holds tokens r, r+n, ...
    ``x[:, stripe_permutation(T, n)]`` stripes; the inverse un-stripes
    (``np.argsort`` of it)."""
    return np.concatenate([np.arange(r, t, n) for r in range(n)])


def _ulysses_local(q: jax.Array, k: jax.Array, v: jax.Array,
                   axis_name: str, causal: bool,
                   use_flash: bool = False) -> jax.Array:
    """Per-rank body: all-to-all seq->heads, per-head attention over the
    full sequence (dense or the flash kernel), heads->seq."""
    n = lax.psum(1, axis_name)
    if q.shape[2] % n != 0:
        raise ValueError(
            f"ulysses needs heads ({q.shape[2]}) divisible by the seq axis "
            f"size ({n}); use ring_attention for odd head counts")
    # [B, T/n, H, D] -> [B, T, H/n, D]: gather sequence, scatter heads
    gather = functools.partial(lax.all_to_all, axis_name=axis_name,
                               split_axis=2, concat_axis=1, tiled=True)
    qg, kg, vg = gather(q), gather(k), gather(v)
    if use_flash:
        from split_learning_tpu.ops.flash_attention import flash_attention
        og = flash_attention(qg, kg, vg, causal=causal)
    else:
        og = full_attention(qg, kg, vg, causal=causal)
    # [B, T, H/n, D] -> [B, T/n, H, D]
    return lax.all_to_all(og, axis_name=axis_name, split_axis=1,
                          concat_axis=2, tiled=True)


def _sharded(mesh: Mesh, body, causal: bool, axis_name: str, **body_kw):
    spec_axes = [None, axis_name, None, None]
    if DATA_AXIS in mesh.axis_names:
        spec_axes[0] = DATA_AXIS
    spec = P(*spec_axes)
    return shard_map(
        functools.partial(body, axis_name=axis_name, causal=causal,
                          **body_kw),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)


def _resolve_block_impl(block_impl: str, b: int, t_q: int, t_kv: int,
                        h: int, itemsize: int) -> str:
    """``"auto"`` resolution for the parallel forms: the HBM-residency
    rule of single-device ``attn="auto"``, applied to what one rank's
    *backward* actually retains. For the dense ring body that is the
    scan residuals over every hop — f32 scores + probabilities per hop,
    i.e. O(B_local * H * T_local * T_global) total (``t_kv`` = global
    T); for ulysses it is the gathered [B_local, H/n, T, T] block.
    ``b`` must already be the per-rank batch. Delegates to
    :func:`...flash_attention.select_attention` so the crossover rule
    has exactly one home."""
    if block_impl != "auto":
        return block_impl
    from split_learning_tpu.ops.flash_attention import select_attention
    choice = select_attention(b, t_q, h, itemsize, t_kv=t_kv)
    return "flash" if choice == "flash" else "dense"


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   mesh: Optional[Mesh] = None, causal: bool = False,
                   axis_name: str = SEQ_AXIS,
                   block_impl: str = "auto",
                   layout: str = "auto") -> jax.Array:
    """Sequence-parallel attention over ``mesh``'s ``seq`` axis.

    ``q/k/v``: global ``[B, T, H, D]`` (call from inside ``jit`` — the
    shard_map partitions them; T must divide by the seq axis size).
    Falls back to single-device attention when ``mesh`` is None or has
    no ``seq`` axis, so model code can call it unconditionally.

    ``block_impl`` picks the per-block math between the ``ppermute``
    hops: ``"dense"`` materializes each rank's O(T_local^2) score block
    in plain XLA; ``"flash"`` streams it through the Pallas kernels
    (:func:`...flash_attention.flash_attention_with_lse`), dropping
    per-rank attention memory to O(T_local * D) so the multi-chip path
    keeps the single-chip flash memory ceiling; ``"auto"`` (default)
    picks per shape — dense while a rank's score block fits comfortably
    in HBM, flash beyond.

    ``layout`` places tokens on ranks: ``"contiguous"`` blocks, or
    ``"striped"`` (token ``g`` on rank ``g % n``) which makes every
    hop's mask a ~half-live local triangle — causal for hops whose
    source rank is at or before this one, strict-causal after — so no
    rank idles at the lockstep ppermute. ``"auto"`` (default) stripes
    exactly when the balance is real: causal with the flash block
    kernels, whose block skipping turns the balanced masks into
    actually-skipped work (~2x shorter critical path once t_local spans
    multiple kernel blocks;
    tests/test_ring_attention.py::test_striped_layout_balances_causal_work).
    The dense body executes masked FLOPs regardless, so it stays
    contiguous unless striping is requested explicitly (both bodies are
    exact either way). Without ``causal`` there is no triangle to
    balance, so an explicit ``"striped"`` request is coerced to
    contiguous.
    """
    if block_impl not in ("dense", "flash", "auto"):
        raise ValueError(f"Unknown ring block_impl: {block_impl!r} "
                         "(expected 'dense', 'flash' or 'auto')")
    if layout not in ("auto", "contiguous", "striped"):
        raise ValueError(f"Unknown ring layout: {layout!r} "
                         "(expected 'auto', 'contiguous' or 'striped')")
    b, t, h, _ = q.shape
    itemsize = jnp.dtype(q.dtype).itemsize
    if mesh is None or axis_name not in mesh.axis_names:
        impl = _resolve_block_impl(block_impl, b, t, t, h, itemsize)
        if impl == "flash":
            from split_learning_tpu.ops.flash_attention import (
                flash_attention)
            return flash_attention(q, k, v, causal=causal)
        return full_attention(q, k, v, causal=causal)
    n = mesh.shape[axis_name]
    t_local = t // n
    b_local = b // mesh.shape.get(DATA_AXIS, 1) or 1
    # the dense body's scan residuals are f32 regardless of input dtype
    impl = _resolve_block_impl(block_impl, b_local, t_local, t, h, 4)
    if layout == "auto":
        # striping only pays when masked work is actually SKIPPED: the
        # flash block kernels skip causally-dead block pairs, so
        # balancing the triangle shortens the lockstep critical path
        # (~2x at t_local >> kernel block). The dense body executes
        # masked FLOPs anyway — striping there buys nothing and costs
        # four permutes (q/k/v in, output back out) — so it stays
        # contiguous.
        layout = ("striped" if causal and impl == "flash"
                  else "contiguous")
    if layout == "striped" and not causal:
        layout = "contiguous"  # nothing to balance without the mask
    if layout == "striped":
        # stripe the token axis (token g on rank g % n) so every
        # (rank, hop) pair carries a ~half-masked local triangle —
        # causal for hops from src <= rank, strict-causal for
        # src > rank — instead of rank r idling for n-1-r of its hops
        perm_np = stripe_permutation(t, n)
        perm = jnp.asarray(perm_np)
        inv = jnp.asarray(np.argsort(perm_np))
        body = _sharded(mesh,
                        (_ring_flash_local if impl == "flash"
                         else _ring_attention_local),
                        causal, axis_name, striped=True)
        o = body(q[:, perm], k[:, perm], v[:, perm])
        return o[:, inv]
    body = (_ring_flash_local if impl == "flash"
            else _ring_attention_local)
    return _sharded(mesh, body, causal, axis_name)(q, k, v)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      mesh: Optional[Mesh] = None, causal: bool = False,
                      axis_name: str = SEQ_AXIS,
                      block_impl: str = "auto") -> jax.Array:
    """All-to-all (DeepSpeed-Ulysses form) sequence-parallel attention.

    After the seq->heads transpose each rank runs full-sequence
    attention over H/n heads; ``block_impl`` picks that math (dense /
    flash kernels / ``"auto"`` per shape — without flash the per-rank
    score block is O(B * H/n * T^2), so long-context ulysses needs it).
    """
    if block_impl not in ("dense", "flash", "auto"):
        raise ValueError(f"Unknown ulysses block_impl: {block_impl!r} "
                         "(expected 'dense', 'flash' or 'auto')")
    b, t, h, _ = q.shape
    itemsize = jnp.dtype(q.dtype).itemsize
    if mesh is None or axis_name not in mesh.axis_names:
        impl = _resolve_block_impl(block_impl, b, t, t, h, itemsize)
        if impl == "flash":
            from split_learning_tpu.ops.flash_attention import (
                flash_attention)
            return flash_attention(q, k, v, causal=causal)
        return full_attention(q, k, v, causal=causal)
    n = mesh.shape[axis_name]
    b_local = b // mesh.shape.get(DATA_AXIS, 1) or 1
    impl = _resolve_block_impl(block_impl, b_local, t, t,
                               max(h // n, 1), itemsize)
    return _sharded(mesh, _ulysses_local, causal, axis_name,
                    use_flash=impl == "flash")(q, k, v)
