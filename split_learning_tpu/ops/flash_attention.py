"""Flash attention — Pallas forward/backward kernel set.

The hot op of the transformer family (models/transformer.py). Dense
softmax attention materializes the ``[T, T]`` score matrix — and XLA
saves it for the backward pass, so on a 16 GB v5e chip the dense path
cannot train past ``B*H*T^2*2B ~ HBM`` (measured: b16/h2/T=16384 fails
to compile with "Used 16.00G of 15.75G hbm"). These kernels stream K/V
blocks through VMEM with the online-softmax recurrence: nothing
quadratic in T ever exists in HBM *or* VMEM, so max trainable context is
set by the O(T*D) activations alone.

Design (the canonical TPU flash schedule):
- 3-D sequential grid ``(batch*heads, outer block, inner block)`` with
  the inner dimension iterating fastest; VMEM scratch accumulators
  persist across the inner grid dimension and are initialized at
  ``inner == 0`` / finalized at ``inner == n-1`` (``pl.when``).
- Block inputs stream per grid step via BlockSpec index maps — Pallas
  double-buffers the DMAs, so K/V never resides whole in VMEM.
- Forward saves only O and the per-row logsumexp (LSE).
- Backward comes in two forms, picked per (padded T, d) by
  ``_use_onepass``:
  (a) *Mid-T one-pass* (``_onepass_bwd_kernel``): grid (bh, k block)
  with Q/dO/LSE/delta and the f32 dQ accumulator whole-sequence
  resident in VMEM; each (k, q) block pair computes scores and dO*V^T
  once and feeds dQ, dK, dV — 10 matmul units of T^2*D vs dense's 8.
  dQ's output block is revisited *consecutively* across the k grid dim
  (index map ignores k), the supported accumulation idiom. Residency
  caps this form: the double-buffered whole-sequence refs
  (``_onepass_resident_bytes`` — ~4 KiB/row at bf16 d=128) against a
  64 MiB budget inside a raised 96 MiB scoped-VMEM limit (the v5e core
  has ~128 MiB; Mosaic's 16 MiB default is what the kernel overrides),
  so bf16 d=128 stays one-pass through T = 16384.
  (b) *Long-T two-kernel split*: dQ grids over (query, key) blocks,
  dK/dV over (key, query) blocks, each recomputing P blockwise from
  (Q, K, LSE) — total 14 matmul units (1.75x dense): each kernel
  re-does scores (2) and dO*V^T (2) plus its own products. The fused
  alternatives fail exactly here: a (key, query) grid revisits dQ
  blocks non-consecutively (unsupported), dQ-partials with a leading
  key-block axis cost O(n_k * T * D) HBM (~17 GiB at T=16384/bh=32),
  and whole-sequence VMEM residency is over budget. The 1.75x
  recompute is the deliberate price of the only regime where flash is
  mandatory (past the dense HBM wall); ``attn="auto"`` arbitrates.
- Causal masking uses global block coordinates; block pairs with no
  causal overlap skip their matmuls entirely (``pl.when`` around the
  accumulate — the grid stays static, ~2x fewer FLOPs at large T).
- The kind of a block pair — dead, whole or cut: a pure function of the
  mask, the pair's offset and the static shapes — decides what its grid
  step fetches, masks and stores. A dead pair fetches nothing: its step
  names the block of the nearest live step (:func:`_inner_maps`), and
  Pallas copies nothing for an index that did not move. A whole pair
  (:func:`_whole_pairs`) masks nothing: the forward and the one-pass
  backward take its scaled scores as they are, no iota, compare or
  select. A cut pair runs its live sub-tiles, masked (next item). The
  forward's running maximum and sum live replicated over a lane tile
  (:func:`_lanes`), so no step broadcasts them across lanes.
  :func:`fetched_pair_share` and :func:`unmasked_pair_share` count both.
- A block pair that the mask only cuts (the diagonal one; under a window
  the band's last one or two) has a body of its own in all four kernels:
  it works in sub-tiles of ``_TILE`` rows and columns, runs only those
  that hold a live (row, column) — per row tile one contiguous range of
  columns, a pure function of the mask and of the pair's static offset
  (:func:`_live_cols`; :func:`live_tile_share` counts them) — and masks
  elementwise inside them. A skipped tile is one whose every ``p`` is
  exactly 0, so the result is the whole-pair body's up to float32
  summation order. Blocks, grid and DMAs stay at the block edge; whole
  pairs keep the single full-block body, and without a causal mask
  no kernel has a sub-tile (tests/test_afmoe.py). Where a row
  block has one key block in all (GPT-2 at T 1024) the forward needs no
  running maximum: each row tile's softmax is final (``_fwd_kernel``).
- Every kernel body is traced once and replayed at the other call sites
  (ops/common.py's ``traced_once``): a model calls one attention at many
  sites, and tracing a body is what a call site costs a process at start-up.

- A ``window`` (query i sees keys j with ``0 <= i - j < window``; the
  afmoe family's ``sliding_attention`` layers) shrinks the inner grid
  extent to the band (:func:`_band_blocks`): step ``j`` of query block
  ``i`` reads key block ``i - (n - 1) + j`` through the index map, so
  key blocks wholly outside the band are neither fetched nor computed,
  and the edge blocks run their live sub-tiles (above). The one-pass
  backward's loop over query blocks stops at the band's end likewise.
- Grouped key/value heads (``[B, T, H_kv, D]`` under ``H`` query
  heads): K/V blocks are read from row ``b // (H // H_kv)`` of the
  folded array, never repeated in HBM; the backward writes dK/dV per
  query head in float32 and the wrapper sums each group's.
  With ``window=None`` and equal head counts every kernel is traced
  exactly as before these two existed (tests/test_afmoe.py).
- Values of another width than the keys (``[B, T, H_kv, D_v]``; latent
  attention: keys of 192 under values of 128): every operand pads to
  its own lane tiles, so QK^T runs at the keys' padded width and PV,
  the output and dV at the values'; the scale is the keys'. A call
  whose widths are equal is traced as before
  (tests/test_joyai_llm_flash.py).

Like every op in this package there is a pure-jnp reference
(:func:`split_learning_tpu.ops.ring_attention.full_attention`) and the
kernels run under the Mosaic interpreter off-TPU
(tests/test_flash_attention.py asserts fwd+grad equivalence; also
validated compiled on a real v5e chip). Head dim pads to the 128-lane
tile and T to the block size, with masks keeping ragged shapes exact.

Composition note: flash is the *single-device* attention math; the ring
form (ops/ring_attention.py) shards T across chips and composes with
these kernels via :func:`flash_attention_with_lse`
(``attn="ring_flash"``): each rank runs the kernel per K/V block and
merges normalized ``(o, lse)`` partials in log space.
"""

from __future__ import annotations

import functools
import operator

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from split_learning_tpu.ops.common import (
    LANE, NEG_BIG as _NEG_BIG, pad_axis, round_up, traced_once, use_interpret)

_BLOCK = 128   # minimum block edge (the MXU tile); see _pick_block
_ROWW = 8      # lane width of the LSE/delta row vectors (tile-masked)

# The backward's form, named from outside a model: None, and
# :func:`_use_onepass` chooses from shapes and a preflight compile; True
# or False, and it answers that. Python sets it and no operator can: a
# test with ``monkeypatch.setattr``, scripts/fused_step_memory.py (which
# compiles for a chip it does not have, so cannot preflight) by assignment.
ONEPASS: bool | None = None


def _pick_block(t: int) -> int:
    """Square block edge for both grid axes. 128x128 blocks drown in
    per-grid-step overhead (DMA setup + semaphores): at T=4096 the
    3-D grid is bh*32*32 steps and the round-3 measurement put flash at
    2.8x slower than dense — worse than the ~1.8x recompute-FLOP ratio
    explains. The round-5 on-chip block sweep (v5e, full training
    step, `artifacts/flash_block_sweep.json`) measured 1024-row blocks
    faster than the prior 512 default at every swept shape — 58.1 vs
    45.8 steps/s at T=1024 b64, 30.0 vs 26.5 at T=4096 b16, 9.2 vs
    8.0 at T=8192 b16 — while 256 lost everywhere (16.1 at T=4096),
    so larger edges win until VMEM, not grid overhead, binds. 1024
    keeps every matmul MXU-shaped ([1024,128]x[128,1024]); the f32
    scores block is 4 MiB and the kernels' working set stays inside
    Mosaic's 16 MiB default (compiled and measured on-chip at
    T=1024..8192).

    The sweep predates PR 1 and nothing since has repeated it. What a
    1024-row block wastes where the mask cuts it (half of the diagonal
    pair, most of a window's far edge) is no longer paid by a smaller
    block but inside the kernel bodies: ``_TILE`` / :func:`_live_cols`
    (PR 31)."""
    tp128 = round_up(t, 128)
    b = 1024
    while b > 128 and tp128 % b:   # largest edge that adds no extra padding
        b //= 2
    return b


# The one-pass backward's whole-sequence refs exceed Mosaic's default
# 16 MiB scoped-VMEM limit at T=4096 (measured: 16.5 MiB requested);
# a v4/v5 core physically has ~128 MiB of VMEM, so the kernel raises
# its own limit to _vmem_limit_bytes() and budgets the whole-sequence
# refs against 2/3 of it, leaving the rest for the double-buffered
# K/V/dK/dV blocks and compiler temporaries.


def _vmem_limit_bytes() -> int:
    """Scoped-VMEM limit the one-pass kernel may request, per device
    generation. v2/v3 cores have only 16 MiB of VMEM — requesting more
    than Mosaic's default there would fail the compile of shapes the
    two-kernel split handles fine — while v4 onward have ~128 MiB. A
    non-TPU backend only ever interprets the kernels, and reports the
    v5e figure so interpret-mode tests select the same backward form as
    the chip. A TPU whose ``device_kind`` names no known generation is
    an error: the limit is a hardware figure, not something to assume.

    The raised figure was only *measured* on v5e; on other real-TPU
    generations this static pick is optimistic on purpose, because it
    is not the last line of defence: on any compiled-TPU path
    :func:`_use_onepass` confirms the selection with a cached preflight
    compile (:func:`_onepass_compile_ok`) and falls back to the
    two-kernel split when the device refuses the raised limit."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return 96 * 1024 * 1024
    kind = dev.device_kind.lower()
    if "v2" in kind or "v3" in kind:
        return 16 * 1024 * 1024
    if any(gen in kind for gen in ("v4", "v5", "v6")):
        return 96 * 1024 * 1024
    raise RuntimeError(
        f"unknown TPU device_kind {dev.device_kind!r}: no scoped-VMEM "
        "figure for this generation (ops/flash_attention.py)")


def _onepass_resident_bytes(tp: int, d: int, itemsize: int,
                            d_v: int | None = None) -> int:
    """True VMEM footprint of the one-pass backward's whole-sequence
    refs. Per padded row: Q (at the keys' width ``d``) + dO (at the
    values' ``d_v``, ``d`` where not given) in the storage dtype, the f32
    dQ output, and the LSE/delta rows — which cost a full 128-lane tile
    each despite _ROWW=8, because VMEM pads the minor dimension to the
    lane width. Pallas double-buffers every ref (constant index maps
    included — the 16.5 MiB scoped-allocation failure at T=4096 bf16
    was exactly 2x the naive sum), hence the factor 2."""
    dp = round_up(d, LANE)
    dvp = dp if d_v is None else round_up(d_v, LANE)
    per_row = dp * (itemsize + 4) + dvp * itemsize + 2 * LANE * 4
    return 2 * tp * per_row


# Below this whole-sequence residency the one-pass backward fits
# Mosaic's 16 MiB *default* scoped-VMEM limit with ~2 MiB to spare for
# the double-buffered K/V/dK/dV block buffers (~1 MiB at block=512
# d=128) and compiler temporaries, so no preflight is needed: the
# raised limit only matters past it. The estimator is accurate — the
# T=4096 bf16 failure requested 16.50 MiB vs a 16.51 MiB estimate.
# The margin was derived at block<=512; _use_onepass only consults it
# there — larger blocks always preflight, because their per-pair f32
# score temporaries (4 MiB each at 1024) void the "~2 MiB to spare"
# arithmetic.
_DEFAULT_LIMIT_SAFE = 12 * 1024 * 1024

# Largest block edge the two-kernel backward split drops to on the
# DEFAULT path when it must carry the gradient. The split kernels'
# four f32 [block,block] temporaries exceed Mosaic's 16 MiB default
# at 1024-row edges; they now request the per-generation allowance
# (same as the fwd/one-pass calls) and a blk-1024 split compiled and
# ran on-chip 2026-08-01 (T=2048 b16, 78.3 steps/s, the split forced) —
# but on generations where the allowance IS the 16 MiB default (v2/v3,
# unknown kinds at their floor) a >512 split would still be a compile
# error, and the split is only ever chosen where one-pass was refused,
# i.e. exactly the VMEM-constrained regime. 512 stays the
# proven-everywhere edge.
_SPLIT_BLOCK_MAX = 512


def _resolve_block(t: int, d: int, dtype, bh: int = 2, group: int = 1,
                   mask: tuple = (False, False, None),
                   d_v: int | None = None) -> tuple[int, bool]:
    """(block, onepass) for a public entry point: the swept default
    edge when the one-pass backward (which preflight-confirms itself)
    carries the gradient, capped to :data:`_SPLIT_BLOCK_MAX` when the
    two-kernel split must take over. ``bh`` is the program's
    batch*heads and ``mask`` its ``(causal, strict, window)``, forwarded
    so the preflight probes the grid shape and the kernel bodies the
    user will actually compile (see :func:`_onepass_compile_ok`).
    ``d_v`` is the values' width where it is not the keys' ``d``.

    Cost note: resolving the backward form eagerly means even a
    forward-only call at a >512 edge pays the one-pass preflight
    compile (cached per shape, ~seconds once per process). Accepted:
    deferring the probe to the first gradient would let the forward
    and backward disagree on the block edge (the split cap changes
    BOTH kernels' padding), and a cached compile is cheap next to a
    user-path compile error."""
    block = _pick_block(t)
    onepass = _use_onepass(t, block, d, dtype, bh, group, mask, d_v)
    if not onepass and block > _SPLIT_BLOCK_MAX:
        block = _SPLIT_BLOCK_MAX
        onepass = _use_onepass(t, block, d, dtype, bh, group, mask, d_v)
    return block, onepass


def _use_onepass(t: int, block: int, d: int, dtype, bh: int = 2,
                 group: int = 1, mask: tuple = (False, False, None),
                 d_v: int | None = None) -> bool:
    """Backward-form selection: one-pass while its whole-sequence
    residency (see :func:`_onepass_resident_bytes`) fits 2/3 of the
    device's scoped-VMEM limit, leaving the rest for the
    double-buffered K/V/dK/dV blocks and compiler temporaries — on a
    v4/v5 core (96 MiB limit, 64 MiB budget) bf16 d=128 passes through
    T=16384. :data:`ONEPASS`, where something set it, is the answer.

    When the shape needs the *raised* scoped-VMEM limit (residency past
    :data:`_DEFAULT_LIMIT_SAFE`) and the kernel will actually be
    Mosaic-compiled (not interpreted), the static choice is confirmed
    by :func:`_onepass_compile_ok` — a cached preflight compile of the
    backward alone — and quietly falls back to the two-kernel split if
    the device rejects the limit. Round-4 lesson: the T=4096 leg was a
    hard compile error on-chip three times (scoped allocation 16.50M >
    16.00M default) because selection trusted the static budget; a
    user-path shape must never be a compile error."""
    if ONEPASS is not None:
        return ONEPASS
    dtype = jnp.dtype(dtype)
    tp = round_up(t, block)
    resident = _onepass_resident_bytes(tp, d, dtype.itemsize, d_v)
    if resident > _vmem_limit_bytes() * 2 // 3:
        return False
    # Skip the preflight only inside the margin it was derived for:
    # small residency AND the <=512 block edge whose buffer arithmetic
    # _DEFAULT_LIMIT_SAFE encodes. Larger edges (the swept 1024
    # default) always ask the compiler — their f32 score temporaries
    # alone can blow the default limit even at tiny T.
    if ((resident > _DEFAULT_LIMIT_SAFE or block > _SPLIT_BLOCK_MAX)
            and not use_interpret()):
        widths = () if d_v is None else (round_up(d_v, LANE),)
        return _onepass_compile_ok(tp, round_up(d, LANE), block, dtype.name,
                                   min(bh, 2), group, mask, *widths)
    return True


@functools.lru_cache(maxsize=None)
def _onepass_compile_ok(tp: int, dp: int, block: int,
                        dtype_name: str, bh_probe: int = 2,
                        group: int = 1,
                        mask: tuple = (False, False, None),
                        dvp: int | None = None) -> bool:
    """Preflight: does the one-pass backward *compile* on this device at
    the padded shape? ``vmem_limit_bytes`` is serialized into the Mosaic
    custom call as ``scoped_memory_configs`` (verified against the
    lowered module — tests/test_flash_attention.py), but JAX documents
    that XLA may additionally require ``--xla_tpu_scoped_vmem_limit_kib``
    to honor it, and the only ground truth is the compiler's verdict on
    the actual chip. ``bh_probe`` is ``min(program bh, 2)`` — NOT a
    fixed 1: Mosaic double-buffers the whole-sequence refs across the
    bh grid boundary, so a bh=1 probe has no next slice to prefetch
    and under-counts scoped VMEM by one slice set. Measured
    2026-08-01: a blk-2048 T=16384 probe PASSED at bh=1 while the real
    bh=32 compile failed at 99.12M vs the 96M limit; bh=2 exhibits the
    boundary, residency does not grow further with bh beyond it, and a
    genuine bh=1 program (no boundary at all) still probes exactly.
    Cached per process — one ~seconds compile per distinct (padded T,
    padded D, block, dtype, probe-bh, group, mask). ``mask`` is the
    call's ``(causal, strict, window)``: since PR 31 a pair the mask cuts
    has a body of its own (sub-tiles, a ``cond`` at the band's far edge),
    so the probe compiles the bodies the call will run — their float32
    score temporaries are ``[rows, tile]`` where the whole pair's are
    ``[block, block]``, so they ask for less, but that is the compiler's
    to say. Grouped heads leave their dK/dV blocks in float32, so
    ``group`` is part of the probe too, and so is ``dvp``, the values'
    padded width where it is not the keys' ``dp``."""
    causal, strict, window = mask
    dvp = dp if dvp is None else dvp
    call = _onepass_call(bh_probe, tp, tp, dp, block, 1.0, causal, strict,
                         jnp.dtype(dtype_name), window, group, dvp)
    of = lambda rows, lanes: jax.ShapeDtypeStruct(
        (rows, tp, lanes), jnp.dtype(dtype_name))
    kv_rows = max(1, bh_probe // group)
    row = jax.ShapeDtypeStruct((bh_probe, tp, _ROWW), jnp.float32)
    try:
        jax.jit(call).lower(of(kv_rows, dp), of(kv_rows, dvp),
                            of(bh_probe, dp), of(bh_probe, dvp),
                            row, row).compile()
        return True
    except Exception as e:
        # Broad on purpose: ANY compile failure means the two-kernel
        # split (always compilable) must take over. But the verdict is
        # cached for the process, so make the demotion — and its true
        # cause, VMEM rejection or probe bug — visible exactly once
        # rather than silent (chip_smoke.py runs with this warning as
        # an error: at its shapes a demotion is a failure).
        import warnings
        warnings.warn(
            f"flash one-pass backward preflight failed at tp={tp} "
            f"dp={dp} block={block} {dtype_name}; using the two-kernel "
            f"split for this shape. Cause: {type(e).__name__}: "
            f"{str(e)[:300]}", RuntimeWarning, stacklevel=2)
        return False


# Measured speed crossover for the round-4/5 kernels (v5e,
# builder-measured before PR 1; the legs span the 07-31 and 08-01
# windows — provenance per leg in artifacts/tpu_window_runs.jsonl;
# today's code: not measured): flash beats dense at every
# T >= 1024 measured on BOTH sides — T=1024 b64: flash 45.8 (07-31
# window) vs dense 41.1 (08-01) / 42.6 (round 3); T=4096 b16: flash
# 26.5 (08-01, 45.7% MFU) vs dense 17.4 (07-31) / 17.3 (round 3),
# 1.52x; T=8192 b16: 7.95 vs 4.54 (both 07-31), 1.75x; T=16384:
# flash-only, dense cannot compile (16G HBM). The cross-window pairs
# are trusted because each dense figure is corroborated by an
# independent round-3 read to <3% (17.4/17.3, 41.1/42.6) — unlike the
# retired 07-31 dense-T=1024 contention read (2.61) they agree across
# days — and the flash margins (8-52%) exceed that cross-window
# variance. T=2048 b64: flash 18.0 (08-01 morning) vs dense 13.3
# (08-01 evening retry), 1.35x — every T >= 1024 now measured on both
# sides. The lower bracket is same-window round-5 silicon (08-01
# evening): T=256 dense leads clearly (353.3 vs 279.4, +26%); T=512
# is a statistical tie (flash 132.6 vs dense 129.7, +2.2% — inside
# the ~5-10% window spread, so not evidence of a flash win); T=1024
# flash leads clearly (58.1 vs 41.1 on the swept 1024 edge). The pin
# stays at the smallest T with a clear measured flash win. (Historic
# context: on round-3 kernels dense led T=256 by 73% — 353 vs 204 —
# so the round-5 kernels closed most of that gap without flipping it.)
_FLASH_SPEED_T = 1024


def select_attention(b: int, t: int, h: int, itemsize: int,
                     hbm_bytes: int | None = None,
                     t_kv: int | None = None,
                     interpret: bool | None = None) -> str:
    """``attn="auto"`` resolution: pick ``"full"`` (XLA dense) or
    ``"flash"`` per shape, from two measured rules:

    1. *Speed*: at or past ``_FLASH_SPEED_T`` the round-4/5 kernels
       beat dense outright on the chip (see the constant's note), so
       flash wins even when dense would fit. This rule is about
       *compiled Mosaic* speed, so it only applies where the kernel
       compiles (``interpret`` False; default: resolved from the
       backend via :func:`use_interpret`) — on interpreter backends
       (CPU test meshes) interpreted flash is never faster than XLA
       dense, and auto must not route a virtual-mesh run through the
       Python interpreter for speed's sake.
    2. *Memory*: dense saves its quadratic score/softmax/dP buffers for
       the backward — 3 buffers of [B,H,T,T] against half the chip's
       HBM (half, because the model activations/params/optimizer need
       the rest and a borderline compile that OOMs mid-run is worse
       than the slower kernel). Past that, flash is mandatory
       (measured: b16/h2/T=16384 bf16 fails to compile at 16G).

    A ``window`` changes neither rule: the dense banded path
    (``full_attention(..., window=)``) still builds and saves the whole
    ``[B, H, T, T]`` scores and masks them, so its residency is counted
    as above, over the query heads ``h`` (grouped key/value heads are
    repeated to ``h`` there); only the flash kernels skip the blocks
    outside the band.

    ``t_kv`` generalizes the rule to asymmetric query/key extents (the
    sharded parallel forms — ops/ring_attention.py — resolve their
    per-rank shapes through here so the crossover has one home)."""
    if t_kv is None:
        t_kv = t
    if interpret is None:
        interpret = use_interpret()
    if not interpret and max(t, t_kv) >= _FLASH_SPEED_T:
        return "flash"
    if hbm_bytes is None:
        hbm_bytes = _device_hbm_bytes()
    dense_resident = 3 * b * h * t * t_kv * itemsize
    return "flash" if dense_resident > hbm_bytes // 2 else "full"


def _device_hbm_bytes() -> int:
    """Default-backend memory budget. A TPU reports it through
    ``memory_stats()`` and one that does not is an error; a non-TPU
    backend (CPU test meshes) gets 16 GiB, the v5e figure, so selection
    stays deterministic across hosts."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return 16 * 1024 ** 3
    limit = (dev.memory_stats() or {}).get("bytes_limit")
    if not limit:
        raise RuntimeError(
            f"TPU {dev.device_kind!r} reports no memory_stats() "
            "bytes_limit: cannot size the dense-attention HBM budget")
    return int(limit)


def _scores(qb, kb, t, k0, q0, scale, causal, strict=False, window=None,
            masked=True):
    """Masked scaled scores for one (q block, k block) pair. Operands
    stay in their storage dtype (bf16 runs the MXU at full rate) and
    accumulate in f32. Both padded key cols and padded query rows are
    masked, so fully-padded rows carry l == 0 / lse == _NEG_BIG.
    ``strict`` excludes the diagonal (row > col) — the mask a striped
    ring hop from a future-rank shard needs (ops/ring_attention.py).
    ``window`` keeps only the band ``row - col < window``. Not
    ``masked`` (a pair of :func:`_whole_pairs`): the scores as they are
    and no mask, for :func:`_kept` to pass through."""
    s = jax.lax.dot_general(
        qb, kb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if not masked:
        return s, None
    rows = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    ok = (rows < t) & (cols < t)
    if causal:
        ok &= (rows > cols) if strict else (rows >= cols)
    if window is not None:
        ok &= (rows - cols) < window
    return jnp.where(ok, s, _NEG_BIG), ok


def _kept(ok, p):
    """``p`` where the mask ``ok`` of :func:`_scores` holds, 0 elsewhere;
    ``p`` itself where there is no mask."""
    return p if ok is None else jnp.where(ok, p, 0.0)


def _band_blocks(window: int, block: int, n_blk: int) -> int:
    """How many key blocks a query block's band can touch (and query
    blocks a key block's): rows ``[q0, q0 + block)`` see keys from
    ``q0 - window + 1`` on, so ``ceil((window - 1) / block)`` blocks
    before the diagonal one. The banded kernels make this their inner
    grid extent, so blocks wholly outside the band are neither fetched
    nor computed."""
    return min(n_blk, -(-(window - 1) // block) + 1)


# Inside a block pair that the mask cuts (the diagonal one, and under a
# window the band's far edge) the kernels work in sub-tiles of this many
# rows and columns and run only those that hold a live (row, column);
# blocks, grid and DMAs stay at _pick_block's edge. One constant, no
# knob: see _tile_edge.
_TILE = 256


def _tile_edge(blk: int) -> int:
    """Sub-tile edge inside a cut pair of ``blk``-row blocks: ``_TILE``
    where it divides a larger block, else the block itself (one tile,
    which is the whole-pair body)."""
    return _TILE if blk > _TILE and blk % _TILE == 0 else blk


def _live_cols(offset: int, blk: int, tile: int, causal: bool,
               strict: bool = False, window=None) -> tuple:
    """Per row tile of a (query block, key block) pair, the column tiles
    ``[lo, hi)`` that hold at least one live (row, column); ``(0, 0)``
    where none does. ``offset`` is the pair's first row minus its first
    column (``q0 - k0``), so local ``(i, j)`` sits ``i - j + offset`` past
    the diagonal: causal keeps ``>= 0`` (``strict``: ``>= 1``), a window
    ``< window``. The live columns of a row tile are one contiguous
    range. Rows and columns past ``t`` are not looked at here: the
    elementwise mask handles them."""
    out = []
    for r0 in range(0, blk, tile):
        j_lo, j_hi = 0, blk - 1
        if causal:       # the tile's last row sees furthest right
            j_hi = min(j_hi, r0 + tile - 1 + offset - int(strict))
        if window is not None:   # its first row sees furthest left
            j_lo = max(j_lo, r0 + offset - window + 1)
        out.append((j_lo // tile, j_hi // tile + 1) if j_lo <= j_hi
                   else (0, 0))
    return tuple(out)


def _live_rows(cols: tuple) -> tuple:
    """:func:`_live_cols` seen from the key side: per column tile the row
    tiles ``[lo, hi)`` that reach it (contiguous likewise)."""
    out = []
    for c in range(len(cols)):
        rows = [r for r, (lo, hi) in enumerate(cols) if lo <= c < hi]
        out.append((rows[0], rows[-1] + 1) if rows else (0, 0))
    return tuple(out)


def _cut_pairs(n_off: int, blk: int, tile: int, causal: bool,
               strict: bool, window) -> dict:
    """The block pairs the mask cuts, among those a kernel visits: offset
    index ``o`` (query block minus key block, ``0 <= o < n_off``) to its
    :func:`_live_cols`. A causal mask cuts the diagonal pair (``o == 0``),
    a window the band's last one or two; every other pair is whole and
    keeps the single full-block body. Empty without a causal mask or
    where the block is one tile."""
    if not causal or tile == blk:
        return {}
    whole = ((0, blk // tile),) * (blk // tile)
    cuts = {}
    for o in range(n_off):
        cols = _live_cols(o * blk, blk, tile, causal, strict, window)
        if cols != whole:
            cuts[o] = cols
    return cuts


def _whole_pairs(n_off: int, t: int, blk: int, causal: bool, strict: bool,
                 window) -> frozenset:
    """The block pairs in which nothing is masked, among those a kernel
    visits, by offset index as in :func:`_cut_pairs`: local ``(i, j)`` of
    offset ``o`` sits between ``(o - 1) * blk + 1`` and ``(o + 1) * blk -
    1`` past the diagonal, and the mask keeps both ends. Their bodies take
    the scores as they are. None where ``t`` is ragged: the last block's
    padded rows and columns are masked in every pair that holds them, so
    every pair keeps its mask."""
    if t % blk:
        return frozenset()
    if not causal:
        return frozenset(range(n_off))
    return frozenset(
        o for o in range(n_off) if (o - 1) * blk + 1 >= int(strict)
        and (window is None or (o + 1) * blk - 1 < window))


def _extents(t: int, block: int, window) -> tuple:
    """(blocks a sequence, block pairs a kernel visits a block): the grid
    of ``t`` in blocks of ``block``, banded under a ``window``."""
    n_blk = round_up(t, block) // block
    return n_blk, (n_blk if window is None
                   else _band_blocks(window, block, n_blk))


def live_tile_share(t: int, block: int, causal: bool, window=None,
                    strict: bool = False, tile: int | None = None) -> float:
    """Share of the sub-tiles in the block pairs the kernels visit at
    (padded) ``t`` that they still run: the static counter of how often
    the cut-pair bodies engage. 1.0 without a causal mask."""
    if not causal:
        return 1.0
    tile = _tile_edge(block) if tile is None else tile
    n_blk, n_off = _extents(t, block, window)
    per = [sum(hi - lo for lo, hi in
               _live_cols(o * block, block, tile, causal, strict, window))
           for o in range(n_off)]
    pairs = [n_blk - o for o in range(n_off)]   # query blocks at offset o
    return (sum(n * p for n, p in zip(pairs, per))
            / (sum(pairs) * (block // tile) ** 2))


def unmasked_pair_share(t: int, block: int, causal: bool, window=None,
                        strict: bool = False) -> float:
    """Share of the live block pairs at ``t`` whose bodies mask nothing
    (:func:`_whole_pairs`): 28 of 36 for a causal T 8192 in blocks of
    1024, none where a row block has one key block or ``t`` is ragged."""
    n_blk, n_off = _extents(t, block, window)
    whole = _whole_pairs(n_off, t, block, causal, strict, window)
    if not causal:   # every pair is visited, at no offset
        return float(bool(whole))
    return (sum(n_blk - o for o in whole)
            / sum(n_blk - o for o in range(n_off)))


def fetched_pair_share(t: int, block: int, causal: bool,
                       window=None) -> float:
    """Share of the forward's grid steps a head that fetch a key block: a
    step whose key block index (:func:`_inner_maps`) is the step before's
    copies nothing, so the dead pairs past the diagonal (and before key 0
    under a window) cost a grid step and no traffic. 36 of 64 for a
    causal T 8192 in blocks of 1024, where every step fetched before."""
    n_blk, n_in = _extents(t, block, window)
    kv_inner, _ = _inner_maps(n_blk, n_in, causal, window, _kv_index(1))
    named = [[int(kv_inner(0, i, k)[1]) for k in range(n_in)]
             for i in range(n_blk)]
    fetched = sum(1 + sum(a != b for a, b in zip(row, row[1:]))
                  for row in named)
    return fetched / (n_blk * n_in)


def _row_tiles(ranges: tuple, blk: int) -> list:
    """The live part of a cut pair by row tile: (rows, columns) as slices
    of the block, a row tile's live columns being one range
    (:func:`_live_cols`), so one narrower product."""
    tile = blk // len(ranges)
    return [(slice(r * tile, (r + 1) * tile), slice(lo * tile, hi * tile))
            for r, (lo, hi) in enumerate(ranges) if lo < hi]


def _key_tiles(ranges: tuple, blk: int) -> list:
    """The same by key tile: a key tile's live rows are one range
    (:func:`_live_rows`), so one shorter product."""
    tile = blk // len(ranges)
    return [(slice(lo * tile, hi * tile), slice(c * tile, (c + 1) * tile))
            for c, (lo, hi) in enumerate(_live_rows(ranges)) if lo < hi]


def _both(a, b):
    """``a & b`` where ``a`` may be None (no condition)."""
    return b if a is None else a & b


def _by_kind(live, o, cuts: dict, n_off: int, body, tiles,
             whole=frozenset()) -> None:
    """Dispatch one grid step of a (query block, key block) kernel by the
    kind of its pair, whose offset index is ``o()``: ``body(rows, cols)``
    over ``tiles(ranges)`` where the mask cuts it into sub-tiles
    (``cuts``), ``body(masked=False)`` where it masks nothing (``whole``),
    ``body()`` on every other pair, nothing where ``live`` (None: always)
    is false."""
    def cut(ranges):
        for rows, cols in tiles(ranges):
            body(rows, cols)

    rest = [x for x in range(n_off) if x not in cuts]
    kinds = [(functools.partial(cut, ranges), [oc])
             for oc, ranges in cuts.items()]
    kinds += [(functools.partial(body, masked=False),
               [x for x in rest if x in whole]),
              (body, [x for x in rest if x not in whole])]
    kinds = [(fn, at) for fn, at in kinds if at]
    o = o() if len(kinds) > 1 else None
    for fn, at in kinds:
        here = live
        if len(at) < n_off:
            here = _both(live, functools.reduce(
                operator.or_, [o == x for x in at]))
        if here is None:
            fn()
        else:
            pl.when(here)(fn)


def _lanes(x, n: int):
    """A statistic held replicated over one lane tile, ``[rows, LANE]``,
    over ``n`` lanes: the same vregs again where ``n`` is whole tiles (so
    that nothing is broadcast across lanes), column 0 broadcast where the
    blocks are smaller than a tile."""
    if n % LANE:
        return jnp.broadcast_to(x[:, :1], (x.shape[0], n))
    return x if n == LANE else pltpu.repeat(x, n // LANE, axis=1)


def _blk(ref, rows=None):
    """Block 0 of a ``[1, rows, lanes]`` ref, or its rows ``rows``."""
    return ref[0] if rows is None else ref[0, rows, :]


def _ix(rows=None):
    """Index of a scratch ref's rows ``rows`` (None: all of them)."""
    return slice(None) if rows is None else rows


def _past(x0, rows=None):
    """Global index of the first row of ``rows`` in a block at ``x0``."""
    return x0 if rows is None else x0 + rows.start


def _fwd_kernel(blk: int, tile: int, t: int, scale: float, causal: bool,
                strict: bool, n_k: int, window,
                q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref):
    """Grid (bh, q block, k block), k fastest. Scratch accumulators carry
    the online softmax across the k dimension. With a ``window`` the
    inner extent ``n_k`` is the band's (:func:`_band_blocks`) and step
    ``j`` is key block ``qb_i - (n_k - 1) + j``, the diagonal one last."""
    qb_i = pl.program_id(1)
    step = pl.program_id(2)
    kb_i = step if window is None else qb_i - (n_k - 1) + step
    q0 = qb_i * blk
    k0 = kb_i * blk
    cuts = _cut_pairs(n_k, blk, tile, causal, strict, window)

    def _final(rows, cols):
        """The whole softmax of the rows ``rows``, whose live keys are
        ``cols`` of this block and no other's."""
        vb = _blk(v_ref, cols)
        s, ok = _scores(_blk(q_ref, rows), _blk(k_ref, cols), t,
                        _past(k0, cols), _past(q0, rows), scale, causal,
                        strict, window)
        m = jnp.max(s, axis=1, keepdims=True)
        p = jnp.where(ok, jnp.exp(s - m), 0.0)
        l = jnp.sum(p, axis=1, keepdims=True)
        # padded query rows, and a strict mask's first row, see no key
        l_safe = jnp.where(l > 0.0, l, 1.0)
        acc = jax.lax.dot_general(
            p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        o_ref[0, rows, :] = (acc / l_safe).astype(o_ref.dtype)
        lse = jnp.where(l > 0.0, m + jnp.log(l_safe), _NEG_BIG)
        lse_ref[0, rows, :] = jnp.broadcast_to(lse, (s.shape[0], _ROWW))

    if n_k == 1 and cuts:
        # One key block (GPT-2 at T 1024): this pair is all a row sees,
        # so there is no running maximum to rebase and nothing to
        # rescale. Each row tile's softmax is final and goes straight to
        # the outputs; the scratch accumulators are not touched. (On the
        # diagonal pair every row tile has live columns.)
        for rows, cols in _row_tiles(cuts[0], blk):
            _final(rows, cols)
        return

    @pl.when(step == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_BIG)
        l_ref[:] = jnp.zeros_like(l_ref)

    # causal: a key block strictly in the future of the whole query
    # block contributes nothing — skip its matmuls entirely (the grid
    # stays static; only the compute is guarded, and the index map names
    # the diagonal's block again, so nothing is fetched: _inner_maps).
    # Blocks are square, so "any overlap" is kb_i <= qb_i. ``rows`` /
    # ``cols``: the sub-tiles of a cut pair (slices of the block), None
    # for the whole pair. m and l are held replicated over a lane tile
    # (_lanes): read, rescaled and stored as such, no lane broadcast.
    def _accumulate(rows=None, cols=None, masked=True):
        qb = _blk(q_ref, rows)
        vb = _blk(v_ref, cols)
        s, ok = _scores(qb, _blk(k_ref, cols), t, _past(k0, cols),
                        _past(q0, rows), scale, causal, strict, window,
                        masked)
        ix = _ix(rows)
        m = m_ref[ix]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        # rebase then re-mask: exp(_NEG_BIG - _NEG_BIG) would be 1
        p = _kept(ok, jnp.exp(s - _lanes(m_new, s.shape[1])))
        corr = jnp.exp(m - m_new)
        l_ref[ix] = l_ref[ix] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[ix] = (acc_ref[ix] * _lanes(corr, acc_ref.shape[1])
                       + jax.lax.dot_general(
                           p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32))
        m_ref[ix] = m_new

    if window is not None:
        # the band starts before key 0; step j sits n_k - 1 - j blocks
        # before the diagonal
        live, o = kb_i >= 0, lambda: n_k - 1 - step
    elif causal:
        live, o = kb_i <= qb_i, lambda: qb_i - kb_i
    else:
        live, o = None, None
    _by_kind(live, o, cuts, n_k, _accumulate,
             functools.partial(_row_tiles, blk=blk),
             _whole_pairs(n_k, t, blk, causal, strict, window))

    @pl.when(step == n_k - 1)
    def _finish():
        l = l_ref[:]
        # padded query rows are row-masked in _scores: l == 0 there
        l_safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[0] = (acc_ref[:] / _lanes(l_safe, acc_ref.shape[1])
                    ).astype(o_ref.dtype)
        lse = jnp.where(l > 0.0, m_ref[:] + jnp.log(l_safe), _NEG_BIG)
        lse_ref[0] = lse[:, :_ROWW]


def _onepass_bwd_kernel(blk: int, tile: int, t: int, scale: float,
                        causal: bool, strict: bool, n_q: int, window,
                        k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                        dk_ref, dv_ref, dq_ref):
    """Single-pass backward for mid-length T: grid ``(bh, k block)``
    with Q/dO/LSE/delta — and the f32 dQ accumulator — fully VMEM
    resident (≈16.5 MiB double-buffered at T=4096 bf16 d=128 — see
    :func:`_onepass_resident_bytes` — against the raised
    ``_vmem_limit_bytes()``, not Mosaic's 16 MiB default).
    Each (k, q) block pair computes scores and ``dO·Vᵀ`` exactly once
    and feeds all three gradients: 10 matmul units of T²·D vs the
    two-kernel split's 14 (module docstring), and one kernel launch
    instead of two. dQ rides an output block whose index map is
    constant across the k grid dimension — consecutive revisiting, the
    standard TPU accumulation idiom — so no O(n_k·T·D) partial buffer
    and no non-consecutive revisits (the constraints that rule this
    form out at long T, where the two-kernel split takes over)."""
    kb_i = pl.program_id(1)
    k0 = kb_i * blk

    @pl.when(kb_i == 0)
    def _init():
        dq_ref[0] = jnp.zeros(dq_ref.shape[1:], dq_ref.dtype)

    kb = k_ref[0]
    vb = v_ref[0]

    def zero_pair(rows):
        """(dK, dV) of ``rows`` keys that no query reaches: one array for
        both where the values are as wide as the keys."""
        dk = jnp.zeros((rows, dq_ref.shape[-1]), jnp.float32)
        if dv_ref.shape[-1] == dq_ref.shape[-1]:
            return dk, dk
        return dk, jnp.zeros((rows, dv_ref.shape[-1]), jnp.float32)

    def pair(q0, n, kb, vb, k0, dk, dv, masked=True):
        """Rows ``[q0, q0 + n)`` of the queries against the keys ``kb`` at
        ``k0``: dQ accumulates in place, (dK, dV) are returned added to
        ``dk`` / ``dv`` (None: nothing to add to)."""
        qb = q_ref[0, pl.ds(q0, n), :]
        dob = do_ref[0, pl.ds(q0, n), :]
        lse = lse_ref[0, pl.ds(q0, n), :][:, :1]
        delta = delta_ref[0, pl.ds(q0, n), :][:, :1]
        s, ok = _scores(qb, kb, t, k0, q0, scale, causal, strict, window,
                        masked)
        p = _kept(ok, jnp.exp(s - lse))
        dv_p = jax.lax.dot_general(
            p.astype(dob.dtype), dob, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dv = dv_p if dv is None else dv + dv_p
        dp = jax.lax.dot_general(
            dob, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_p = jax.lax.dot_general(
            ds.astype(qb.dtype), qb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk = dk_p if dk is None else dk + dk_p
        dq_ref[0, pl.ds(q0, n), :] += (jax.lax.dot_general(
            ds.astype(kb.dtype), kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        ).astype(dq_ref.dtype)
        return dk, dv

    def cut(j, ranges, carry):
        """Query block ``j``, a pair the mask cuts: per key tile its live
        rows are one range (:func:`_live_rows`), so each key tile takes
        one shorter product and leaves its own rows of dK, dV."""
        parts = []
        for c, (lo, hi) in enumerate(_live_rows(ranges)):
            if lo == hi:
                parts.append(zero_pair(tile))
                continue
            keys = slice(c * tile, (c + 1) * tile)
            parts.append(pair(j * blk + lo * tile, (hi - lo) * tile,
                              k_ref[0, keys, :], v_ref[0, keys, :],
                              k0 + c * tile, None, None))
        dk, dv = (jnp.concatenate(x, axis=0) for x in zip(*parts))
        return carry[0] + dk, carry[1] + dv

    # causal: query blocks strictly before this key block are dead;
    # banded: so are those past the band (_band_blocks). Query block
    # kb_i + o sits o blocks past the diagonal: the cut ones run their
    # live sub-tiles, the whole ones between them the loop.
    n_off = n_q if window is None else _band_blocks(window, blk, n_q)
    cuts = _cut_pairs(n_off, blk, tile, causal, strict, window)
    carry = zero_pair(kb.shape[0])
    if 0 in cuts:
        carry = cut(kb_i, cuts[0], carry)
    whole = [o for o in range(n_off) if o not in cuts]   # one run
    if whole:
        # its body takes the scores as they are if the mask leaves every
        # entry of every pair of the run
        masked = not set(whole) <= _whole_pairs(n_off, t, blk, causal,
                                                strict, window)
        start = 0
        if causal:
            start = kb_i + whole[0] if whole[0] else kb_i
        stop = n_q if window is None else jnp.minimum(
            n_q, kb_i + (whole[-1] + 1))
        carry = jax.lax.fori_loop(
            start, stop, lambda j, carry: pair(
                j * blk, blk, kb, vb, k0, *carry, masked=masked), carry)
    for o in cuts:
        if o:   # the band's far edge, where it is inside T
            carry = jax.lax.cond(
                kb_i + o < n_q, functools.partial(cut, kb_i + o, cuts[o]),
                lambda c: c, carry)
    dk, dv = carry
    dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _dq_kernel(blk: int, tile: int, t: int, scale: float, causal: bool,
               strict: bool, n_k: int, window,
               q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, acc_ref):
    """Grid (bh, q block, k block): dQ = scale * sum_k dS_k @ K_k,
    dS = P * (dO @ V^T - delta). Banded as :func:`_fwd_kernel` is."""
    qb_i = pl.program_id(1)
    step = pl.program_id(2)
    kb_i = step if window is None else qb_i - (n_k - 1) + step
    q0 = qb_i * blk
    k0 = kb_i * blk

    @pl.when(step == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _accumulate(rows=None, cols=None):
        qb = _blk(q_ref, rows)
        kb = _blk(k_ref, cols)
        s, ok = _scores(qb, kb, t, _past(k0, cols), _past(q0, rows), scale,
                        causal, strict, window)
        p = jnp.where(ok, jnp.exp(s - _blk(lse_ref, rows)[:, :1]), 0.0)
        dp = jax.lax.dot_general(
            _blk(do_ref, rows), _blk(v_ref, cols),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - _blk(delta_ref, rows)[:, :1])
        acc_ref[_ix(rows)] += jax.lax.dot_general(
            ds.astype(kb.dtype), kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if window is not None:
        live, o = kb_i >= 0, lambda: n_k - 1 - step
    elif causal:
        # key blocks strictly in the future of this query block are dead
        live, o = kb_i <= qb_i, lambda: qb_i - kb_i
    else:
        live, o = None, None
    _by_kind(live, o, _cut_pairs(n_k, blk, tile, causal, strict, window),
             n_k, _accumulate, functools.partial(_row_tiles, blk=blk))

    @pl.when(step == n_k - 1)
    def _finish():
        dq_ref[0] = (acc_ref[:] * scale).astype(dq_ref.dtype)


def _dkv_kernel(blk: int, tile: int, t: int, scale: float, causal: bool,
                strict: bool, n_q: int, window, n_blk: int,
                k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc):
    """Grid (bh, k block, q block): dV = sum_q P^T @ dO,
    dK = scale * sum_q dS^T @ Q. With a ``window`` the inner extent
    ``n_q`` is the band's and step ``j`` is query block ``kb_i + j``
    (of ``n_blk``), the diagonal one first."""
    kb_i = pl.program_id(1)
    step = pl.program_id(2)
    qb_i = step if window is None else kb_i + step
    k0 = kb_i * blk
    q0 = qb_i * blk

    @pl.when(step == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    # causal: this key block only receives gradient from query blocks at
    # or after it (q0 >= k0 for some overlap) — skip strictly-past ones
    def _accumulate(rows=None, cols=None):
        qb = _blk(q_ref, rows)
        kb = _blk(k_ref, cols)
        dob = _blk(do_ref, rows)
        s, ok = _scores(qb, kb, t, _past(k0, cols), _past(q0, rows), scale,
                        causal, strict, window)
        p = jnp.where(ok, jnp.exp(s - _blk(lse_ref, rows)[:, :1]), 0.0)
        dv_acc[_ix(cols)] += jax.lax.dot_general(
            p.astype(dob.dtype), dob, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            dob, _blk(v_ref, cols), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - _blk(delta_ref, rows)[:, :1])
        dk_acc[_ix(cols)] += jax.lax.dot_general(
            ds.astype(qb.dtype), qb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if window is not None:
        # the band ends past row T; step j sits j blocks past the diagonal
        live, o = qb_i < n_blk, lambda: step
    elif causal:
        live, o = qb_i >= kb_i, lambda: qb_i - kb_i
    else:
        live, o = None, None
    _by_kind(live, o, _cut_pairs(n_q, blk, tile, causal, strict, window),
             n_q, _accumulate, functools.partial(_key_tiles, blk=blk))

    @pl.when(step == n_q - 1)
    def _finish():
        dk_ref[0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


# --------------------------------------------------------------------- #
@functools.lru_cache(maxsize=None)
def _onepass_kernel(*static):
    """The one-pass backward's body for one set of static arguments, the
    same object at every call site so that it is traced once."""
    return traced_once(functools.partial(_onepass_bwd_kernel, *static))


def _kv_index(group: int):
    """Row of the folded ``[B * H_kv, T, D]`` key/value array that query
    row ``b`` of ``[B * H, T, D]`` reads: query head ``n`` reads
    key/value head ``n // group``, and ``B * H`` folds batch-major, so
    the row is ``b // group``."""
    return (lambda b: b) if group == 1 else (lambda b: b // group)


def _inner_maps(n_blk: int, n_in: int, causal: bool, window, kv):
    """Index maps of the blocks that move with the inner grid axis, in a
    grid ``(bh, outer block i, inner step k)``: ``kv_inner``, the key and
    value block of query block ``i`` (forward, dQ), and ``q_inner``, the
    query-side block of key block ``i`` (dK/dV). A step whose pair is dead
    names the block of the nearest live step, and Pallas copies nothing
    for a step whose index is the step before's, so a dead pair costs a
    grid step and no fetch. ``kv`` is :func:`_kv_index`'s row map."""
    if window is not None:
        # banded: step k of query block i is key block i - (n_in-1) + k
        # (clamped: the kernel skips the steps before key 0), and step k
        # of key block i is query block i + k (clamped likewise)
        def kv_inner(b, i, k):
            return (kv(b), jnp.maximum(i - (n_in - 1) + k, 0), 0)

        def q_inner(b, i, k):
            return (b, jnp.minimum(i + k, n_blk - 1), 0)
    elif causal and n_blk > 1:
        # step k is block k: the key blocks past the diagonal are dead,
        # and so are the query blocks before it (one block has neither)
        def kv_inner(b, i, k):
            return (kv(b), jnp.minimum(k, i), 0)

        def q_inner(b, i, k):
            return (b, jnp.maximum(k, i), 0)
    else:
        def kv_inner(b, i, k):
            return (kv(b), k, 0)

        def q_inner(b, i, k):
            return (b, k, 0)
    return kv_inner, q_inner


def _onepass_call(bh: int, t: int, tp: int, dp: int, block: int,
                  scale: float, causal: bool, strict: bool, in_dtype,
                  window=None, group: int = 1, dvp: int | None = None):
    """The one-pass backward's ``pallas_call``, shared verbatim between
    the real VJP (:func:`_make_flash`) and the preflight probe
    (:func:`_onepass_compile_ok`) so the probe compiles exactly what the
    user path would. Whole-sequence refs (index maps ignore the k grid
    dim; dq revisits its block consecutively across k) against the
    raised ``_vmem_limit_bytes()``, not Mosaic's 16 MiB default.

    Grouped heads (``group`` query heads a key/value head): K/V blocks
    are read from row ``b // group``, and dK/dV come out per *query*
    head in float32 — the caller sums each group's. ``dvp`` is the
    values' padded width (V, dO and dV) where it is not ``dp``, the
    queries' and keys' (Q, K, dQ and dK)."""
    n_blk = tp // block
    dvp = dp if dvp is None else dvp
    kv = _kv_index(group)
    seq = lambda lanes: pl.BlockSpec((1, tp, lanes), lambda b, k: (b, 0, 0),
                                     memory_space=pltpu.VMEM)
    seqrow = seq(_ROWW)
    kin = lambda lanes: pl.BlockSpec(
        (1, block, lanes), lambda b, k: (kv(b), k, 0),
        memory_space=pltpu.VMEM)
    kout = lambda lanes: pl.BlockSpec(
        (1, block, lanes), lambda b, k: (b, k, 0), memory_space=pltpu.VMEM)
    kv_dtype = in_dtype if group == 1 else jnp.float32
    return pl.pallas_call(
        _onepass_kernel(block, _tile_edge(block), t, scale, causal, strict,
                        n_blk, window),
        out_shape=(
            jax.ShapeDtypeStruct((bh, tp, dp), kv_dtype),
            jax.ShapeDtypeStruct((bh, tp, dvp), kv_dtype),
            jax.ShapeDtypeStruct((bh, tp, dp), jnp.float32),
        ),
        grid=(bh, n_blk),
        in_specs=[kin(dp), kin(dvp), seq(dp), seq(dvp), seqrow, seqrow],
        out_specs=(kout(dp), kout(dvp), seq(dp)),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit_bytes()),
        interpret=use_interpret(),
    )


@functools.lru_cache(maxsize=None)
def _make_flash(bh: int, t: int, d: int, causal: bool, dtype_name: str,
                block: int, with_lse: bool = False, strict: bool = False,
                onepass: bool = False, window=None, group: int = 1,
                d_v: int | None = None):
    """Custom-VJP flash attention for one static ([BH, T, D], causal).

    ``d`` is the width of a query and of a key, and sets the scale
    ``d ** -0.5``; ``d_v`` the width of a value and of the output where
    it differs (latent attention: keys of 192 under values of 128). Each
    pads to its own lane tiles, so the second product runs at the
    values' width whatever the keys'.

    ``with_lse=True`` additionally returns the per-row logsumexp as a
    differentiable output — the hook ring attention composes on
    (partial results merge exactly via (o, lse) pairs). The backward
    absorbs the lse cotangent into the ``delta`` row vector:
    ``dS = P * (dP - (delta - g_lse))`` since ``d lse / d s = P``.

    ``window`` (with ``causal``) keeps keys ``0 <= i - j < window``; the
    inner grid extent shrinks to the band (:func:`_band_blocks`).
    ``group`` > 1: K/V are ``[BH // group, T, D]``."""
    in_dtype = jnp.dtype(dtype_name)
    scale = d ** -0.5
    tp = round_up(t, block)
    dp = round_up(d, LANE)
    d_v = d if d_v is None else d_v
    dvp = round_up(d_v, LANE)
    n_blk, n_in = _extents(t, block, window)
    grid = (bh, n_blk, n_in)
    kv = _kv_index(group)

    def pad_qkv(x, lanes=dp):
        return pad_axis(pad_axis(x, 1, tp), 2, lanes)

    def outer(b, i, k):   # block of the outer (grid dim 1) axis
        return (b, i, 0)

    def kv_outer(b, i, k):   # key/value block of the outer axis
        return (kv(b), i, 0)

    kv_inner, q_inner = _inner_maps(n_blk, n_in, causal, window, kv)
    blk = lambda idx, lanes=dp: pl.BlockSpec((1, block, lanes), idx,
                                             memory_space=pltpu.VMEM)
    vblk = lambda idx: blk(idx, dvp)     # a block of V, O, dO or dV
    row = lambda idx: blk(idx, _ROWW)
    acc_scratch = pltpu.VMEM((block, dp), jnp.float32)
    v_scratch = pltpu.VMEM((block, dvp), jnp.float32)
    static = (block, _tile_edge(block), t, scale, causal, strict, n_in,
              window)
    # the forward's running maximum and sum, replicated over a lane tile
    # (_lanes); where every row tile's softmax is final (_fwd_kernel:
    # one key block, cut) they are not touched and stay as narrow as
    # they were
    final = n_in == 1 and _cut_pairs(n_in, block, _tile_edge(block), causal,
                                     strict, window)
    stat_scratch = pltpu.VMEM((block, _ROWW if final else LANE), jnp.float32)
    fwd_kernel = traced_once(functools.partial(_fwd_kernel, *static))
    dq_kernel = traced_once(functools.partial(_dq_kernel, *static))
    dkv_kernel = traced_once(functools.partial(_dkv_kernel, *static, n_blk))

    def fwd_call(q, k, v):
        qp, kp, vp = pad_qkv(q), pad_qkv(k), pad_qkv(v, dvp)
        o, lse = pl.pallas_call(
            fwd_kernel,
            out_shape=(
                jax.ShapeDtypeStruct((bh, tp, dvp), in_dtype),
                jax.ShapeDtypeStruct((bh, tp, _ROWW), jnp.float32),
            ),
            grid=grid,
            in_specs=[blk(outer), blk(kv_inner), vblk(kv_inner)],
            out_specs=(vblk(outer), row(outer)),
            scratch_shapes=[v_scratch, stat_scratch, stat_scratch],
            interpret=use_interpret(),
            # same per-generation allowance the one-pass backward gets
            # (a limit, not a reservation): at the default <=1024 edges
            # the working set fits Mosaic's 16 MiB default anyway, but
            # a 2048-row tuning edge's f32 score block alone is 16 MiB
            # and needs the raised ceiling to compile at all
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_vmem_limit_bytes()),
        )(qp, kp, vp)
        return o, lse, (qp, kp, vp)

    def out_of(o, lse):
        if with_lse:
            return o[:, :t, :d_v], lse[:, :t, 0]
        return o[:, :t, :d_v]

    @jax.custom_vjp
    def attn(q, k, v):
        o, lse, _ = fwd_call(q, k, v)
        return out_of(o, lse)

    def vjp_fwd(q, k, v):
        o, lse, (qp, kp, vp) = fwd_call(q, k, v)
        return out_of(o, lse), (qp, kp, vp, o, lse)

    def vjp_bwd(res, g):
        qp, kp, vp, o, lse = res
        g_lse = None
        if with_lse:
            g, g_lse = g
        # dO stays in the storage dtype so the backward matmuls run the
        # MXU at native rate; delta accumulates in f32
        dop = pad_qkv(g.astype(in_dtype), dvp)
        delta = jnp.sum(dop.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=2, keepdims=True)
        if g_lse is not None:
            # d lse / d s = P: the lse cotangent rides the same P-weighted
            # row reduction, so it folds into delta with a minus sign
            delta = delta - pad_axis(
                g_lse.astype(jnp.float32), 1, tp)[..., None]
        delta = jnp.broadcast_to(delta, (bh, tp, _ROWW))
        if onepass:
            # mid-T fast path: one kernel, scores computed once per
            # block pair (shared builder — see _onepass_call)
            dk, dv, dq = _onepass_call(
                bh, t, tp, dp, block, scale, causal, strict, in_dtype,
                window, group, dvp)(kp, vp, qp, dop, lse, delta)
            dq = dq.astype(in_dtype)
        else:
            # same per-generation allowance as the fwd call: the
            # default path never exceeds _SPLIT_BLOCK_MAX (where the
            # 16 MiB default suffices), but an explicit large-block
            # override that the bh-exact preflight demotes to this
            # split must not become the compile error the one-pass
            # fallback exists to prevent
            split_params = pltpu.CompilerParams(
                vmem_limit_bytes=_vmem_limit_bytes())
            kv_dtype = in_dtype if group == 1 else jnp.float32
            dq = pl.pallas_call(
                dq_kernel,
                out_shape=jax.ShapeDtypeStruct((bh, tp, dp), in_dtype),
                grid=grid,
                in_specs=[blk(outer), blk(kv_inner), vblk(kv_inner),
                          vblk(outer), row(outer), row(outer)],
                out_specs=blk(outer),
                scratch_shapes=[acc_scratch],
                interpret=use_interpret(),
                compiler_params=split_params,
            )(qp, kp, vp, dop, lse, delta)
            dk, dv = pl.pallas_call(
                dkv_kernel,
                out_shape=(
                    jax.ShapeDtypeStruct((bh, tp, dp), kv_dtype),
                    jax.ShapeDtypeStruct((bh, tp, dvp), kv_dtype),
                ),
                grid=grid,
                in_specs=[blk(kv_outer), vblk(kv_outer), blk(q_inner),
                          vblk(q_inner), row(q_inner), row(q_inner)],
                out_specs=(blk(outer), vblk(outer)),
                scratch_shapes=[acc_scratch, v_scratch],
                interpret=use_interpret(),
                compiler_params=split_params,
            )(kp, vp, qp, dop, lse, delta)
        if group > 1:
            # per query head in float32: each key/value head's gradient
            # is the sum over the query heads that read it
            fold = lambda x: x.reshape(
                bh // group, group, tp, x.shape[-1]).sum(1).astype(in_dtype)
            dk, dv = fold(dk), fold(dv)
        trim = lambda x, lanes=d: x[:, :t, :lanes]
        return trim(dq), trim(dk), trim(dv, d_v)

    attn.defvjp(vjp_fwd, vjp_bwd)
    return attn


def _folded(q, k, v, causal: bool, window, with_lse: bool, strict: bool):
    """Shared entry: check the mask and the head counts, resolve the
    block, fold ``[B, T, H, D]`` to ``[B * H, T, D]`` and call."""
    b, t, h, d = q.shape
    h_kv, d_v = k.shape[2], v.shape[3]
    if (k.shape[:3] != v.shape[:3] or k.shape[3] != d or h % h_kv
            or k.shape[:2] != (b, t)):
        raise ValueError(f"q {q.shape} against k {k.shape}, v {v.shape}: "
                         "key/value heads must divide the query heads, "
                         "and a key is as wide as a query")
    if window is not None and (not causal or strict or window < 1):
        raise ValueError("window is the causal band 0 <= i - j < window: "
                         "it needs causal=True, strict=False, window >= 1")
    if window is not None and window >= t:
        window = None   # the band covers every causal key
    # equal widths are the call every other family makes: no argument
    widths = {} if d_v == d else {"d_v": d_v}
    block, onepass = _resolve_block(t, d, q.dtype, bh=b * h,
                                    group=h // h_kv,
                                    mask=(causal, strict, window), **widths)
    fn = _make_flash(b * h, t, d, causal, str(q.dtype), block,
                     with_lse=with_lse, strict=strict, onepass=onepass,
                     window=window, group=h // h_kv, **widths)

    def fold(x):  # [B, T, H, D] -> [B*H, T, D]
        return jnp.transpose(x, (0, 2, 1, 3)).reshape(-1, t, x.shape[-1])

    return fn(fold(q), fold(k), fold(v))


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False, window: int | None = None
                    ) -> jax.Array:
    """Blockwise-streamed attention, ``[B, T, H, D] -> [B, T, H, D]``.

    Drop-in for
    :func:`split_learning_tpu.ops.ring_attention.full_attention` with a
    Pallas kernel forward/backward (compiled on TPU, interpreted
    elsewhere). ``k``/``v`` may hold fewer heads than ``q``
    (``[B, T, H_kv, D]``, ``H % H_kv == 0``): query head ``n`` reads
    key/value head ``n // (H // H_kv)``. ``v`` may be of another width
    than ``q`` and ``k`` (``[B, T, H_kv, D_v]``); the output is ``[B, T,
    H, D_v]``. ``window`` (needs ``causal``)
    lets query ``i`` see keys ``j`` with ``0 <= i - j < window``; key
    blocks wholly outside the band are skipped, not masked.
    """
    b, t, h, _ = q.shape
    o = _folded(q, k, v, causal, window, False, False)
    return jnp.transpose(o.reshape(b, h, t, v.shape[-1]), (0, 2, 1, 3))


def flash_attention_with_lse(q: jax.Array, k: jax.Array, v: jax.Array,
                             causal: bool = False, strict: bool = False,
                             window: int | None = None
                             ) -> tuple[jax.Array, jax.Array]:
    """:func:`flash_attention` that also returns the per-row logsumexp.

    ``[B, T, H, D] -> ([B, T, H, D], [B, T, H])``. Both outputs are
    differentiable (the lse cotangent folds into the backward's delta
    row). ``(o, lse)`` pairs from disjoint key sets merge exactly —
    ring attention (ops/ring_attention.py) uses this as its per-block
    compute so no rank ever materializes O(T_local^2) scores.

    ``strict`` masks the diagonal too (row > col) — the mask a striped
    ring hop from a future-rank shard needs; a fully-masked first row
    comes back as ``o = 0, lse = NEG_BIG``, the identity of the
    log-space merge. ``strict`` refines the causal mask, so it requires
    ``causal=True``. ``window`` and grouped key/value heads as in
    :func:`flash_attention`."""
    if strict and not causal:
        raise ValueError("strict=True refines the causal mask and "
                         "requires causal=True")
    b, t, h, _ = q.shape
    o, lse = _folded(q, k, v, causal, window, True, strict)
    o = jnp.transpose(o.reshape(b, h, t, v.shape[-1]), (0, 2, 1, 3))
    return o, jnp.transpose(lse.reshape(b, h, t), (0, 2, 1))
