"""Split AFMoE — routed experts beside a shared one, window beside full
attention over grouped heads (the Trinity family's ``afmoe`` layer).

Nothing of models/transformer.py's ``Block`` fits; the stages are
models/cut.py's (split, u_split, federated), given :func:`_run_layers`, the
embedding times ``sqrt(d_model)`` (the family's muP rule) and
:class:`RMSNorm` as the final norm.

One layer (RMSNorm(x) = x / sqrt(mean(x^2) + eps) * scale, no biases):

- attention: ``u = norm_in(h)``; q ``[T, H, D]``, k, v ``[T, H_kv, D]``
  and an output gate ``[T, H * D]`` from ``u``; q and k RMS-normed over
  ``D``; rotary positions (rotate-half) on ``sliding_attention`` layers
  only, none on ``full_attention`` ones; query head ``n`` reads
  key/value head ``n // (H // H_kv)``; causal, and on a sliding layer
  only keys ``0 <= i - j < window``; ``a = attention * sigmoid(gate)``;
  ``h = h + norm_post_attn(a @ Wo)``.
- a dense layer: ``h = h + norm_post_mlp(SwiGLU(norm_pre_mlp(h)))``.
- an expert layer: ``m = norm_pre_mlp(h)``; the router scores all
  ``experts_total`` in float32 (``sigmoid(m @ Wr)``), picks
  ``experts_per_token`` by score plus a constant selection bias,
  normalises the picked scores over all of them and scales by
  ``route_scale``; ``h = h + norm_post_mlp(shared(m) + routed(m))``.

**The routed layer is told its share.** It holds experts
``[expert_offset, expert_offset + experts_held)`` of ``experts_total``
as three stacked leaves (two where the expert has no gate:
:class:`RoutedExperts`), routes over all of them and computes the part
its own give: the (token, expert) pairs are sorted by the held expert
they chose, pairs of absent experts last (:func:`held_pairs`), the first
``R`` sorted rows are gathered, the grouped products
(ops/grouped_matmul.py) run over the rows that are filled, and every
token sums its pairs' rows, a row of zeros for each pair not held. No
token is dropped, shapes are static, one program serves every routing.
What the absent experts would add is left out; no code stands in for
absent chips. ``experts_held == experts_total`` is the whole layer.

**``R`` follows the rows the routing fills.** The worst case is every
pair held, ``n * k`` rows (tokens x experts per token); a share of the
experts fills ``experts_held / experts_total`` of that under even
routing. :func:`pair_rungs` gives up to three static row counts: twice
the even share in whole row tiles (8192 for 8 of 128 experts at 8192
tokens x 8), twice that (16384) where it is still under the worst case,
and ``n * k``, each a body of one ``lax.switch`` whose index is read on
the device from the sort's ``group_sizes`` (:func:`rung_of`: the
smallest rung that holds their sum). The top rung is the worst case
itself, so no routing overflows; sorted rows at or past ``R`` are pairs
of absent experts and add exactly zero, as rows past the groups' sum do
in any rung; the rows that are filled are the same rows in the same
order and tiles whatever the rung. What stays ``n * k`` long is indexed
by pair: the two sorts, and the gather of each token's rows (forward,
and the gradient of the gather out), which reads an ``[R + 1, d]``
table. A share of a quarter to a half of the experts has two rungs
(twice the lower one is the worst case or more), a share of half or
more has one rung and no conditional. XLA sizes a step's temporaries for
the largest branch, the top rung's; how it packs them moves a little
with the number of branches (PERF.md, Findings PR 41). The grouped
products follow the rows that are filled whatever the rung; everything
XLA runs around them (the gather out, the zeroing of unfilled rows after
every product, the activation, the float32 cotangent rows) is a pass
over the rung's static rows, so a layer whose routing sends it 2.1 times
the even share would, with two rungs, run 4 to 16 times the rows it ran
a step before. A router without a balancing loss drifts exactly there:
of the (step, layer) samples above the lower rung in the four routed
cells' 20 s windows, most are under twice it (PERF.md, Findings PR 35-41).
Three rungs and not four or a ladder of halvings up to the worst case:
every rung is one more body (twelve kernel call sites, forward and
backward; eight without a gate) to trace, lower and load in every
process. PR 29 read ``setup_s`` +17.8 % for two more rungs against a
bound of 10 % and was refused; one more costs 4 to 8 %, and a fourth
between the middle and the top would save under 2 ms a layer where the
pairs themselves, at four times the even share, are the products' own
work. A rung is its own XLA program of the same bfloat16 mathematics:
where a rounding falls may follow its shapes, so two rungs agree to the
comparison's limits on the chip and not to the last bit (on the CPU, in
float32, a routing gives the same output from any rung that holds it).

**What ``remat`` recomputes.** With ``remat`` the routed part is one
``custom_vjp`` (:func:`_routed_recomputed`): its backward switches again
on the same index and makes the rung's body again inside the branch
(:func:`_routed_rows`: the gather out, the grouped products, the
sum back), so none of a rung's rows is kept and what a conditional
returns is its result alone. One ``checkpoint`` around a ``switch``
would instead return every rung's kept rows from every branch, zeros
for the rung not taken. The router product, scores, top-k and the pairs'
sorts run once and their small results are kept. Everything else the
forward made is kept for the backward too: every dense product's output
(q, k, v, output gate and output projection, the shared expert's and the
dense layer's gate / up / down), what the flash kernels' backward reads
(padded q, k, v, the output and the row logsumexp) and the elementwise
work between them, so no kernel and no dense product of a layer runs a
second time. Without ``remat`` the routed part runs on the top rung
alone and autodiff keeps its rows: kept rows of every rung would all be
outputs of the ``switch`` (15.9 GB a step at the benchmark's sizes with
two rungs, no cell runs it). ``Config.remat`` (``core/stage.remat_plan``:
the whole stage under ``jax.checkpoint``) nests over this for a user
short of memory.

**What the step reports.** A caller that applies the layer with the
collection ``obs/spans.STEP_COUNTERS`` mutable (``core/stage.with_counters``:
the fused step) gets, for each routed layer, what the device alone knew:
``pairs`` (``group_sizes``: the pairs each held expert got this step),
``rows`` (the row count of the rung they select; ``n * k`` without
``remat`` or where the ladder has one rung) and ``ladder`` (the static
rungs). Nothing else is computed for them, and a caller that does not ask
traces the program without them.

The selection bias ``expert_bias`` is a float32 leaf under
``stop_gradient``: its published update follows the per-expert token
counts of a step, which now leave the step as ``pairs`` (above); nothing
feeds them back yet (ROADMAP.md M4a), so the bias stays where ``init``
put it. Decoding through a KV cache is not built for this family.
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from split_learning_tpu.core.stage import SplitPlan
from split_learning_tpu.models import cut
from split_learning_tpu.obs import spans
from split_learning_tpu.ops.flash_attention import (
    flash_attention, select_attention)
from split_learning_tpu.ops.common import round_up
from split_learning_tpu.ops.grouped_matmul import (
    _tiles as _row_tiles, grouped_matmul)
from split_learning_tpu.ops.ring_attention import full_attention

_LAYER_TYPES = ("sliding_attention", "full_attention")
_HI = jax.lax.Precision.HIGHEST
_INIT = nn.initializers.normal(0.02)


class RMSNorm(nn.Module):
    """Statistics in float32 whatever the compute type; the parameter is
    ``scale`` (benchmarks/weights.py draws leaves of that name around 1)."""

    eps: float = 1e-5
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x = x.astype(jnp.float32)
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + self.eps)
        return (y * scale).astype(self.dtype)


def _linear(features: int, dtype, name: str) -> nn.Dense:
    return nn.Dense(features, use_bias=False, dtype=dtype, kernel_init=_INIT,
                    name=name)


def rope(x: jax.Array, theta: float) -> jax.Array:
    """Rotary positions, rotate-half form, position = index along axis 1
    of ``[B, T, H, D]``; computed in float32."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    return (x32 * cos + jnp.concatenate([-x2, x1], -1) * sin).astype(x.dtype)


class AfmoeAttention(nn.Module):
    """Grouped-head attention. ``plain`` (models/nemotron_h.py's) is the
    same layer without the output gate and without the norms of q and k:
    with ``window`` None it has no positions either, four products and
    the kernel. ``rope_always`` (models/ouro.py's) turns q and k in a
    full layer too."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    window: Optional[int]     # None: a full_attention layer
    rope_theta: float = 10000.0
    eps: float = 1e-5
    attn: str = "auto"
    dtype: Any = jnp.float32
    plain: bool = False
    rope_always: bool = False

    @nn.compact
    def __call__(self, u):
        b, t, e = u.shape
        h, hk, d = self.num_heads, self.num_kv_heads, self.head_dim
        q = _linear(h * d, self.dtype, "q")(u).reshape(b, t, h, d)
        k = _linear(hk * d, self.dtype, "k")(u).reshape(b, t, hk, d)
        v = _linear(hk * d, self.dtype, "v")(u).reshape(b, t, hk, d)
        if not self.plain:
            gate = _linear(h * d, self.dtype, "gate")(u)
            q = RMSNorm(self.eps, self.dtype, name="q_norm")(q)
            k = RMSNorm(self.eps, self.dtype, name="k_norm")(k)
        if self.window is not None or self.rope_always:
            q, k = rope(q, self.rope_theta), rope(k, self.rope_theta)
        impl = self.attn
        if impl == "auto":
            # the dense-against-flash rule counts dense residency at
            # [B, H, T, T] whatever the window (select_attention's note)
            impl = select_attention(b, t, h, jnp.dtype(self.dtype).itemsize)
        fn = {"flash": flash_attention, "full": full_attention}[impl]
        # the scope names the kernels' calls in a device trace
        scope = spans.ATTN_FULL if self.window is None else spans.ATTN_WINDOW
        with jax.named_scope(scope):
            o = fn(q, k, v, causal=True, window=self.window)
        a = o.reshape(b, t, h * d)
        if not self.plain:
            a = a * jax.nn.sigmoid(gate)
        return _linear(e, self.dtype, "out")(a)


class SwiGLU(nn.Module):
    width: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, m):
        g = _linear(self.width, self.dtype, "gate")(m)
        u = _linear(self.width, self.dtype, "up")(m)
        return _linear(m.shape[-1], self.dtype, "down")(jax.nn.silu(g) * u)


def _take(x, rows):
    """``x[rows]`` for row indices known to lie inside ``x``: no pass
    over the result to fill what an index out of range would leave."""
    return x.at[rows].get(mode="promise_in_bounds")


def _row_of_pair(x, inverse):
    """``x``'s row for each pair: row ``inverse[p]`` of the sorted rows, a
    row of zeros for a pair sorted at or past ``len(x)``."""
    if x.shape[0] < inverse.shape[0]:
        x = jnp.pad(x, ((0, 1),) + ((0, 0),) * (x.ndim - 1))
        inverse = jnp.minimum(inverse, x.shape[0] - 1)
    return _take(x, inverse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rows_out(x, order, inverse, fan: int):
    """``x[order // fan]``: the token of the pair at each sorted row.
    ``order`` holds the first ``R`` entries of a permutation of the
    ``fan * len(x)`` pairs, ``inverse`` is that permutation's inverse. The
    gradient is a gather through ``inverse`` and a sum over each token's
    ``fan`` pairs, never a scatter."""
    return _take(x, order // fan)


def _rows_out_fwd(x, order, inverse, fan):
    return _rows_out(x, order, inverse, fan), inverse


def _rows_out_bwd(fan, inverse, g):
    back = _row_of_pair(g, inverse).reshape(-1, fan, g.shape[-1])
    return back.sum(1, dtype=jnp.float32).astype(g.dtype), None, None


_rows_out.defvjp(_rows_out_fwd, _rows_out_bwd)


@jax.custom_vjp
def _rows_back(x, order, inverse, weights):
    """``[n, d]``: every token's sum over its ``k`` pairs of the pair's
    row of ``x`` (sorted rows ``[R, d]``; ``order``, ``inverse`` as in
    :func:`_rows_out`) times its ``weights [n, k]``, summed in float32.
    The gradient stays in the sorted rows: nothing in it has a row a pair
    but the weights' own ``[n, k]``."""
    n, k = weights.shape
    per_pair = _row_of_pair(x, inverse).reshape(n, k, -1)
    return jnp.einsum("nkd,nk->nd", per_pair.astype(jnp.float32),
                      weights).astype(x.dtype)


def _rows_back_fwd(x, order, inverse, weights):
    return _rows_back(x, order, inverse, weights), (x, order, inverse, weights)


def _rows_back_bwd(res, g):
    x, order, inverse, weights = res
    g = _take(g, order // weights.shape[1]).astype(jnp.float32)
    w = _take(weights.reshape(-1), order)
    d_w = _row_of_pair(jnp.sum(g * x.astype(jnp.float32), -1), inverse)
    return ((g * w[:, None]).astype(x.dtype), None, None,
            d_w.reshape(weights.shape))


_rows_back.defvjp(_rows_back_fwd, _rows_back_bwd)


def route(m32, router_kernel, bias, per_token: int, route_scale: float):
    """(chosen ``[N, k]`` int32, weights ``[N, k]`` float32) over all the
    router's outputs; product, scores and top-k in float32."""
    logits = jnp.dot(m32, router_kernel.astype(jnp.float32), precision=_HI)
    scores = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), per_token)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = picked / (picked.sum(-1, keepdims=True) + 1e-20) * route_scale
    return chosen, weights


def held_pairs(chosen, offset: int, held: int):
    """Sort the (token, slot) pairs by the expert held here that they
    chose; pairs of absent experts go last. Returns (``order``: pair
    index at each sorted row, ``inverse``: sorted row of each pair,
    ``group_sizes [held]``)."""
    local = chosen.reshape(-1) - offset
    gid = jnp.where((local >= 0) & (local < held), local, held)
    iota = jnp.arange(gid.shape[0], dtype=jnp.int32)
    _, order = jax.lax.sort((gid, iota), num_keys=1, is_stable=True)
    _, inverse = jax.lax.sort((order, iota), num_keys=1)
    sizes = (gid[:, None] == jnp.arange(held)[None]).sum(0, dtype=jnp.int32)
    return order, inverse, sizes


def pair_rungs(pairs: int, held: int, total: int) -> tuple[int, ...]:
    """The row counts the routed part is built for, smallest first (the
    module header): twice what even routing sends to ``held`` of ``total``
    experts, in whole row tiles of the grouped product, twice that, and
    the worst case ``pairs``; each of the first two only where it is
    under the worst case."""
    rows = -(-2 * pairs * held // total)
    rows = round_up(rows, _row_tiles(rows, 1, 1)[0])
    return tuple(r for r in (rows, 2 * rows) if r < pairs) + (pairs,)


def rung_of(filled, rungs: Sequence[int]):
    """Index of the smallest of ``rungs`` (:func:`pair_rungs`) that holds
    ``filled`` rows; ``filled`` may be a device value."""
    return sum(filled > rows for rows in rungs[:-1])


def _routed_rows(rows: int, gated: bool, m, order, inverse, sizes, weights,
                 *mats):
    """The held experts' part of ``[n, d]`` tokens ``m``, computed over
    the first ``rows`` sorted pairs (``sum(sizes) <= rows``). ``mats`` are
    the experts' stacked leaves: gate, up and down of a ``gated`` expert
    (``silu(x W_gate) * (x W_up)``), else up and down (``relu(x W_up)^2``)."""
    with jax.named_scope(spans.MOE_ROUTE):
        order = order[:rows]
        x = _rows_out(m, order, inverse, weights.shape[1])
    with jax.named_scope(spans.MOE_EXPERTS):
        if gated:
            gate, up, down = mats
            act = jax.nn.silu(grouped_matmul(x, gate, sizes)) * \
                grouped_matmul(x, up, sizes)
        else:
            up, down = mats
            act = jnp.square(jax.nn.relu(grouped_matmul(x, up, sizes)))
        out = grouped_matmul(act, down, sizes)
    with jax.named_scope(spans.MOE_ROUTE):
        # rows of absent experts are zero, so their weights count for
        # the router's normalisation and for nothing here
        return _rows_back(out, order, inverse, weights)


@functools.lru_cache(maxsize=None)
def _rung(rows: int, gated: bool):
    """(forward, backward) of :func:`_routed_rows` at ``rows``, each under
    its own ``jit``: the backward makes the forward again and keeps
    nothing of it. One pair a rung and expert form, so a step traces and
    lowers a rung once for all its layers."""
    body = functools.partial(_routed_rows, rows, gated)

    def backward(operands, g):
        m, order, inverse, sizes, *floats = operands
        _, vjp = jax.vjp(lambda m, *floats: body(m, order, inverse, sizes,
                                                 *floats), m, *floats)
        return vjp(g)

    return jax.jit(body), jax.jit(backward)


def _switch(half: int, form, sizes, *args):
    """Run the smallest rung that holds ``sum(sizes)`` rows: its forward
    (``half`` 0) or backward (1); the index never leaves the device.
    ``form`` is ``(rungs, gated)``."""
    rungs, gated = form
    bodies = [_rung(rows, gated)[half] for rows in rungs]
    if len(bodies) == 1:
        return bodies[0](*args)
    return jax.lax.switch(rung_of(sizes.sum(), rungs), bodies, *args)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _routed_recomputed(form, m, order, inverse, sizes, *floats):
    """:func:`_routed_rows` over the rung of ``form``'s that ``sizes``
    select, recomputed in that rung by the backward pass."""
    return _switch(0, form, sizes, m, order, inverse, sizes, *floats)


def _routed_recomputed_fwd(form, *operands):
    return _routed_recomputed(form, *operands), operands


def _routed_recomputed_bwd(form, operands, g):
    sizes = operands[3]
    d_m, *d_floats = _switch(1, form, sizes, operands, g)
    return (d_m, None, None, None, *d_floats)


_routed_recomputed.defvjp(_routed_recomputed_fwd, _routed_recomputed_bwd)


class RoutedExperts(nn.Module):
    """The part of a routed layer that the experts held here give. An
    expert is ``W_down (silu(W_gate x) * (W_up x))``, three stacked
    leaves, or with ``gated`` off ``W_down relu(W_up x)^2``, two
    (models/nemotron_h.py's): two grouped products a pass where the gated
    form runs three."""

    width: int
    experts_total: int
    experts_held: int
    expert_offset: int
    per_token: int
    route_scale: float
    dtype: Any = jnp.float32
    remat: bool = False       # AfmoeLayer's: recompute each rung's body
    gated: bool = True

    @nn.compact
    def __call__(self, m32):
        n, d = m32.shape
        held, k = self.experts_held, self.per_token
        router = self.param("router", _INIT, (d, self.experts_total))
        bias = self.param("expert_bias", nn.initializers.zeros,
                          (self.experts_total,))
        wide = lambda name: self.param(name, _INIT, (held, d, self.width))
        mats = ((wide("gate"),) if self.gated else ()) + (
            wide("up"), self.param("down", _INIT, (held, self.width, d)))
        with jax.named_scope(spans.MOE_ROUTE):
            chosen, weights = route(m32, router, bias, k, self.route_scale)
            order, inverse, sizes = held_pairs(chosen, self.expert_offset,
                                               held)
        operands = (m32.astype(self.dtype), order, inverse, sizes, weights,
                    *mats)
        # without remat: kept rows of every rung would be the switch's outputs
        rungs = pair_rungs(n * k, held, self.experts_total) if self.remat \
            else (n * k,)
        # init makes every collection mutable: the weights stay alone
        if self.is_mutable_collection(spans.STEP_COUNTERS) \
                and not self.is_initializing():
            self._count(sizes, rungs)
        if not self.remat:
            return _routed_rows(n * k, self.gated, *operands)
        return _routed_recomputed((rungs, self.gated), *operands)

    def _count(self, sizes, rungs) -> None:
        """Sow what the step decides on the device (the module header):
        the pairs each held expert got, the rows of the rung they select,
        and the rungs themselves. Only a caller that made the collection
        mutable reaches this, so no other program has these values."""
        ladder = jnp.asarray(rungs, jnp.int32)
        for name, value in ((spans.MOE_PAIRS, sizes),
                            (spans.MOE_ROWS,
                             ladder[rung_of(sizes.sum(), rungs)]),
                            (spans.MOE_LADDER, ladder)):
            self.sow(spans.STEP_COUNTERS, name, value,
                     reduce_fn=lambda _, new: new, init_fn=lambda: None)


class AfmoeLayer(nn.Module):
    num_heads: int
    num_kv_heads: int
    head_dim: int
    layer_type: str
    window: int
    dense_width: int          # > 0: a dense layer of this width
    expert_width: int
    experts_total: int
    experts_held: int
    expert_offset: int
    experts_per_token: int
    shared_experts: int
    route_scale: float
    rope_theta: float = 10000.0
    eps: float = 1e-5
    attn: str = "auto"
    dtype: Any = jnp.float32
    remat: bool = False       # recompute the routed part in the backward

    @nn.compact
    def __call__(self, h):
        b, t, e = h.shape
        norm = lambda name: RMSNorm(self.eps, self.dtype, name=name)
        window = self.window if self.layer_type == "sliding_attention" \
            else None
        a = AfmoeAttention(
            self.num_heads, self.num_kv_heads, self.head_dim, window,
            self.rope_theta, self.eps, self.attn, self.dtype,
            name="attn")(norm("norm_in")(h))
        h = h + norm("norm_post_attn")(a)
        if self.dense_width:
            y = SwiGLU(self.dense_width, self.dtype,
                       name="mlp")(norm("norm_pre_mlp")(h))
            return h + norm("norm_post_mlp")(y)
        # the router reads the float32 norm, the experts its rounding
        m32 = RMSNorm(self.eps, jnp.float32, name="norm_pre_mlp")(h)
        with jax.named_scope(spans.MOE_SHARED):
            y = SwiGLU(self.expert_width * self.shared_experts, self.dtype,
                       name="shared")(m32.astype(self.dtype))
        # only the routed part's rows, one for every pair computed, are
        # too many to keep for the backward (the module header)
        y = y + RoutedExperts(
            self.expert_width, self.experts_total, self.experts_held,
            self.expert_offset, self.experts_per_token, self.route_scale,
            self.dtype, self.remat,
            name="experts")(m32.reshape(b * t, e)).reshape(b, t, e)
        return h + norm("norm_post_mlp")(y)


def _run_layers(h, first: int, layer_types, dense_layers: int, layer_kw):
    """Layers ``[first, first + len(layer_types))`` of the model, named
    ``layer<i>`` by their index in it (call inside a compact method).
    ``layer_kw`` are :class:`AfmoeLayer`'s fields as items; with its
    ``remat`` each expert layer's routed part is recomputed in the
    backward pass and nothing else is (the module header)."""
    kw = dict(layer_kw)
    for i, kind in enumerate(layer_types, start=first):
        dense = kw["dense_width"] if i < dense_layers else 0
        h = AfmoeLayer(**{**kw, "dense_width": dense, "layer_type": kind},
                       name=f"layer{i}")(h)
    return h


def afmoe_plan(mode: str = "split", dtype: Any = jnp.float32, *,
               vocab: int = 256, d_model: int = 64, num_heads: int = 4,
               num_kv_heads: int = 2, head_dim: int = 16,
               dense_width: int = 192, expert_width: int = 32,
               experts_total: int = 8, experts_held: Optional[int] = None,
               expert_offset: int = 0, experts_per_token: int = 2,
               shared_experts: int = 1, route_scale: float = 1.0,
               window: int = 8,
               layer_types: Sequence[str] = ("sliding_attention",) * 4
               + ("full_attention",),
               dense_layers: int = 1, client_depth: int = 1,
               rope_theta: float = 10000.0, rms_norm_eps: float = 1e-5,
               attn: str = "auto", remat: bool = True) -> SplitPlan:
    """Build the AFMoE :class:`SplitPlan` for ``mode``.

    The arguments carry the published names' values for the layers kept:
    ``layer_types`` one entry a layer, the first ``dense_layers`` of them
    dense (SwiGLU of ``dense_width``), the rest routed (``experts_held``
    of ``experts_total`` experts of ``expert_width`` from
    ``expert_offset`` on, ``experts_per_token`` a token, beside
    ``shared_experts`` shared ones). The client holds the embedding and
    the first ``client_depth`` layers. ``remat`` recomputes each expert
    layer's routed part in the backward pass, at the rows its routing
    fills, so that none of its rows is kept, and keeps everything else of
    a layer's forward (the module header)."""
    cut.check_attn(attn)
    layer_types = tuple(layer_types)
    bad = sorted(set(layer_types) - set(_LAYER_TYPES))
    if bad:
        raise ValueError(f"Unknown layer types {bad} (expected {_LAYER_TYPES})")
    held = cut.held_experts(experts_total, experts_held, expert_offset)
    cut.check_client_depth(client_depth, len(layer_types))
    cut.check_heads(num_heads, num_kv_heads)
    eps = float(rms_norm_eps)
    layer_kw = tuple(dict(
        num_heads=num_heads, num_kv_heads=num_kv_heads, head_dim=head_dim,
        window=window, dense_width=dense_width, expert_width=expert_width,
        experts_total=experts_total, experts_held=held,
        expert_offset=expert_offset, experts_per_token=experts_per_token,
        shared_experts=shared_experts, route_scale=float(route_scale),
        rope_theta=float(rope_theta), eps=eps, attn=attn,
        dtype=dtype, remat=bool(remat)).items())
    span = lambda first, kinds: (first, kinds, dense_layers, layer_kw)
    # the embedding times sqrt(d_model): the family's muP rule
    embed = cut.EmbedStage(
        vocab, d_model, _run_layers, span(0, layer_types[:client_depth]),
        dtype, d_model ** 0.5)
    return cut.split_plan(
        mode, embed, span(client_depth, layer_types[client_depth:]),
        cut.HeadStage(vocab, RMSNorm(eps, dtype), dtype))
