"""Phi-4-mini-flash (SambaY) as the program builds it, from the
configuration's ``plan.kwargs`` (the published names' values; ``layers_kept``
are published indices, and a layer's kind follows its index): every
projection, the MLPs and the head are matrix products; the two softmax maps
of a differential attention are counted at the keys a query sees, QK^T at
the head size and PV at twice it (the paired values).  **Left out of the
model count**: the selective scan's elementwise work (about 10 operations a
channel, state and token: 8.4 GFLOP a sequence of 8192 forward, under 0.1 %
of the products, and none of it can run on the MXU that the peak describes),
the convolution, the norms, the gates, the embedding lookup.  Recomputed work
(each MLP's forward again in the backward pass) is not counted.
"""

from __future__ import annotations

from .afmoe import keys_seen  # mean keys a causal query sees, windowed or not
# the convolution's two kernel passes, costed by their bytes
from .common import ITEMSIZE, conv_silu_bwd, conv_silu_fwd  # noqa: F401


def _kw(config: dict) -> dict:
    return {"layers_published": 32, "mb_per_layer": 2, "expand": 2,
            **config["plan"]["kwargs"]}


def layer_kind(i: int, published: int, mb_per_layer: int) -> str:
    half = published // 2
    ssm = i % mb_per_layer == 0
    if i >= half + 2:
        return "gmu" if ssm else "cross"
    if ssm:
        return "mamba"
    return "window" if i < half else "full"


def mixer_matmul_params(kw: dict, kind: str) -> int:
    """Weights a token meets in one mixer's matrix products."""
    d, inner = kw["d_model"], kw["expand"] * kw["d_model"]
    wide = kw["num_heads"] * kw["head_dim"]
    narrow = kw["num_kv_heads"] * kw["head_dim"]
    if kind == "mamba":          # in, x, dt, out
        return (d * 2 * inner + inner * (kw["dt_rank"] + 2 * kw["d_state"])
                + kw["dt_rank"] * inner + inner * d)
    if kind == "gmu":
        return 2 * d * inner
    if kind == "cross":          # q and out
        return d * wide + wide * d
    return d * (wide + 2 * narrow) + wide * d


def attention_flops_per_key(kw: dict) -> int:
    """Both maps, all query heads, forward: QK^T at ``head_dim`` and PV at
    twice it for each of ``num_heads`` (query head, key) pairs."""
    return kw["num_heads"] * 2 * (kw["head_dim"] + 2 * kw["head_dim"])


def forward_flops_per_token(config: dict, t: int) -> float:
    kw = _kw(config)
    mlp = 3 * kw["d_model"] * kw["mlp_width"]
    total = 2.0 * kw["d_model"] * kw["vocab"]
    for i in kw["layers_kept"]:
        kind = layer_kind(i, kw["layers_published"], kw["mb_per_layer"])
        total += 2.0 * (mixer_matmul_params(kw, kind) + mlp)
        if kind in ("window", "full", "cross"):
            window = kw["window"] if kind == "window" else None
            total += attention_flops_per_key(kw) * keys_seen(t, window)
    return total


def train_flops_per_token(config: dict, t: int) -> float:
    """Forward plus backward: a product's backward is two products."""
    return 3.0 * forward_flops_per_token(config, t)


# -- the kernels: (operations, bytes) of one call -------------------------- #

def attention_shape(config: dict, rows: int, t: int) -> dict:
    kw = _kw(config)
    return dict(batch=rows, heads=kw["num_heads"], kv_heads=kw["num_kv_heads"],
                t=t, head_dim=kw["head_dim"])


def attn_fwd(batch: int, heads: int, kv_heads: int, t: int, head_dim: int,
             window: int | None, itemsize: int = 2) -> tuple:
    """One call serves both maps: ``heads`` (query head, key) products of
    QK^T at ``head_dim`` and of PV at ``2 * head_dim``, over the keys each
    query sees; q read at ``head_dim`` and o written at twice it over the
    query heads, k read at ``head_dim`` over the key/value heads and the
    paired values at twice it over half of them, once."""
    ops = 2 * 3 * head_dim * batch * heads * t * keys_seen(t, window)
    moved = (3 * heads + 2 * kv_heads) * batch * t * head_dim * itemsize
    return ops, moved


def attn_bwd(batch: int, heads: int, kv_heads: int, t: int, head_dim: int,
             window: int | None, itemsize: int = 2) -> tuple:
    """S again, dK and dQ at ``head_dim``, dP and dV at twice it; q, o, do
    read and dq written over the query heads, k and the paired values read
    and their gradients written over the key/value heads."""
    ops = 2 * 7 * head_dim * batch * heads * t * keys_seen(t, window)
    moved = (6 * heads + 4 * kv_heads) * batch * t * head_dim * itemsize
    return ops, moved


def conv_silu_shape(config: dict, rows: int, t: int) -> dict:
    """The Mamba layer's convolution reads ``x``, half of the first
    product's output in the plan's type, and hands the scan float32."""
    kw = _kw(config)
    return dict(batch=rows, t=t, channels=kw["expand"] * kw["d_model"],
                taps=kw["d_conv"], x_itemsize=ITEMSIZE[config["plan"]["dtype"]],
                y_itemsize=4)


def scan_shape(config: dict, rows: int, t: int) -> dict:
    kw = _kw(config)
    return dict(batch=rows, t=t, d_inner=kw["expand"] * kw["d_model"],
                d_state=kw["d_state"])


def scan_fwd(batch: int, t: int, d_inner: int, d_state: int) -> tuple:
    """The recurrence forward, float32: a channel, state and token take an
    exponential, four multiplies and two adds; x and delta read and y
    written once over ``[t, d_inner]``, B and C over ``[t, d_state]``, A
    once."""
    ops = 7.0 * batch * t * d_inner * d_state
    moved = 4 * (batch * t * (3 * d_inner + 2 * d_state) + d_inner * d_state)
    return ops, moved


def scan_bwd(batch: int, t: int, d_inner: int, d_state: int) -> tuple:
    """The backward: the forward's states made again (7) and about 15 more
    a channel, state and token; x, delta and dy read, dx and ddelta written
    over ``[t, d_inner]``, B, C and their gradients over ``[t, d_state]``, A
    and its gradient once."""
    ops = 22.0 * batch * t * d_inner * d_state
    moved = 4 * (batch * t * (5 * d_inner + 4 * d_state) + 2 * d_inner * d_state)
    return ops, moved
