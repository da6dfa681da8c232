"""LFM2-MoE (LFM2-24B-A2B) as the program builds it, from the
configuration's ``plan.kwargs`` (the published names' values; the layers
built are the published indices ``layers_kept``): a ``conv`` operator's two
projections, an attention operator's four, the dense SwiGLU, the router, the
routed experts and the head are matrix products; the embedding lookup, the
norms, the rotary turns, **the convolution's gates and taps** (seven
operations a channel and token) and the pairs' sort and gathers are not.

Attention is counted **at the true head width** (64), at the keys a query
sees, whatever the kernel pads.  The routed experts are counted at the
expected number of pairs under even routing, as ``flops/afmoe.py`` counts
them.  Recomputed work (``remat``: the routed part again in the backward
pass) is not counted.
"""

from __future__ import annotations

# the kernels, (operations, bytes) of one call: the flash kernels over grouped
# heads at the width the configuration gives (here 64) and the grouped
# products over the experts held are that family's, costed the same way
from .afmoe import (  # noqa: F401
    _kw, attention_shape, attn_bwd, attn_fwd, expected_pairs_per_token,
    expert_mm, expert_mm_shape, keys_seen)


def conv_params(kw: dict) -> int:
    """``W_in`` (d -> 3 d) and ``W_out`` (d -> d)."""
    return 4 * kw["d_model"] * kw["d_model"]


def attention_params(kw: dict) -> int:
    """q and out over all query heads; k and v over the key/value heads."""
    wide = kw["num_heads"] * kw["head_dim"]
    narrow = kw["num_kv_heads"] * kw["head_dim"]
    return kw["d_model"] * (2 * wide + 2 * narrow)


def layer_matmul_params(kw: dict, index: int, experts_met: float) -> float:
    """Weights a token meets in the matrix products of published layer
    ``index``, of whose routed experts it meets ``experts_met``."""
    d = kw["d_model"]
    operator = conv_params(kw) if kw["layer_types"][index] == "conv" \
        else attention_params(kw)
    if index < kw["dense_layers"]:
        return operator + 3 * d * kw["dense_width"]
    return operator + d * kw["experts_total"] + experts_met * 3 * d * kw["expert_width"]


def attention_layers(kw: dict) -> int:
    return sum(kw["layer_types"][i] == "full_attention" for i in kw["layers_kept"])


def forward_flops_per_token(config: dict, t: int) -> float:
    kw = _kw(config)
    weights = kw["d_model"] * kw["vocab"] + sum(
        layer_matmul_params(kw, i, expected_pairs_per_token(kw))
        for i in kw["layers_kept"])
    per_key = 2 * 2 * kw["num_heads"] * kw["head_dim"]   # QK^T and PV
    return 2.0 * weights + attention_layers(kw) * per_key * keys_seen(t, None)


def train_flops_per_token(config: dict, t: int) -> float:
    """Forward plus backward: a product's backward is two products."""
    return 3.0 * forward_flops_per_token(config, t)


def model_params(kw: dict, layers, experts: int, vocab: int, tied: bool = False) -> float:
    """Every parameter of the published layers ``layers`` with ``experts``
    routed experts a layer and ``vocab`` rows: the products' weights, the
    convolutions' taps, the selection bias and the norms' scales."""
    d = kw["d_model"]
    total = (1 if tied else 2) * d * vocab + d           # embedding, head, final norm
    for i in layers:
        total += layer_matmul_params(kw, i, experts) + 2 * d
        if kw["layer_types"][i] == "conv":
            total += kw["conv_taps"] * d
        else:
            total += 2 * kw["head_dim"]
        if i >= kw["dense_layers"]:
            total += kw["experts_total"]
    return total
