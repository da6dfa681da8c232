"""A looped language model (Ouro) as the program builds it, from the
configuration's ``plan.kwargs`` (the published names' values; ``layers`` the
layers kept, ``passes`` how often the stack runs): a layer application's
four attention projections and three SwiGLU products and every pass's head
are matrix products; the embedding lookup, the norms, the rotary turns, the
exit gate (one product of width 1 a pass) and the objective are not.

**The loop multiplies the work, not the weights**: a token meets every kept
layer ``passes`` times and the head once a pass, so the count is ``passes``
times one pass's, whatever the parameter tree holds.  Attention is counted
at the keys a query sees, at the true head width.  Recomputed work
(``remat_mlp_passes``: the ``gate`` and ``up`` products of the first passes
again in the backward pass) is not counted.
"""

from __future__ import annotations

# the flash kernels over the heads the configuration gives (16 of 128 on 16,
# group 1) are that family's, costed the same way
from .afmoe import _kw, attention_shape, attn_bwd, attn_fwd, keys_seen  # noqa: F401
# four projections and no gate: q and out over the query heads, k and v over
# the key/value heads
from .lfm2_moe import attention_params


def layer_matmul_params(kw: dict) -> int:
    """Weights a token meets in one application of one layer."""
    return attention_params(kw) + 3 * kw["d_model"] * kw["width"]


def layer_applications(config: dict) -> int:
    """How often a step's forward runs a layer: the layers kept times the
    passes.  The flash forward runs as often, unless a ``remat`` makes a
    pass's attention again."""
    kw = _kw(config)
    return kw["layers"] * kw["passes"]


def forward_flops_per_token(config: dict, t: int) -> float:
    kw = _kw(config)
    one_pass = kw["layers"] * layer_matmul_params(kw) + kw["d_model"] * kw["vocab"]
    per_key = 2 * 2 * kw["num_heads"] * kw["head_dim"]   # QK^T and PV
    return kw["passes"] * 2.0 * one_pass \
        + layer_applications(config) * per_key * keys_seen(t, None)


def train_flops_per_token(config: dict, t: int) -> float:
    """Forward plus backward: a product's backward is two products."""
    return 3.0 * forward_flops_per_token(config, t)


def model_params(kw: dict, layers: int, vocab: int) -> int:
    """Every parameter of a model of ``layers`` layers and ``vocab`` rows:
    the products' weights, four norms a layer and the final one, the exit
    gate with its bias, the embedding and the untied head.  The loop adds
    none."""
    d = kw["d_model"]
    return layers * (layer_matmul_params(kw) + 4 * d) + 2 * d * vocab + d + d + 1
