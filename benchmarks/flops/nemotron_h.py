"""Nemotron-H (Nemotron-Labs-TwoTower-30B-A3B's tower) as the program builds
it, from the configuration's ``plan.kwargs`` (the published names' values;
the layers built are the published indices ``layers_kept``, each one mixer
by its letter of ``pattern``): a Mamba-2 layer's two projections and **the
four batched products of its recurrence's chunked form**, an attention
layer's four projections, the shared expert, the router, the routed experts
and the head are matrix products; the embedding lookup, the norms, the
convolution, the decays' exponentials, the carried states' scan and the
pairs' sort and gathers are not.

Attention is counted at the keys a query sees.  The routed experts (ungated:
two products an expert) are counted at the expected number of pairs under
even routing, as ``flops/afmoe.py`` counts them.  The chunked form is counted
as it is computed: every token against its whole chunk, not the causal half.
Recomputed work (``remat``: the routed part again in the backward pass) is
not counted.
"""

from __future__ import annotations

# the kernels, (operations, bytes) of one call: the flash kernels over grouped
# heads and the grouped products over the experts held are that family's,
# costed the same way at this configuration's widths
from .afmoe import (  # noqa: F401
    _kw, attention_shape, attn_bwd, attn_fwd, expected_pairs_per_token,
    expert_mm, expert_mm_shape, keys_seen)
# q, k, v and out with no gate beside them, as that family's
from .lfm2_moe import attention_params  # noqa: F401
# the convolution's two kernel passes, costed by their bytes
from .common import ITEMSIZE, conv_silu_bwd, conv_silu_fwd  # noqa: F401


def mamba_params(kw: dict) -> int:
    """``W_in`` (d -> z, xBC and dt) and ``W_out`` (d_inner -> d)."""
    inner = kw["mamba_heads"] * kw["mamba_head_dim"]
    wide = inner + 2 * kw["ssm_groups"] * kw["ssm_state"]
    return kw["d_model"] * (inner + wide + kw["mamba_heads"]) + inner * kw["d_model"]


def layer_matmul_params(kw: dict, index: int, experts_met: float) -> float:
    """Weights a token meets in the matrix products of published layer
    ``index``, of whose routed experts it meets ``experts_met``."""
    d = kw["d_model"]
    kind = kw["pattern"][index]
    if kind == "M":
        return mamba_params(kw)
    if kind == "*":
        return attention_params(kw)
    return (d * kw["experts_total"] + 2 * d * kw["shared_width"]
            + experts_met * 2 * d * kw["expert_width"])


def ssd_products(batch: int, t: int, heads: int, head_dim: int, groups: int,
                 state: int, chunk: int) -> float:
    """Forward operations of one layer's chunked recurrence: the scores ``C
    B^T`` once a group (``chunk x state`` a token), their product with the
    chunk's inputs, the state a chunk adds and what the carried state gives
    back, once a head."""
    scores = 2.0 * groups * chunk * state
    inside = 2.0 * heads * chunk * head_dim
    states = 2.0 * heads * head_dim * state
    return batch * t * (scores + inside + 2 * states)


def ssd_shape(config: dict, rows: int, t: int) -> dict:
    kw = _kw(config)
    return dict(batch=rows, t=t, heads=kw["mamba_heads"], head_dim=kw["mamba_head_dim"],
                groups=kw["ssm_groups"], state=kw["ssm_state"], chunk=kw["chunk"])


def ssd_fwd(batch: int, t: int, heads: int, head_dim: int, groups: int,
            state: int, chunk: int, itemsize: int = 2) -> tuple:
    """(operations, bytes) of one layer's forward call (``ops/ssd.py``'s
    ``ssd_fwd``): :func:`ssd_products`; ``x`` ``[t, heads x head_dim]``, ``B``
    and ``C`` ``[t, groups x state]`` read as stored, ``dt`` and the summed
    decays (in two layouts) ``[t, heads]`` in float32, ``y`` written in
    float32 and every chunk's first state ``[state, head_dim]`` a head as
    stored."""
    tokens, inner = batch * t, heads * head_dim
    wide = tokens * (inner + 2 * groups * state) * itemsize
    small = 3 * tokens * heads * 4
    moved = wide + small + tokens * inner * 4 + tokens // chunk * inner * state * itemsize
    return ssd_products(batch, t, heads, head_dim, groups, state, chunk), moved


def ssd_bwd(batch: int, t: int, heads: int, head_dim: int, groups: int,
            state: int, chunk: int, itemsize: int = 2) -> tuple:
    """The backward call (``ssd_bwd``): it makes the scores and the masked
    product again and runs ten products where the forward runs four, 2.5
    times :func:`ssd_products`; it reads what the forward read and the kept
    states, ``dy`` in float32 in ``y``'s place, and writes ``dx``, ``dB``,
    ``dC`` as stored, the three ``[t, heads]`` gradients in float32 and a
    chunk's last decay's ``[heads x head_dim]`` a chunk."""
    tokens, inner = batch * t, heads * head_dim
    wide = tokens * (inner + 2 * groups * state) * itemsize
    small = 3 * tokens * heads * 4
    moved = (2 * wide + 2 * small + tokens * inner * 4
             + tokens // chunk * inner * (state * itemsize + 4))
    return 2.5 * ssd_products(batch, t, heads, head_dim, groups, state, chunk), moved


def conv_silu_shape(config: dict, rows: int, t: int) -> dict:
    """A Mamba-2 layer's convolution runs over ``xBC``, the first product's
    output in the plan's type, and hands it back in that type."""
    kw = _kw(config)
    itemsize = ITEMSIZE[config["plan"]["dtype"]]
    return dict(batch=rows, t=t, taps=kw["conv_taps"], x_itemsize=itemsize,
                y_itemsize=itemsize, channels=kw["mamba_heads"] * kw["mamba_head_dim"]
                + 2 * kw["ssm_groups"] * kw["ssm_state"])


def layers_of(kw: dict, letter: str) -> int:
    return sum(kw["pattern"][i] == letter for i in kw["layers_kept"])


def forward_flops_per_token(config: dict, t: int) -> float:
    kw = _kw(config)
    weights = kw["d_model"] * kw["vocab"] + sum(
        layer_matmul_params(kw, i, expected_pairs_per_token(kw))
        for i in kw["layers_kept"])
    per_key = 2 * 2 * kw["num_heads"] * kw["head_dim"]   # QK^T and PV
    return (2.0 * weights + layers_of(kw, "*") * per_key * keys_seen(t, None)
            + layers_of(kw, "M") * ssd_products(**ssd_shape(config, 1, 1)))


def train_flops_per_token(config: dict, t: int) -> float:
    """Forward plus backward: a product's backward is two products."""
    return 3.0 * forward_flops_per_token(config, t)


def model_params(kw: dict, layers, experts: float, vocab: int,
                 embedding: bool = True) -> float:
    """Every parameter of the published layers ``layers`` with ``experts``
    routed experts a layer and ``vocab`` rows (the untied head, the
    embedding unless left out): the products' weights, a Mamba-2 layer's
    taps, biases, ``dt_bias``, ``A_log``, ``D`` and grouped norm, the
    selection bias and the norms' scales."""
    d = kw["d_model"]
    total = (2 if embedding else 1) * d * vocab + d      # head, final norm
    for i in layers:
        total += layer_matmul_params(kw, i, experts) + d
        if kw["pattern"][i] == "M":
            inner = kw["mamba_heads"] * kw["mamba_head_dim"]
            wide = inner + 2 * kw["ssm_groups"] * kw["ssm_state"]
            total += (kw["conv_taps"] + 1) * wide + 3 * kw["mamba_heads"] + inner
        elif kw["pattern"][i] == "E":
            total += kw["experts_total"]
    return total
