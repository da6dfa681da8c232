"""AFMoE (Trinity) as the program builds it, from the configuration's
``plan.kwargs`` (the published names' values for the layers kept): the
projections, the dense and shared SwiGLUs, the router, the head and the
routed experts are matrix products; the embedding lookup and the pairs'
sort and gathers are not.

The routed experts are counted **at the expected number of pairs under even
routing**: a token sends ``experts_per_token`` pairs to ``experts_total``
experts, of which ``experts_held`` are here, so ``per_token * held / total``
pairs a token reach this chip.  What a run really routes here differs by
seed, layer and step (the step's ``pairs`` counter says it, and
``moe_pairs_x_even_p50`` reads it); the model count stays at the
expectation, so that the same work is asked of every run, and
``moe_expert_mm_roofline_pct`` costs the grouped products at the pairs held.
Recomputed work (each layer's forward again in the backward pass) is not
counted.
"""

from __future__ import annotations


def _kw(config: dict) -> dict:
    return config["plan"]["kwargs"]


def keys_seen(t: int, window: int | None) -> float:
    """Mean number of keys a query sees in a causal sequence of ``t``: query
    i sees ``i + 1``, or ``min(i + 1, window)`` under a window."""
    if window is None or window >= t:
        return (t + 1) / 2
    return window - window * (window - 1) / (2 * t)


def attention_params(kw: dict) -> int:
    """q, output gate and out over all query heads; k and v over the
    key/value heads."""
    wide = kw["num_heads"] * kw["head_dim"]
    narrow = kw["num_kv_heads"] * kw["head_dim"]
    return kw["d_model"] * (3 * wide + 2 * narrow)


def expected_pairs_per_token(kw: dict) -> float:
    return kw["experts_per_token"] * kw["experts_held"] / kw["experts_total"]


def layer_matmul_params(kw: dict, dense: bool) -> float:
    """Weights a token meets in one layer's matrix products."""
    d = kw["d_model"]
    if dense:
        return attention_params(kw) + 3 * d * kw["dense_width"]
    one_expert = 3 * d * kw["expert_width"]
    return (attention_params(kw) + kw["shared_experts"] * one_expert
            + d * kw["experts_total"] + expected_pairs_per_token(kw) * one_expert)


def forward_flops_per_token(config: dict, t: int) -> float:
    kw = _kw(config)
    per_key = 2 * 2 * kw["num_heads"] * kw["head_dim"]   # QK^T and PV
    total = 2.0 * kw["d_model"] * kw["vocab"]
    for i, kind in enumerate(kw["layer_types"]):
        window = kw["window"] if kind == "sliding_attention" else None
        total += 2.0 * layer_matmul_params(kw, i < kw["dense_layers"])
        total += per_key * keys_seen(t, window)
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
    """QK^T and PV over the keys each query sees; q read and o written over
    the query heads, k and v read over the key/value heads, once."""
    ops = 2 * 2 * batch * heads * head_dim * t * keys_seen(t, window)
    moved = (2 * heads + 2 * kv_heads) * batch * t * head_dim * itemsize
    return ops, moved


def attn_bwd(batch: int, heads: int, kv_heads: int, t: int, head_dim: int,
             window: int | None, itemsize: int = 2) -> tuple:
    """Five products (S again, dP, dV, dK, dQ) where the forward has two;
    q, o, do read and dq written over the query heads, k, v read and dk, dv
    written over the key/value heads."""
    ops = 5 * 2 * batch * heads * head_dim * t * keys_seen(t, window)
    moved = (4 * heads + 4 * kv_heads) * batch * t * head_dim * itemsize
    return ops, moved


def expert_mm_shape(config: dict, rows: int, t: int) -> dict:
    kw = _kw(config)
    return dict(pairs=rows * t * expected_pairs_per_token(kw),
                experts=kw["experts_held"], d_model=kw["d_model"],
                width=kw["expert_width"])


def expert_mm(pairs: float, experts: int, d_model: int, width: int,
              weight_itemsize: int = 2, itemsize: int = 2) -> tuple:
    """One grouped product over the experts held, at ``pairs`` rows: every
    one of a layer's nine (gate, up and down; forward, the rows' gradient
    and the weights' gradient) is ``2 * pairs * d_model * width``
    operations, reads or writes the pairs' rows on both sides and every held
    expert's matrix once (the weights' gradient leaves in float32:
    ``weight_itemsize`` 4)."""
    ops = 2.0 * pairs * d_model * width
    moved = pairs * (d_model + width) * itemsize + experts * d_model * width * weight_itemsize
    return ops, moved
