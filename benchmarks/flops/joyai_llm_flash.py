"""JoyAI-LLM-Flash (latent attention, routed experts, one multi-token-
prediction module) as the program builds it, from the configuration's
``plan.kwargs`` (the published names' values for the layers kept): the
projections, the SwiGLUs, the router, the head (twice: the module's logits go
through it too), ``W_eh`` and the routed experts are matrix products; the two
embedding lookups, the norms, the rotary turns and the pairs' sort and
gathers are not.

Attention is counted **at the true widths**: QK^T at ``qk_nope_head_dim +
qk_rope_head_dim`` (192) and PV at ``v_head_dim`` (128), at the keys a query
sees, whatever the kernel pads.  The routed experts are counted at the
expected number of pairs under even routing, as ``flops/afmoe.py`` counts
them.  The module runs every position (the last one's logits are masked out
of the loss, not left out of the products).  Recomputed work (``remat``: the
routed part again in the backward pass) is not counted.
"""

from __future__ import annotations

# the grouped products over the experts held are afmoe's (the routed layer is
# that family's class), costed the same way at this configuration's 2048 x 768
from .afmoe import expert_mm, expert_mm_shape  # noqa: F401


def _kw(config: dict) -> dict:
    return config["plan"]["kwargs"]


def keys_seen(t: int) -> float:
    """Mean number of keys a query sees in a causal sequence of ``t``."""
    return (t + 1) / 2


def attention_params(kw: dict) -> int:
    """The two down-projections, the two up-projections and the output."""
    d, heads = kw["d_model"], kw["num_heads"]
    qk = kw["qk_nope_head_dim"] + kw["qk_rope_head_dim"]
    return (d * kw["q_lora_rank"] + kw["q_lora_rank"] * heads * qk
            + d * (kw["kv_lora_rank"] + kw["qk_rope_head_dim"])
            + kw["kv_lora_rank"] * heads * (kw["qk_nope_head_dim"] + kw["v_head_dim"])
            + heads * kw["v_head_dim"] * d)


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


def attention_flops_per_key(kw: dict) -> int:
    """QK^T and PV of all heads for one (query, key)."""
    return 2 * kw["num_heads"] * (kw["qk_nope_head_dim"] + kw["qk_rope_head_dim"]
                                  + kw["v_head_dim"])


def forward_flops_per_token(config: dict, t: int) -> float:
    kw = _kw(config)
    d, head = kw["d_model"], kw["d_model"] * kw["vocab"]
    weights = head + sum(layer_matmul_params(kw, i < kw["dense_layers"])
                         for i in range(kw["layers"]))
    blocks = kw["layers"]
    if kw.get("mtp_layers", 1):
        # [embedding ; state] W_eh, one more expert layer, the head again
        weights += 2 * d * d + layer_matmul_params(kw, False) + head
        blocks += 1
    return 2.0 * weights + blocks * attention_flops_per_key(kw) * keys_seen(t)


def train_flops_per_token(config: dict, t: int) -> float:
    """Forward plus backward: a product's backward is two products."""
    return 3.0 * forward_flops_per_token(config, t)


# -- the kernels: (operations, bytes) of one call -------------------------- #

def attention_shape(config: dict, rows: int, t: int) -> dict:
    kw = _kw(config)
    return dict(batch=rows, heads=kw["num_heads"], t=t,
                qk_dim=kw["qk_nope_head_dim"] + kw["qk_rope_head_dim"],
                v_dim=kw["v_head_dim"])


def attn_fwd(batch: int, heads: int, t: int, qk_dim: int, v_dim: int,
             itemsize: int = 2) -> tuple:
    """QK^T at ``qk_dim`` and PV at ``v_dim`` over the keys each query sees;
    q and k read at ``qk_dim``, v read and o written at ``v_dim``, once."""
    ops = 2 * (qk_dim + v_dim) * batch * heads * t * keys_seen(t)
    moved = 2 * (qk_dim + v_dim) * batch * heads * t * itemsize
    return ops, moved


def attn_bwd(batch: int, heads: int, t: int, qk_dim: int, v_dim: int,
             itemsize: int = 2) -> tuple:
    """S again, dK and dQ at ``qk_dim``, dP and dV at ``v_dim``; q, k read
    and dq, dk written at ``qk_dim``, v, o, do read and dv written at
    ``v_dim``."""
    ops = 2 * (3 * qk_dim + 2 * v_dim) * batch * heads * t * keys_seen(t)
    moved = 4 * (qk_dim + v_dim) * batch * heads * t * itemsize
    return ops, moved
