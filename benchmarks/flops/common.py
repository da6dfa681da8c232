"""Operations and bytes from shapes, for the model and for its kernels.

A product of an [m, k] by a [k, n] matrix is 2*m*k*n operations.  The
backward pass of a product costs two products, so forward plus backward is
three times the forward.  Causal attention is counted at half: the masked
half of the score matrix need not be computed.  Recomputed work (the party
paths' ``stage_backward`` runs a stage's forward twice) is not counted.
"""

from __future__ import annotations


# bytes an element of the plan's ``dtype`` takes where a kernel reads it as stored
ITEMSIZE = {"bfloat16": 2, "float32": 4}


def block_matmul_params(d_model: int, mlp_ratio: int = 4) -> int:
    """Weights of one block that sit in a matrix product: q, k, v, out
    (4 d^2) and the MLP's two (2 * ratio * d^2)."""
    return 4 * d_model * d_model + 2 * mlp_ratio * d_model * d_model


def attention_flops_per_token(t: int, d_model: int, causal: bool) -> float:
    """Forward operations per token of one layer's QK^T and PV: each is
    2 * t * d_model per token over all heads; half of that if causal."""
    full = 2 * 2 * t * d_model
    return full / 2 if causal else full


def train_flops_per_token(matmul_params: int, layers: int, t: int,
                          d_model: int, causal: bool) -> float:
    """Forward plus backward operations per token of the whole model."""
    fwd = 2 * matmul_params + layers * attention_flops_per_token(t, d_model, causal)
    return 3.0 * fwd


def flash_fwd(batch: int, heads: int, t: int, head_dim: int, causal: bool,
              itemsize: int = 2) -> tuple:
    """(operations, bytes) the forward attention of one call needs: QK^T
    and PV at the true head size; q, k, v read and o written once."""
    ops = 2 * 2 * batch * heads * t * t * head_dim * (0.5 if causal else 1.0)
    moved = 4 * batch * heads * t * head_dim * itemsize
    return ops, moved


def flash_bwd(batch: int, heads: int, t: int, head_dim: int, causal: bool,
              itemsize: int = 2) -> tuple:
    """(operations, bytes) of the backward: five products (S again, dP, dV,
    dK, dQ) where the forward has two; q, k, v, o, do read and dq, dk, dv
    written once."""
    ops = 5 * 2 * batch * heads * t * t * head_dim * (0.5 if causal else 1.0)
    moved = 8 * batch * heads * t * head_dim * itemsize
    return ops, moved


def conv_silu_fwd(batch: int, t: int, channels: int, taps: int,
                  x_itemsize: int, y_itemsize: int) -> tuple:
    """(operations, bytes) of one forward call of ``silu(bias + causal
    depthwise convolution)`` (``ops/causal_conv.py``): ``x`` read and ``y``
    written once over ``[t, channels]``, each in its stored type; the taps
    and the bias are ``taps + 1`` rows.  A tap is a multiply and an add, the
    silu about four more: none of it a matrix product, so the bytes bind."""
    elements = batch * t * channels
    return ((2 * taps + 4.0) * elements,
            elements * (x_itemsize + y_itemsize) + (taps + 1) * channels * 4)


def conv_silu_bwd(batch: int, t: int, channels: int, taps: int,
                  x_itemsize: int, y_itemsize: int) -> tuple:
    """The backward call: ``dy`` (in ``y``'s type) and ``x`` read, ``dx``
    written in ``x``'s type, once over ``[t, channels]``; the pre-activation
    is made again in registers, and the taps' and the bias's gradients leave
    as float32 sums by sublane, ``8 (taps + 1)`` rows."""
    elements = batch * t * channels
    return ((6 * taps + 10.0) * elements,
            elements * (2 * x_itemsize + y_itemsize) + 9 * (taps + 1) * channels * 4)


def least_seconds(ops: float, moved: float, peak: dict) -> tuple:
    """The roofline: (least time, which bound binds)."""
    by_ops, by_bytes = ops / peak["bf16_flops_per_s"], moved / peak["hbm_bytes_per_s"]
    return (by_ops, "compute") if by_ops >= by_bytes else (by_bytes, "memory")
