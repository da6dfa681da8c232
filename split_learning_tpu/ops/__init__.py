"""The kernel layer: the operations the models call beneath their layers.

The reference has no native components (SURVEY.md §2); here the layer
beneath Python is hand-written Pallas TPU kernels and explicitly
scheduled collectives. Each file offers one form of its operation and
chooses it from what it can see (shapes, types, the mask, a preflight
compile); nothing a user sets picks a kernel:

- :mod:`~split_learning_tpu.ops.flash_attention` — blockwise-streamed
  attention forward/backward kernels: VMEM-resident online softmax,
  O(T*D) HBM traffic per head instead of the dense path's O(T^2) score
  matrix; ``select_attention`` resolves ``attn="auto"``.
- :mod:`~split_learning_tpu.ops.ring_attention` — sequence/context-
  parallel attention (ring over ``ppermute``, Ulysses over
  ``all_to_all``); not a Pallas kernel but a collective op in the same
  slot.
- :mod:`~split_learning_tpu.ops.grouped_matmul` — a routed layer's
  grouped products.
- :mod:`~split_learning_tpu.ops.selective_scan`,
  :mod:`~split_learning_tpu.ops.ssd`,
  :mod:`~split_learning_tpu.ops.causal_conv` — the Mamba and Mamba-2
  recurrences and their convolution-and-silu, kernels where the shapes
  fill their tiles and plain ``jax.numpy`` at any other shape.

Kernels run compiled on TPU and through the Mosaic interpreter elsewhere
(:func:`~split_learning_tpu.ops.common.use_interpret`; tests use the
8-device CPU mesh, SURVEY.md §4 item 4).
"""

from split_learning_tpu.ops.common import use_interpret
from split_learning_tpu.ops.flash_attention import (
    flash_attention, flash_attention_with_lse, select_attention)
from split_learning_tpu.ops.ring_attention import (
    full_attention,
    ring_attention,
    ulysses_attention,
)

__all__ = [
    "use_interpret",
    "flash_attention",
    "flash_attention_with_lse",
    "select_attention",
    "full_attention",
    "ring_attention",
    "ulysses_attention",
]
