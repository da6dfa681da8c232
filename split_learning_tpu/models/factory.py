"""Model factory — role+mode dispatch, mirroring the reference's `get_model`.

Reference (``src/model_def.py:49-71``): federated → `FullModel` for both
roles; split → `ModelPartA` for client / `ModelPartB` for server; unknown
mode → ``ValueError``. Here the factory returns a :class:`SplitPlan` plus
the stage indices the role owns — the "model" is always the plan; a party
just owns a subset of stages.
"""

from __future__ import annotations

from typing import Any, Tuple

import jax.numpy as jnp

from split_learning_tpu.core.stage import SplitPlan
from split_learning_tpu.models.cnn import (
    chain3_cnn_plan, split_cnn_plan, u_split_cnn_plan)

_FAMILIES = {}


def register_model(name: str):
    def deco(fn):
        _FAMILIES[name] = fn
        return fn
    return deco


def _dtype_of(dtype: Any) -> Any:
    if isinstance(dtype, str):
        return jnp.dtype(dtype)
    return dtype


@register_model("split_cnn")
def _split_cnn(mode: str, dtype: Any, **kw: Any) -> SplitPlan:
    if kw:
        raise ValueError(f"split_cnn is the fixed reference architecture "
                         f"(src/model_def.py:5-28); it takes no size "
                         f"overrides (got {sorted(kw)})")
    if mode == "u_split":
        return u_split_cnn_plan(dtype=dtype)
    # both "split" and "federated" use the same 2-stage plan: federated mode
    # trains the composition (the reference's FullModel, src/model_def.py:31-46)
    return split_cnn_plan(dtype=dtype)


@register_model("split_cnn_chain3")
def _split_cnn_chain3(mode: str, dtype: Any, **kw: Any) -> SplitPlan:
    """The reference CNN as a 3-stage MPMD pipeline chain (PR 14):
    client(A) → stage(trunk) → stage(head), two wire cuts. Served by
    runtime/stage.py StageRuntime parties and driven by
    runtime/pipeline_runner.py."""
    if kw:
        raise ValueError(f"split_cnn_chain3 is the fixed reference "
                         f"architecture re-cut; it takes no size "
                         f"overrides (got {sorted(kw)})")
    if mode != "split":
        raise ValueError("split_cnn_chain3 is a pipeline chain plan; "
                         "use mode='split'")
    return chain3_cnn_plan(dtype=dtype)


@register_model("resnet18")
def _resnet18(mode: str, dtype: Any, **kw: Any) -> SplitPlan:
    if kw:
        raise ValueError(f"resnet18 takes no size overrides "
                         f"(got {sorted(kw)})")
    from split_learning_tpu.models.resnet import resnet18_plan
    return resnet18_plan(mode=mode, dtype=dtype)


@register_model("resnet18_4stage")
def _resnet18_4stage(mode: str, dtype: Any, **kw: Any) -> SplitPlan:
    """The BASELINE.md config-4 shape: 4 pipeline stages."""
    if kw:
        raise ValueError(f"resnet18_4stage takes no size overrides "
                         f"(got {sorted(kw)})")
    from split_learning_tpu.models.resnet import resnet18_plan
    if mode != "split":
        raise ValueError("resnet18_4stage is a pipeline plan; use mode='split'")
    return resnet18_plan(mode=mode, dtype=dtype, stages=4)


@register_model("vit")
def _vit(mode: str, dtype: Any, **kw: Any) -> SplitPlan:
    """Vision transformer on the image datasets: patchify stem +
    the shared transformer trunk/head (models/vit.py); build
    seq-parallel variants via models.vit.vit_plan(mesh=..., attn=...)."""
    from split_learning_tpu.models.vit import vit_plan
    return vit_plan(mode=mode, dtype=dtype, **kw)


@register_model("transformer")
def _transformer(mode: str, dtype: Any, **kw: Any) -> SplitPlan:
    """Long-context family (beyond reference scope): dense attention by
    default; build seq-parallel variants via
    models.transformer.transformer_plan(mesh=..., attn="ring")."""
    from split_learning_tpu.models.transformer import transformer_plan
    return transformer_plan(mode=mode, dtype=dtype, **kw)


@register_model("transformer_lm")
def _transformer_lm(mode: str, dtype: Any, **kw: Any) -> SplitPlan:
    """Causal language model: causal attention + per-token next-token
    head (train with --dataset lm, labels = inputs shifted by one)."""
    from split_learning_tpu.models.transformer import transformer_plan
    return transformer_plan(mode=mode, dtype=dtype, lm=True, **kw)


@register_model("afmoe")
def _afmoe(mode: str, dtype: Any, **kw: Any) -> SplitPlan:
    """Routed experts (this party's share of them) beside a shared one,
    window beside full attention over grouped heads, RMSNorm, rotary
    positions on the window layers (models/afmoe.py)."""
    from split_learning_tpu.models.afmoe import afmoe_plan
    return afmoe_plan(mode=mode, dtype=dtype, **kw)


@register_model("phi4flash")
def _phi4flash(mode: str, dtype: Any, **kw: Any) -> SplitPlan:
    """State-space layers beside differential attention, and a second
    half that reads a memory and a key/value set the first half made
    (models/phi4flash.py)."""
    from split_learning_tpu.models.phi4flash import phi4flash_plan
    return phi4flash_plan(mode=mode, dtype=dtype, **kw)


@register_model("joyai_llm_flash")
def _joyai_llm_flash(mode: str, dtype: Any, **kw: Any) -> SplitPlan:
    """Latent attention (keys wider than values), this party's share of
    the routed experts beside a shared one, and a multi-token-prediction
    module whose loss the final stage carries as its own objective
    (models/joyai_llm_flash.py)."""
    from split_learning_tpu.models.joyai_llm_flash import (
        joyai_llm_flash_plan)
    return joyai_llm_flash_plan(mode=mode, dtype=dtype, **kw)


@register_model("lfm2_moe")
def _lfm2_moe(mode: str, dtype: Any, **kw: Any) -> SplitPlan:
    """Gated short convolutions in most layers, grouped-head attention
    with normed queries and keys in the others, and this party's share of
    the routed experts with no shared one (models/lfm2_moe.py)."""
    from split_learning_tpu.models.lfm2_moe import lfm2_moe_plan
    return lfm2_moe_plan(mode=mode, dtype=dtype, **kw)


@register_model("nemotron_h")
def _nemotron_h(mode: str, dtype: Any, **kw: Any) -> SplitPlan:
    """One mixer a layer: Mamba-2 in its chunked form, this party's share
    of ungated relu^2 experts beside a shared one, grouped-head attention
    without positions (models/nemotron_h.py)."""
    from split_learning_tpu.models.nemotron_h import nemotron_h_plan
    return nemotron_h_plan(mode=mode, dtype=dtype, **kw)


@register_model("ouro")
def _ouro(mode: str, dtype: Any, **kw: Any) -> SplitPlan:
    """A looped language model: one stack of sandwich-normed dense layers
    run several times with the same weights, an exit gate and a loss read
    after every pass through one head (models/ouro.py)."""
    from split_learning_tpu.models.ouro import ouro_plan
    return ouro_plan(mode=mode, dtype=dtype, **kw)


def get_plan(model: str = "split_cnn", mode: str = "split",
             dtype: Any = jnp.float32, **size_kw: Any) -> SplitPlan:
    """Build the SplitPlan for a model family under a learning mode.

    ``size_kw`` (d_model, num_heads, client_depth, server_depth, ...)
    forwards to the family's plan builder; families without size
    parameters (the fixed reference CNN, ResNet-18) reject them with a
    ValueError rather than silently ignoring a requested size."""
    if mode not in ("split", "federated", "u_split"):
        # preserve the reference's ValueError contract (src/model_def.py:70-71)
        raise ValueError(f"Unknown learning mode: {mode!r}")
    if model not in _FAMILIES:
        raise ValueError(
            f"Unknown model family: {model!r} (have {sorted(_FAMILIES)})")
    return _FAMILIES[model](mode, _dtype_of(dtype), **size_kw)


def get_model(role: str, mode: str = "split", model: str = "split_cnn",
              dtype: Any = jnp.float32) -> Tuple[SplitPlan, Tuple[int, ...]]:
    """Reference-shaped entry point: (plan, indices of stages `role` owns).

    Mirrors ``get_model(role)`` at ``src/model_def.py:49-71``:
    - federated: both parties own/train the full composition,
    - split/u_split: each party owns its side of the cut(s).
    """
    if role not in ("client", "server"):
        raise ValueError(f"Unknown role: {role!r}")
    plan = get_plan(model=model, mode=mode, dtype=dtype)
    if mode == "federated":
        return plan, tuple(range(plan.num_stages))
    return plan, plan.stages_of(role)
