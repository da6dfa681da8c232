"""How a list of layers becomes the stages of a split plan, for a causal
language model cut at a layer. A family gives its layers as a function
``run(h, *layers) -> h`` that builds and calls them inside a compact method
(naming each ``layer<i>``), the spans ``layers`` each party holds, and the
norm its model ends in; everything around the layers is here, once, and
this module imports no family:

- split:   client(embedding + the first layers) -> server(the rest + final
           norm + untied head), stages ``embed``, ``trunk_head``
- u_split: client(embedding + the first layers) -> server(the rest)
           -> client(final norm + head), stages ``embed``, ``trunk``, ``head``
- federated: the composition of the split plan.

A stage is ``(params, x) -> y`` with one cut tensor. None decodes through a
cache (:func:`no_cache`). The refusals every family's builder made in its
own copy are here too (:func:`check_attn`, :func:`held_experts`,
:func:`check_client_depth`, :func:`check_heads`, :func:`kept_layers`); what
only one family can get wrong stays in its file. models/transformer.py's
stages (GPT-2, ViT) are not these: they decode, and carry positions and a
cache.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence

import flax.linen as nn
import jax.numpy as jnp

from split_learning_tpu.core.stage import SplitPlan, from_flax

ATTN_IMPLS = ("auto", "full", "flash")
_INIT = nn.initializers.normal(0.02)


def no_cache(cache_len, decode_cache) -> None:
    """Refuse a decode through a cache: no kind of layer these stages hold
    has one built."""
    if cache_len or decode_cache is not None:
        raise NotImplementedError(
            "no KV-cache decode is built for these layers: a window needs "
            "a cache that forgets (ROADMAP.md M4), a short convolution its "
            "last tokens and a state-space layer its state (a recurrent "
            "state beside the keys and values), and latent attention a "
            "latent cache that holds c_kv and k_r and never the keys (M7; "
            "runtime/generate.py)")


class EmbedStage(nn.Module):
    """Client bottom stage: ``[B, T] int -> [B, T, d_model]``: the
    embedding rows held (times ``scale`` where the family has such a rule;
    no position table) and the first layers, ``run(h, *layers)``."""

    vocab: int
    d_model: int
    run: Callable
    layers: tuple
    dtype: Any = jnp.float32
    scale: Optional[float] = None

    @nn.compact
    def __call__(self, tokens, *, cache_len: int = 0, decode_cache=None,
                 pos=None):
        no_cache(cache_len, decode_cache)
        h = nn.Embed(self.vocab, self.d_model, dtype=self.dtype,
                     embedding_init=_INIT, name="tok")(tokens)
        if self.scale is not None:
            h = h * jnp.asarray(self.scale, self.dtype)
        return self.run(h, *self.layers)


class HeadStage(nn.Module):
    """The family's final norm (``norm_f``, named by this field) and the
    untied head over the vocabulary rows held; products in the compute
    type, accumulated and returned in float32, so the loss is a float32
    softmax. The server's top stage ends in it; alone it is the client's
    top stage of the U-shape."""

    vocab: int
    norm_f: nn.Module
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h, *, cache_len: int = 0, decode_cache=None,
                 pos=None):
        no_cache(cache_len, decode_cache)
        x = self.norm_f(h)
        kernel = self.param("lm_head", _INIT, (h.shape[-1], self.vocab))
        return jnp.dot(x, kernel.astype(self.dtype),
                       preferred_element_type=jnp.float32)


class TrunkStage(nn.Module):
    """The layers ``run(h, *layers)``: the U-shape's middle stage, or with
    a ``head`` (held under that name) the server's top stage of the
    2-party split."""

    run: Callable
    layers: tuple
    head: Optional[nn.Module] = None

    @nn.compact
    def __call__(self, h, *, cache_len: int = 0, decode_cache=None,
                 pos=None):
        no_cache(cache_len, decode_cache)
        h = self.run(h, *self.layers)
        return h if self.head is None else self.head(h)


def split_plan(mode: str, embed: EmbedStage, rest: tuple, head: nn.Module,
               *, top: Optional[nn.Module] = None,
               objective: Optional[str] = None) -> SplitPlan:
    """The :class:`SplitPlan` of ``mode`` from the client's bottom stage
    ``embed``, the span ``rest`` of the layers after it (what ``embed.run``
    takes after ``h``: a trunk stage is ``(run, span)`` and nothing else,
    so a chain is more spans, ROADMAP.md M2) and the module ``head`` that
    ends the model. ``top`` is the stage that runs ``rest`` and ends the
    model, for a family whose head is not a reader of the hidden state
    alone (one set of leaves under two methods: its ``objective``, the name
    of the method ``(h, labels) -> losses``); without one it is ``rest``
    with ``head`` after it."""
    if mode == "u_split":
        return SplitPlan(
            stages=(from_flax("embed", embed),
                    from_flax("trunk", TrunkStage(embed.run, rest)),
                    from_flax("head", head, objective)),
            owners=("client", "server", "client"))
    if top is None:
        top = TrunkStage(embed.run, rest, head)
    return SplitPlan(
        stages=(from_flax("embed", embed),
                from_flax("trunk_head", top, objective)),
        owners=("client", "server"))


def check_attn(attn: str) -> None:
    if attn not in ATTN_IMPLS:
        raise ValueError(f"Unknown attn impl: {attn!r} (expected {ATTN_IMPLS})")


def held_experts(total: int, held: Optional[int], offset: int) -> int:
    """How many of the router's ``total`` experts a routed layer holds from
    ``offset`` on: ``held``, all of them where it is None."""
    held = total if held is None else held
    if not (0 <= offset and offset + held <= total and held >= 1):
        raise ValueError(f"experts [{offset}, {offset + held}) are not among "
                         f"the router's {total}")
    return held


def check_client_depth(client_depth: int, layers: int) -> None:
    if not 0 <= client_depth <= layers:
        raise ValueError(f"client_depth {client_depth} of {layers} layers")


def check_heads(num_heads: int, num_kv_heads: int) -> None:
    if num_heads % num_kv_heads:
        raise ValueError(f"{num_kv_heads} key/value heads do not divide "
                         f"{num_heads} query heads")


def kept_layers(layers_kept: Sequence[int], published: int,
                kinds: Sequence = ()) -> tuple:
    """``layers_kept`` as a tuple of distinct rising indices of the
    ``published`` layers. ``kinds`` (one entry a published layer) are the
    kinds of a model that is another model without one of them: a cut that
    keeps no layer of a kind is refused."""
    kept = tuple(int(i) for i in layers_kept)
    if list(kept) != sorted(set(kept)) or not kept or not (
            0 <= kept[0] and kept[-1] < published):
        raise ValueError(f"layers_kept {list(kept)} are not distinct rising "
                         f"indices of {published} published layers")
    dropped = sorted(set(kinds) - {kinds[i] for i in kept}) if kinds else ()
    if dropped:
        raise ValueError(f"layers_kept {list(kept)} keep no {dropped} layer, "
                         "a kind that the published layers name")
    return kept
