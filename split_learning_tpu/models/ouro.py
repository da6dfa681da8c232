"""Split looped language model — one stack of dense layers run several
times with the same weights, an exit read after every pass (the ``ouro``
family, arXiv:2510.25741).

A layer is a dense one with **sandwich norms** (RMSNorm(x) = x /
sqrt(mean(x^2) + eps) * scale, statistics in float32, no bias anywhere;
the published names)::

    a = Attn(input_layernorm(h));           h = h + input_layernorm_2(a)
    m = MLP(post_attention_layernorm(h));   h = h + post_attention_layernorm_2(m)

- **Attn**: models/afmoe.py's :class:`~split_learning_tpu.models.afmoe.
  AfmoeAttention` in its ``plain`` form with ``rope_always``: ``q``, ``k``,
  ``v`` from ``u`` (``[T, H, D]``, ``[T, H_kv, D]``), rotary positions
  (rotate-half over the whole head, ``models/afmoe.py:rope``) on ``q`` and
  ``k``, scores times ``D^-0.5``, causal, softmax in float32, ``o W_o``.
- **MLP**: models/afmoe.py's :class:`~split_learning_tpu.models.afmoe.
  SwiGLU`, ``W_down (silu(W_gate x) * W_up x)``.
- **The model**: ``h = E[tokens]``; for pass ``s`` of ``passes``: ``h =
  layers(h)`` (every layer in order, the same weights every pass), ``h =
  norm_f(h)``: **the final norm closes every pass**, and what it gives is
  both the pass's exit ``e_s`` and the next pass's input; ``lambda_s =
  sigmoid(w_g . e_s + b_g)`` (``early_exit_gate``, float32), ``logits_s =
  e_s W_head``. ``apply`` returns the last pass's logits (at inference no
  pass before the last exits at the published threshold 1).
- **The objective** (the paper's first training stage; one loss a token,
  so it is the final stage's own objective, ``core/stage.Stage``): with
  ``l_s = CE(logits_s, y)``, ``q_s = lambda_s prod_{j<s} (1 - lambda_j)``
  for every pass but the last and ``q_last = prod_{j<last} (1 -
  lambda_j)`` (the last pass takes what is left, so ``q`` sums to 1), a
  token's loss is ``sum_s q_s l_s - beta H(q)``, ``H(q) = -sum_s q_s log
  q_s``, in float32, with ``log q`` made from the gates' logits and ``q``
  from it (:func:`log_exit_distribution`): AdamW's first steps swing the
  gate until it saturates for some tokens, and the objective and its
  gradient stay finite there.

**The loop lives in the server's top stage** (:class:`LoopStage`): the
layers are built once and called ``passes`` times, so the parameter tree
holds them once and autodiff sums each leaf's gradient over its uses. The
client's stage is models/cut.py's embedding with no layer. **A cut inside
the looped span would be crossed at every pass** (``passes`` times up and
``passes - 1`` times back, each way, a step), and a plan's stages run once,
in order: ``client_depth`` above 0 is refused by name, and so is
``u_split``, whose client-held head would need every pass's hidden state
(ROADMAP.md, "cannot run yet"). split = client(embedding) -> server(the
loop, the final norm, the gate, the head); federated is the composition.

**What ``remat`` recomputes** (the activations a step keeps are ``passes``
times what its parameters suggest: this is what decides the fit). A layer
application is four regions: each branch (a norm and the operator it
feeds) and each close (the norm after the branch and the residual sum).
With ``remat`` a close keeps its two inputs and makes the norm again; a
branch keeps its input, every matrix product's output and the flash
kernel's output and row statistics (:func:`_products_and_kernels`) and
makes again only the elementwise passes between them: the norm, the
rotary turns and the kernel's layout of q, k and v, ``silu(g) * u``. No
product and no kernel runs a second time. ``remat_mlp_passes`` (a static
count: the passes are the same layers, so only the pass tells two
applications apart) makes the MLP branch of the first so many passes keep
its input alone: its ``gate`` and ``up`` products run again in the
backward pass (``down`` does not: nothing of the backward reads its
output). Without ``remat`` autodiff keeps everything. Decoding through a
cache is not built: the adaptive exit needs a key/value cache a pass
(runtime/generate.py, ROADMAP.md M7).

**What the step reports.** A caller that applies the objective with
``obs/spans.STEP_COUNTERS`` mutable (``core/stage.with_counters``: the
fused step) gets ``exit_mass`` (the mean of ``q_s`` over the step's
tokens) and ``exit_loss`` (the mean of ``l_s``), one entry a pass; a
caller that does not ask traces the program without them.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax

from split_learning_tpu.core.stage import SplitPlan
from split_learning_tpu.models import cut
from split_learning_tpu.models.afmoe import AfmoeAttention, RMSNorm, SwiGLU
from split_learning_tpu.obs import spans

_INIT = nn.initializers.normal(0.02)
_F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What every layer of one model shares; :func:`ouro_plan` documents
    each."""

    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    width: int
    rope_theta: float
    eps: float
    attn: str
    dtype: Any

    def norm(self) -> RMSNorm:
        return RMSNorm(self.eps, self.dtype)


def _products_and_kernels(prim, *_, **__) -> bool:
    """A ``jax.checkpoint`` policy: keep what a matrix product or a Pallas
    kernel gave, make everything else again."""
    return prim.name in ("dot_general", "pallas_call")


class OuroLayer(nn.Module):
    """One layer, applied as often as the loop calls it."""

    sizes: Sizes

    def setup(self):
        s = self.sizes
        self.input_layernorm = s.norm()
        self.self_attn = AfmoeAttention(
            s.num_heads, s.num_kv_heads, s.head_dim, None, s.rope_theta,
            s.eps, s.attn, s.dtype, plain=True, rope_always=True)
        self.input_layernorm_2 = s.norm()
        self.post_attention_layernorm = s.norm()
        self.mlp = SwiGLU(s.width, s.dtype)
        self.post_attention_layernorm_2 = s.norm()

    def attn_branch(self, h):
        return self.self_attn(self.input_layernorm(h))

    def attn_close(self, h, a):
        return h + self.input_layernorm_2(a)

    def mlp_branch(self, h):
        return self.mlp(self.post_attention_layernorm(h))

    def mlp_close(self, h, m):
        return h + self.post_attention_layernorm_2(m)

    def __call__(self, h, remat: bool = False, remat_mlp: bool = False):
        """``remat``, ``remat_mlp``: this application's regions (the module
        header); static, and no number depends on them."""
        if not remat:
            h = self.attn_close(h, self.attn_branch(h))
            return self.mlp_close(h, self.mlp_branch(h))
        keep = _products_and_kernels
        a = nn.remat(OuroLayer.attn_branch, policy=keep)(self, h)
        h = nn.remat(OuroLayer.attn_close)(self, h, a)
        m = nn.remat(OuroLayer.mlp_branch,
                     policy=None if remat_mlp else keep)(self, h)
        return nn.remat(OuroLayer.mlp_close)(self, h, m)


def log_exit_distribution(z):
    """``log q [..., S]`` from the gates' logits ``z [..., S]`` of the passes:
    a pass exits with its gate's share of what no earlier pass took, and the
    last takes the rest whatever its gate says. In logarithms (``log lambda =
    log_sigmoid(z)``, ``log(1 - lambda) = log_sigmoid(-z)``): a gate saturates
    in float32 from a logit of 17 on, a later pass's ``q`` is then exactly 0,
    and ``q log q`` written over ``q`` has no finite gradient there."""
    stay = jnp.cumsum(jax.nn.log_sigmoid(-z[..., :-1]), axis=-1)
    return jnp.concatenate([jnp.zeros_like(z[..., :1]), stay], -1) \
        + jnp.concatenate([jax.nn.log_sigmoid(z[..., :-1]),
                           jnp.zeros_like(z[..., :1])], -1)


class LoopStage(nn.Module):
    """The server's top stage: ``layers`` layers bound once and run
    ``passes`` times, the final norm after every pass, the exit gate and
    the untied head over the vocabulary rows held. ``__call__`` gives the
    last pass's logits, :meth:`losses` the objective of the module header.
    Products in the compute type, accumulated in float32; the gate, the
    exit distribution, logits and losses float32."""

    vocab: int
    sizes: Sizes
    layers: int
    passes: int
    beta: float
    remat: bool
    remat_mlp_passes: int

    def setup(self):
        s = self.sizes
        for i in range(self.layers):
            setattr(self, f"layer{i}", OuroLayer(s))
        self.norm_f = s.norm()
        self.early_exit_gate = nn.Dense(
            1, dtype=_F32, kernel_init=_INIT, bias_init=_INIT,
            precision=jax.lax.Precision.HIGHEST)
        self.lm_head = self.param("lm_head", _INIT, (s.d_model, self.vocab))

    def _exits(self, h) -> list:
        """``e_s`` for every pass: what the final norm gives after it."""
        exits = []
        for s in range(self.passes):
            for i in range(self.layers):
                h = getattr(self, f"layer{i}")(
                    h, self.remat, self.remat and s < self.remat_mlp_passes)
            h = self.norm_f(h)
            exits.append(h)
        return exits

    def _logits(self, e):
        return jnp.dot(e, self.lm_head.astype(self.sizes.dtype),
                       preferred_element_type=_F32)

    def __call__(self, h, *, cache_len: int = 0, decode_cache=None,
                 pos=None):
        cut.no_cache(cache_len, decode_cache)
        return self._logits(self._exits(h)[-1])

    def losses(self, h, labels):
        """``[B, T]`` float32, one loss a token: the expectation of the
        passes' cross-entropies under the exit distribution, less ``beta``
        times its entropy."""
        ce = optax.softmax_cross_entropy_with_integer_labels
        exits = self._exits(h)
        per_pass = jnp.stack([ce(self._logits(e), labels) for e in exits], -1)
        log_q = log_exit_distribution(jnp.concatenate(
            [self.early_exit_gate(e.astype(_F32)) for e in exits], -1))
        q = jnp.exp(log_q)
        # init makes every collection mutable: the weights stay alone
        if self.is_mutable_collection(spans.STEP_COUNTERS) \
                and not self.is_initializing():
            for name, value in ((spans.EXIT_MASS, q),
                                (spans.EXIT_LOSS, per_pass)):
                self.sow(spans.STEP_COUNTERS, name,
                         value.reshape(-1, self.passes).mean(0),
                         reduce_fn=lambda _, new: new, init_fn=lambda: None)
        entropy = -jnp.sum(q * log_q, -1)
        return jnp.sum(q * per_pass, -1) - self.beta * entropy


def _no_layers(h):
    """The client's span of the layers: none (the module header)."""
    return h


def ouro_plan(mode: str = "split", dtype: Any = jnp.float32, *,
              vocab: int = 256, d_model: int = 64, num_heads: int = 4,
              num_kv_heads: int = 4, head_dim: int = 16, width: int = 176,
              layers: int = 2, passes: int = 4, beta: float = 0.1,
              client_depth: int = 0, rope_theta: float = 1e6,
              rms_norm_eps: float = 1e-6, attn: str = "auto",
              remat: bool = True, remat_mlp_passes: int = 0) -> SplitPlan:
    """Build the looped model's :class:`SplitPlan` for ``mode``.

    The arguments carry the published names' values: ``layers`` is the
    number of layers kept (every one full attention and a SwiGLU of
    ``width``, ``intermediate_size``), ``passes`` is ``total_ut_steps``,
    ``beta`` the weight of the exit distribution's entropy in the
    objective. The client holds the embedding and no layer
    (``client_depth`` 0, the only one: the module header). ``remat`` and
    ``remat_mlp_passes``: the module header."""
    cut.check_attn(attn)
    cut.check_heads(num_heads, num_kv_heads)
    if mode == "u_split":
        raise NotImplementedError(
            "u_split: a head held by the client reads every pass's hidden "
            f"state, {passes} tensors across one cut where a plan's cut "
            "carries one (core/stage.SplitPlan; ROADMAP.md M4b)")
    if client_depth:
        raise NotImplementedError(
            f"client_depth {client_depth}: a cut inside the looped span is "
            f"crossed at every pass ({passes} times up, {passes - 1} back, "
            "each way, a step), and a plan's stages run once, in order "
            "(core/stage.SplitPlan, core/losses.plan_loss, runtime/client.py,"
            " runtime/server.py); the client holds the embedding alone")
    if layers < 1 or passes < 1:
        raise ValueError(f"{layers} layers run {passes} times")
    if not 0 <= remat_mlp_passes <= passes:
        raise ValueError(f"remat_mlp_passes {remat_mlp_passes} of {passes} "
                         "passes")
    if head_dim % 2:
        raise ValueError(f"rotate-half needs an even head_dim, got {head_dim}")
    sizes = Sizes(
        d_model=d_model, num_heads=num_heads, num_kv_heads=num_kv_heads,
        head_dim=head_dim, width=width, rope_theta=float(rope_theta),
        eps=float(rms_norm_eps), attn=attn, dtype=dtype)
    top = LoopStage(vocab, sizes, layers, passes, float(beta), bool(remat),
                    int(remat_mlp_passes))
    # no U-shape (refused above), so nothing reads a head or a span alone
    return cut.split_plan(
        mode, cut.EmbedStage(vocab, d_model, _no_layers, (), dtype), (), top,
        top=top, objective="losses")
