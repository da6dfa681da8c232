"""GPipe pipeline parallelism over the ``pipe`` mesh axis with ``ppermute``
cut-layer exchange — the explicit ICI transport (BASELINE.json north star,
configs 2/4/5).

Split learning *is* 2-stage pipeline parallelism (SURVEY.md §2): the
reference runs stage A in the client pod and stage B in the server pod with
a blocking HTTP round trip at the cut and one batch in flight
(``src/client_part.py:114-133``). Here the cut-layer hop is a
``jax.lax.ppermute`` along the ``pipe`` axis inside one jitted program —
zero serialization, ICI bandwidth — and the schedule is GPipe: M
microbatches fill the pipe so stages overlap instead of idling.

Design notes (TPU-first):
- The forward pipeline is written as a ``lax.scan`` over clock ticks;
  ``jax.grad`` differentiates through it, so the reverse (gradient)
  schedule — including the reverse ``ppermute`` carrying cut-layer
  cotangents (the reference's "gradient returns" hop,
  ``src/server_part.py:57-58``) — is generated by the scan transpose, not
  hand-written.
- Stages are heterogeneous (different activation shapes), but SPMD ranks
  execute one program: inter-stage tensors ride a common flat buffer of
  ``max_elems`` per example, and each rank decodes/encodes its stage's
  shape via ``lax.switch``. The branches contain no collectives, so XLA
  compiles the switch to a real HLO ``conditional`` — verified from the
  compiled module in tests/test_pipeline_perf.py — and each rank executes
  ONLY its own stage's branch per tick (round 1 documented a feared
  S-times branch blowup here; inspection and measurement showed it does
  not exist in the compiled program). The real overheads are (a) the
  flat-buffer padding to ``max_elems`` on the ppermute hop, (b) the
  M+S-1 tick schedule (the standard GPipe bubble), and (c) per-tick
  lockstep at the ppermute, i.e. ticks run at the slowest stage's pace —
  quantified in artifacts/pipeline_measurements.json (produced by
  scripts/measure_pipeline.py, consistency asserted by
  tests/test_pipeline_perf.py). Gradients of unselected branches are
  structurally zero, and the cross-stage ``psum`` reassembles the full
  gradient tree.
- Data parallelism composes orthogonally: the batch is sharded over the
  ``data`` axis, gradients psum over (data, pipe).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from split_learning_tpu.core.losses import cross_entropy, refuse_objective
from split_learning_tpu.core.stage import SplitPlan, remat_plan
from split_learning_tpu.obs import dispatch_debug as obs_dispatch
from split_learning_tpu.parallel.mesh import DATA_AXIS, PIPE_AXIS
from split_learning_tpu.runtime.state import (
    TrainState, apply_grads, make_state, make_tx)
from split_learning_tpu.utils.config import Config


@dataclasses.dataclass(frozen=True)
class _StageSpec:
    in_shape: Tuple[int, ...]   # per-example input shape
    in_elems: int
    in_dtype: Any               # decode cast target (int tokens ride the
                                # float buffer exactly: |vocab| << 2^24)


def _stage_specs(plan: SplitPlan, params, sample):
    """Per-example input shapes/dtypes of every stage, final output shape,
    and the per-stage output dtypes (mixed precision makes these bf16)."""
    specs, out_dtypes = [], []
    x = jax.eval_shape(lambda v: v, jnp.asarray(sample[:1]))
    for stage, p in zip(plan.stages, params):
        shp = tuple(x.shape[1:])
        specs.append(_StageSpec(in_shape=shp, in_elems=int(np.prod(shp)),
                                in_dtype=x.dtype))
        x = jax.eval_shape(stage.apply, p, x)
        out_dtypes.append(x.dtype)
    return tuple(specs), tuple(x.shape[1:]), tuple(out_dtypes)


class PipelinedTrainer:
    """GPipe training of a SplitPlan over a (data, pipe) mesh."""

    def __init__(self, plan: SplitPlan, cfg: Config, rng: jax.Array,
                 sample_input: np.ndarray, mesh: Mesh,
                 microbatches: Optional[int] = None) -> None:
        if PIPE_AXIS not in mesh.axis_names:
            raise ValueError(f"mesh must have a {PIPE_AXIS!r} axis")
        # the last rank's logits are gathered over microbatches first
        refuse_objective(plan, "the SPMD pipeline (PipelinedTrainer)")
        n_pipe = mesh.shape[PIPE_AXIS]
        if n_pipe != plan.num_stages:
            raise ValueError(
                f"pipe axis size {n_pipe} != num stages {plan.num_stages}")
        self.plan = plan if not cfg.remat else remat_plan(plan)
        plan = self.plan
        self.cfg = cfg
        self.mesh = mesh
        self.M = microbatches if microbatches is not None else cfg.microbatches
        n_data = mesh.shape.get(DATA_AXIS, 1)
        local_b = cfg.batch_size // n_data
        if local_b % self.M != 0:
            raise ValueError(
                f"microbatches ({self.M}) must divide the per-client batch "
                f"({local_b} = batch_size {cfg.batch_size} / {n_data} clients)")
        self.mb_size = local_b // self.M
        self._tx = make_tx(cfg)

        params = tuple(plan.init(rng, jnp.asarray(sample_input)))
        state = make_state(params, self._tx)
        # public for the same reason as FusedSplitTrainer.state_sharding:
        # restored checkpoints must re-commit to this before stepping
        self.state_sharding = NamedSharding(mesh, P())
        state = jax.device_put(state, self.state_sharding)
        self.state = state

        self._sample_ndim = np.ndim(sample_input)
        self._specs, out_shape, out_dtypes = _stage_specs(
            plan, params, sample_input)
        # per-example logits shape: (C,) for classifiers, (T, V) for the
        # causal LM's per-token head — the logits slot carries it whole
        self.out_tail = out_shape
        # common flat buffer: the largest per-example tensor crossing a cut.
        # Its dtype is the cut tensors' compute dtype — under bf16 mixed
        # precision the ppermute hop moves half the ICI bytes, and casting
        # the injected input down is exactly what the first stage's
        # compute-dtype cast would do anyway.
        # stage 0's raw input never rides the cut buffer (it has its own
        # injection stream, see tick()), so it doesn't set the hop size
        self.buf_elems = max(s.in_elems
                             for s in (self._specs[1:] or self._specs))
        cut_dtypes = out_dtypes[:-1] or out_dtypes
        self.buf_dtype = jnp.result_type(*cut_dtypes)
        if (any(jnp.issubdtype(s.in_dtype, jnp.integer)
                for s in self._specs[1:])
                and jnp.finfo(self.buf_dtype).nmant < 23):
            # a non-first integer-input stage would receive its ids via
            # the float cut buffer; bf16 is exact only up to 256 (id 257
            # would silently become 256), f32 up to 2^24 — promote.
            # Stage 0 is exempt: its input rides the raw injection
            # stream, never the cut buffer (see tick()).
            self.buf_dtype = jnp.dtype(jnp.float32)
        self.logits_dtype = out_dtypes[-1]

        self._build(mesh, n_data)

    # ------------------------------------------------------------------ #
    def _build(self, mesh: Mesh, n_data: int) -> None:
        plan, M, S = self.plan, self.M, self.plan.num_stages
        mbsz, buf_elems = self.mb_size, self.buf_elems
        out_tail = self.out_tail
        buf_dtype, logits_dtype = self.buf_dtype, self.logits_dtype
        specs = self._specs
        tx = self._tx
        global_b = self.cfg.batch_size
        T = M + S - 1  # pipeline clock ticks

        def encode(x):
            flat = x.reshape(x.shape[0], -1).astype(buf_dtype)
            pad = buf_elems - flat.shape[1]
            return jnp.pad(flat, ((0, 0), (0, pad)))

        def pipelined_loss(params, x_local, y_local):
            """Runs on ONE (data row, pipe rank); x_local [B_local, ...]."""
            r = jax.lax.axis_index(PIPE_AXIS)
            is_last = (r == S - 1).astype(jnp.float32)

            # stage 0's input never crosses a cut: it rides this raw
            # stream in its own dtype (integer token ids stay exact and
            # the bf16 cut buffer stays bf16), padded with S-1 drain ticks
            xs = x_local.reshape((M, mbsz) + x_local.shape[1:])
            inject = jnp.concatenate(
                [xs, jnp.zeros((S - 1, mbsz) + x_local.shape[1:],
                               x_local.dtype)], 0)

            def branch(i):
                spec = specs[i]

                def run(buf, x0):
                    if i == 0:
                        x = x0
                    else:
                        x = buf[:, :spec.in_elems] \
                            .reshape((mbsz,) + spec.in_shape)
                        if jnp.issubdtype(spec.in_dtype, jnp.integer):
                            # ids crossed the float cut buffer; restore
                            # the dtype the stage (e.g. nn.Embed) needs
                            x = x.astype(spec.in_dtype)
                    out = plan.stages[i].apply(params[i], x)
                    if i == S - 1:
                        # final stage: logits travel in the logits slot
                        return (jnp.zeros((mbsz, buf_elems), buf_dtype),
                                out.astype(logits_dtype))
                    return encode(out), jnp.zeros((mbsz,) + out_tail,
                                                  logits_dtype)
                return run

            branches = [branch(i) for i in range(S)]

            def tick(carry, inp):
                recv, logits_acc = carry
                x0, t = inp
                out_buf, logits = jax.lax.switch(r, branches, recv, x0)
                # last rank records microbatch m = t - (S-1)
                m = t - (S - 1)
                valid = jnp.logical_and(m >= 0, m < M)
                m_clip = jnp.clip(m, 0, M - 1)
                upd = jax.lax.dynamic_update_index_in_dim(
                    logits_acc, logits, m_clip, axis=0)
                logits_acc = jnp.where(valid, upd, logits_acc)
                # the cut-layer hop: stage r -> stage r+1 over ICI
                sent = jax.lax.ppermute(
                    out_buf, PIPE_AXIS,
                    [(i, i + 1) for i in range(S - 1)])
                return (sent, logits_acc), None

            carry0 = (jnp.zeros((mbsz, buf_elems), buf_dtype),
                      jnp.zeros((M, mbsz) + out_tail, logits_dtype))
            (_, logits_acc), _ = jax.lax.scan(
                tick, carry0, (inject, jnp.arange(T)))

            ys = y_local.reshape((M, mbsz) + y_local.shape[1:])
            ce = cross_entropy(logits_acc.reshape((M * mbsz,) + out_tail),
                               ys.reshape((M * mbsz,) + y_local.shape[1:]))
            # local sum / global batch; nonzero only on the last pipe rank
            return is_last * ce * (M * mbsz) / global_b

        def sharded_step(state: TrainState, x, y):
            loss_local, grads = jax.value_and_grad(
                pipelined_loss, argnums=0)(state.params, x, y)
            # reassemble: stage grads live on their pipe rank (others are
            # structurally zero); data shards each carry their slice
            grads = jax.lax.psum(grads, (DATA_AXIS, PIPE_AXIS))
            loss = jax.lax.psum(loss_local, (DATA_AXIS, PIPE_AXIS))
            new_state = apply_grads(tx, state, grads)
            return new_state, loss

        x_spec = P(*((DATA_AXIS,) + (None,) * (self._sample_ndim - 1)))
        state_spec = jax.tree_util.tree_map(lambda _: P(), self.state)

        def step(state, x, y):
            return shard_map(
                sharded_step,
                mesh=mesh,
                in_specs=(state_spec, x_spec, P(DATA_AXIS)),
                out_specs=(state_spec, P()),
                check_vma=False,
            )(state, x, y)

        rep = NamedSharding(mesh, P())
        self._x_sharding = NamedSharding(mesh, x_spec)
        self._y_sharding = NamedSharding(mesh, P(DATA_AXIS))
        self._step = jax.jit(step, donate_argnums=(0,))
        # dispatch watchdog (slt-lint phase 2): None unless enabled
        self._dd = obs_dispatch.attach()
        self._ddtok = obs_dispatch.token()

    # ------------------------------------------------------------------ #
    def train_step(self, x: np.ndarray, y: np.ndarray) -> float:
        x = jax.device_put(jnp.asarray(x), self._x_sharding)
        y = jax.device_put(jnp.asarray(y), self._y_sharding)
        with obs_dispatch.step_scope(
                self._dd, (self._ddtok, "pipeline_step"),
                sig_fn=lambda: (x.shape, str(x.dtype), y.shape)):
            self.state, loss = self._step(self.state, x, y)
        with obs_dispatch.expected_d2h(self._dd):
            return float(loss)

    @property
    def params(self):
        return self.state.params


# ---------------------------------------------------------------------- #
# In-mesh hop shuttle (PR 16): one cut-layer exchange as the SAME
# ``ppermute`` collective the fused trainer's tick() uses, but packaged
# per hop for the MPMD chain's DeviceTransport. The payload is staged
# into a slot-stacked buffer sharded over the pipe axis, shifted
# src -> dst inside shard_map, and the destination slot sliced back out
# — all inside ONE jitted program, so the buffer never bounces through
# host and cross-device placement is XLA's problem, not python's.

def make_hop_shift(mesh: Mesh, src: int, dst: int):
    """Jitted ``fn(x) -> jax.Array`` moving ``x`` from pipe rank ``src``
    to pipe rank ``dst`` via ``jax.lax.ppermute`` over ``mesh``'s
    ``pipe`` axis. One program per (shape, dtype) signature — callers
    cache the returned fn per wire, so steady state never recompiles."""
    if PIPE_AXIS not in mesh.axis_names:
        raise ValueError(f"mesh must have a {PIPE_AXIS!r} axis")
    n = mesh.shape[PIPE_AXIS]
    if not (0 <= src < n and 0 <= dst < n):
        raise ValueError(
            f"hop {src}->{dst} outside the pipe axis (size {n})")
    if src == dst:
        raise ValueError("hop shift needs distinct src/dst ranks")

    def shift(x):
        buf = jnp.zeros((n,) + x.shape, x.dtype).at[src].set(x)
        buf = jax.lax.with_sharding_constraint(
            buf, NamedSharding(mesh, P(PIPE_AXIS)))
        moved = shard_map(
            lambda b: jax.lax.ppermute(b, PIPE_AXIS, [(src, dst)]),
            mesh=mesh, in_specs=P(PIPE_AXIS), out_specs=P(PIPE_AXIS),
            check_vma=False,
        )(buf)
        return moved[dst]

    jitted = jax.jit(shift)
    replicated = NamedSharding(mesh, P())

    def call(x):
        # the payload may arrive committed to a single device (e.g. the
        # hub's rank-0 buffers) — lay it onto the mesh first (pure D2D;
        # a jit spanning the mesh rejects off-mesh-committed args)
        return jitted(jax.device_put(x, replicated))

    return call
