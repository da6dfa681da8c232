"""Fused SPMD trainer — the TpuTransport fast path (BASELINE.json north star).

The reference's hot loop pays a 2 x 5.28 MiB pickle/HTTP round trip per step
(SURVEY.md §3.1). Here the whole split step — client stage forward, cut-layer
"send", server stage forward, loss, backward, cut-layer gradient "return",
both SGD updates — is ONE jitted XLA program over a device mesh:

- the cut-layer exchange serializes nothing; under a sharded mesh it lowers
  to ICI collectives chosen by XLA, and on one chip it fuses away entirely;
- multi-client data parallelism (BASELINE.md config 3) is the mesh's
  ``data`` axis: the global batch is sharded across clients and gradient
  psum over ICI replaces the reference's per-epoch weight shipping;
- GPipe-style microbatching (config 4) is a ``lax.scan`` accumulating
  gradients over microbatches — compiler-friendly control flow, constant
  memory in the number of microbatches.

The split structure is preserved *functionally* (same SplitPlan, same
per-stage params as the MPMD runtimes), so fused and transport-based
training are numerically interchangeable — tested in
tests/test_fused.py.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from split_learning_tpu.core.losses import (
    cross_entropy, plan_loss_with_counters)
from split_learning_tpu.core.stage import SplitPlan, remat_plan
from split_learning_tpu.obs import dispatch_debug as obs_dispatch
from split_learning_tpu.obs import spans
from split_learning_tpu.obs import trace as obs_trace
from split_learning_tpu.parallel.mesh import (
    DATA_AXIS, SEQ_AXIS, batch_sharding, replicated, tp_param_sharding)
from split_learning_tpu.runtime.state import (
    TrainState, apply_grads, make_state, make_tx)
from split_learning_tpu.utils.config import Config


class FusedSplitTrainer:
    """Single-program split training over an optional (data, pipe) mesh."""

    def __init__(self, plan: SplitPlan, cfg: Config, rng: jax.Array,
                 sample_input: np.ndarray,
                 mesh: Optional[Mesh] = None) -> None:
        self.plan = plan if not cfg.remat else remat_plan(plan)
        plan = self.plan  # grads recompute stage forwards under remat
        self.cfg = cfg
        self.mesh = mesh
        self._tx = make_tx(cfg)

        params = tuple(plan.init(rng, jnp.asarray(sample_input)))
        state = make_state(params, self._tx)
        if mesh is not None:
            # batch sharded over 'data'; params replicated — except under
            # tensor parallelism, where weight matrices shard their output
            # features over 'model' (optimizer traces mirror their params,
            # so the same per-leaf rule shards them identically).
            # state_sharding is public: restored checkpoints must be
            # device_put with it before stepping (launch/run.py resume).
            self.state_sharding = tp_param_sharding(mesh, state)
            state = jax.device_put(state, self.state_sharding)
            self._y_sharding = batch_sharding(mesh)
            if SEQ_AXIS in mesh.axis_names and np.ndim(sample_input) >= 2:
                # context parallelism: inputs [B, T, ...] shard their
                # sequence dim over 'seq' so the non-attention compute
                # partitions along T and ring/Ulysses attention
                # (ops/ring_attention.py) finds its shards in place
                self._x_sharding = NamedSharding(mesh, P(DATA_AXIS, SEQ_AXIS))
            else:
                self._x_sharding = self._y_sharding
        else:
            self.state_sharding = None
            self._x_sharding = None
            self._y_sharding = None
        self.state = state

        microbatches = cfg.microbatches
        tx = self._tx

        def loss_fn(params, x, y):
            return plan_loss_with_counters(plan, params, x, y, cross_entropy)

        def step_fn(state: TrainState, x, y):
            """``(state, loss, counters)``: what the plan's modules sowed
            into ``spans.STEP_COUNTERS`` this step, by module path, still
            on the device (``{}`` for a plan that sows nothing, whose
            program is then the one without this output). Nothing reads
            them but :meth:`train_step` while recording. A microbatch's
            counters stay inside the scan and ``epoch_fn`` drops a
            step's: XLA removes both."""
            counters = {}
            if microbatches == 1:
                (loss, counters), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(state.params, x, y)
            else:
                # GPipe-style gradient accumulation: scan over microbatches.
                mb = microbatches
                xs = x.reshape((mb, x.shape[0] // mb) + x.shape[1:])
                ys = y.reshape((mb, y.shape[0] // mb) + y.shape[1:])

                def micro(carry, xy):
                    g_acc, l_acc = carry
                    xmb, ymb = xy
                    (l, _), g = jax.value_and_grad(
                        loss_fn, has_aux=True)(state.params, xmb, ymb)
                    g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
                    return (g_acc, l_acc + l), None

                zeros = jax.tree_util.tree_map(jnp.zeros_like, state.params)
                (g_sum, l_sum), _ = jax.lax.scan(
                    micro, (zeros, jnp.zeros(())), (xs, ys))
                grads = jax.tree_util.tree_map(lambda g: g / mb, g_sum)
                loss = l_sum / mb
            return apply_grads(tx, state, grads), loss, counters

        def epoch_fn(state: TrainState, xs, ys):
            """T steps in one XLA program: lax.scan over the step axis.

            Amortizes per-step host dispatch (~100us, comparable to the
            whole on-chip step for the MNIST CNN) across T steps — the
            jit-once/scan-many idiom the reference's per-batch HTTP round
            trip structurally rules out."""
            return jax.lax.scan(
                lambda s, xy: step_fn(s, xy[0], xy[1])[:2], state, (xs, ys))

        if mesh is not None:
            state_sh = self.state_sharding
            x_sh, y_sh = self._x_sharding, self._y_sharding
            # epoch inputs carry a leading step axis: same spec shifted by 1
            ep_x = NamedSharding(mesh, P(None, *tuple(x_sh.spec)))
            ep_y = NamedSharding(mesh, P(None, *tuple(y_sh.spec)))
            self._step = jax.jit(
                step_fn,
                in_shardings=(state_sh, x_sh, y_sh),
                out_shardings=(state_sh, replicated(mesh), replicated(mesh)),
                donate_argnums=(0,),
            )
            self._epoch = jax.jit(
                epoch_fn,
                in_shardings=(state_sh, ep_x, ep_y),
                out_shardings=(state_sh, replicated(mesh)),
                donate_argnums=(0,),
            )
            self._seq_sharding = (ep_x, ep_y)
        else:
            self._step = jax.jit(step_fn, donate_argnums=(0,))
            self._epoch = jax.jit(epoch_fn, donate_argnums=(0,))
            self._seq_sharding = None
        # dispatch watchdog (slt-lint phase 2): None unless enabled
        self._dd = obs_dispatch.attach()
        self._ddtok = obs_dispatch.token()

    def train_step(self, x: np.ndarray, y: np.ndarray) -> float:
        """One fused step on the global batch (sharded over clients).
        Spans (obs/trace.py): ``step_total`` > ``h2d`` (the inputs),
        ``dispatch`` (the call of the jitted step), ``loss_wait`` (the
        blocking read of the loss, where the device's time shows) and,
        while recording and where the plan sows any, ``counters_read``:
        one ``device_get`` of the step's counters, whose attributes are
        the record (``layers``: the module paths; one list a counter,
        an entry a layer: the routed layer's ``pairs``, ``rows`` and
        ``ladder``, models/afmoe.py). With recording off the counters
        are never fetched."""
        with obs_trace.span(spans.STEP_TOTAL):
            loss, counters = self._dispatch_step(x, y)
            with obs_dispatch.expected_d2h(self._dd), \
                    obs_trace.span(spans.LOSS_WAIT):
                loss = float(loss)
            if counters and obs_trace.recording():
                with obs_dispatch.expected_d2h(self._dd), \
                        obs_trace.span(spans.COUNTERS_READ) as read:
                    got = jax.device_get(counters)
                    layers = sorted(got)
                    names = sorted({n for layer in got.values() for n in layer})
                    # a layer that sows no counter of a name reads None
                    read.set(layers=layers, **{
                        n: [got[layer][n].tolist() if n in got[layer]
                            else None for layer in layers] for n in names})
            return loss

    def _dispatch_step(self, x, y) -> Tuple[jax.Array, dict]:
        with obs_trace.span(spans.H2D, bytes=obs_trace.nbytes(x, y)):
            x = jnp.asarray(x)
            y = jnp.asarray(y)
            if self._x_sharding is not None:
                x = jax.device_put(x, self._x_sharding)
                y = jax.device_put(y, self._y_sharding)
        with obs_trace.span(spans.DISPATCH), obs_dispatch.step_scope(
                self._dd, (self._ddtok, "fused_step"),
                sig_fn=lambda: (x.shape, str(x.dtype), y.shape)):
            self.state, loss, counters = self._step(self.state, x, y)
        return loss, counters

    def train_epoch(self, xs, ys) -> jax.Array:
        """Run ``xs.shape[0]`` steps in one device dispatch; returns the
        per-step loss series (device array, not blocked on)."""
        xs = jnp.asarray(xs)
        ys = jnp.asarray(ys)
        if self._seq_sharding is not None:
            ep_x, ep_y = self._seq_sharding
            xs = jax.device_put(xs, ep_x)
            ys = jax.device_put(ys, ep_y)
        with obs_dispatch.step_scope(
                self._dd, (self._ddtok, "fused_epoch"),
                sig_fn=lambda: (xs.shape, str(xs.dtype), ys.shape)):
            self.state, losses = self._epoch(self.state, xs, ys)
        return losses

    def train_step_async(self, x, y) -> jax.Array:
        """Like train_step but does not block on the loss transfer —
        use in throughput benchmarks to keep the device queue full. The
        step's counters are not read."""
        with obs_trace.span(spans.STEP_TOTAL):
            return self._dispatch_step(x, y)[0]

    @property
    def params(self) -> Tuple[Any, ...]:
        return self.state.params
