"""Client-party trainers: split, U-shaped split, and federated loops.

Re-expresses ``src/client_part.py``'s three loops TPU-natively:

- split loop ≡ ``train_split_learning()`` (``src/client_part.py:103-141``):
  forward the bottom stage, ship activations through the transport, receive
  the cut-layer gradient, backprop it into the bottom stage, SGD step.
  The reference splices the autograd tape manually
  (``requires_grad_(True)`` + ``activations.backward(grad)``,
  ``src/server_part.py:45`` / ``src/client_part.py:132``); here the splice
  is a ``jax.vjp`` whose cotangent arrives from the transport. The backward
  recomputes the bottom-stage forward (rematerialization — the
  TPU-idiomatic trade of FLOPs for memory, and it keeps both halves of the
  step independently jittable around the host-side transport boundary).
- U-shaped loop (BASELINE.md config 5): client owns bottom A and head C;
  labels never leave the client — two transport hops per step.
- federated loop ≡ ``train_federated_learning()``
  (``src/client_part.py:143-198``): local epochs on the full composition,
  per-epoch FedAvg through the transport.

Failure policy is explicit (SURVEY.md §3.4): the reference silently drops
batches on any error (``continue`` at ``src/client_part.py:127-129,140-141``);
here the policy is configurable — "raise" (default), "retry" (bounded), or
"skip" (reference-compatible, but counted and reported).
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from split_learning_tpu.core.losses import final_loss, plan_loss
from split_learning_tpu.core.stage import SplitPlan, stage_backward
from split_learning_tpu.obs import dispatch_debug as obs_dispatch
from split_learning_tpu.obs import spans
from split_learning_tpu.obs import trace as obs_trace
from split_learning_tpu.runtime.state import (
    TrainState, apply_grads, jit_apply_grads, make_state, make_tx)
from split_learning_tpu.transport.base import (
    Backpressure, Transport, TransportError)
from split_learning_tpu.utils.config import Config


@dataclasses.dataclass
class StepRecord:
    step: int
    loss: float
    epoch: int


class FailurePolicy:
    RAISE = "raise"
    RETRY = "retry"
    SKIP = "skip"


class SplitClientTrainer:
    """The classic 2-party split client (bottom stage A)."""

    def __init__(self, plan: SplitPlan, cfg: Config, rng: jax.Array,
                 transport: Transport,
                 failure_policy: str = FailurePolicy.RAISE,
                 max_retries: int = 3,
                 retry_backoff: float = 0.5,
                 logger: Optional[Any] = None,
                 client_id: int = 0,
                 breaker: Optional[Any] = None) -> None:
        """retry_backoff: base seconds for exponential backoff between
        retries (0.5 -> 0.5, 1, 2, 4...). Without it, a restarting server
        (seconds of downtime) would exhaust every retry in microseconds —
        elastic recovery needs the client to outwait the outage.

        breaker: optional CircuitBreaker (runtime/breaker.py). When set,
        it observes every transport outcome; once open, retry waits
        become cheap /health probes with backoff+jitter instead of blind
        sleeps followed by full-payload POSTs at a dead server."""
        self.plan = plan
        self.cfg = cfg
        self.transport = transport
        self.failure_policy = failure_policy
        self.max_retries = max_retries
        self.retry_backoff = retry_backoff
        self.breaker = breaker
        self.logger = logger
        self.client_id = client_id
        self.dropped_batches = 0

        client_idx = plan.stages_of("client")
        if client_idx != (0,):
            raise ValueError("SplitClientTrainer expects the client to own "
                             "exactly stage 0; use USplitClientTrainer for "
                             "U-shaped plans")
        self.stage = plan.stages[0]
        # init only the client stage (server inits its own half)
        self._tx = make_tx(cfg)
        self.state: Optional[TrainState] = None
        self._rng = rng

        stage = self.stage
        self._fwd = jax.jit(stage.apply)
        self._bwd = jax.jit(
            lambda p, x, g: stage_backward(stage, p, x, g))
        self._apply_grads = jit_apply_grads(self._tx)
        # dispatch watchdog (slt-lint phase 2): None unless enabled
        self._dd = obs_dispatch.attach()
        self._ddtok = obs_dispatch.token()

    @property
    def wire_ef(self) -> Optional[Any]:
        """The transport's up-direction topk8 error-feedback buffer, when
        the wire mode carries one (HttpTransport/LocalTransport with
        compress="topk8"; None otherwise). Client-side EF state lives on
        the transport — it belongs to the wire, not the weights — but is
        surfaced here so restore logic can reset it alongside the
        TrainState (a pre-restore residual describes a stream the
        restored weights never produced)."""
        return getattr(self.transport, "_ef", None)

    def ensure_init(self, sample_x: np.ndarray) -> None:
        if self.state is None:
            # Convention: every party runs plan.init from the shared seed and
            # keeps its own stages — so a split run and a monolithic run with
            # the same seed start from identical parameters (the equivalence
            # property SURVEY.md §4 item 3 requires).
            params = self.plan.init(self._rng, jnp.asarray(sample_x))[0]
            self.state = make_state(params, self._tx)

    def train_step(self, x: np.ndarray, y: np.ndarray, step: int,
                   round_no: Optional[int] = None) -> Optional[float]:
        """One split step; returns the loss, or None if the batch was
        dropped under the 'skip' policy. ``round_no`` is the
        MultiClientSplitRunner round this step belongs to, kept as the
        ``round`` attribute of the step's root span.

        Tracing (obs/trace.py): the step is one ``step_total`` span
        tiled by ``client_fwd`` (the inputs' ``h2d``, the jitted
        forward, the cut tensor's ``d2h``), ``transport``,
        ``client_bwd`` (the ``h2d`` of the inputs again and of the cut
        gradient, the jitted backward) and ``opt_apply`` (the call of
        the jitted optimizer step, ``jit_apply_grads``: one dispatch,
        which donates the optimizer state and the gradients). Off (the
        default) a span is an annotation and nothing else: no record,
        no trace id, no payload key. On, the step gets a trace id
        (propagated to the server through the transport via CTX) and
        one record a span. Tracing adds no sync either way: a span
        measures what this thread did, including the waits the program
        itself makes (``np.asarray(acts)`` blocks on the device, and is
        where the previous step's backward and optimizer programs, both
        dispatched without a wait, show up); what the device did
        meanwhile is the device trace's to say."""
        self.ensure_init(x)
        with obs_trace.span(spans.STEP_TOTAL, tid=self.client_id, step=step,
                            trace=(self.client_id, step), round=round_no):
            return self._train_step(x, y, step)

    def _train_step(self, x: np.ndarray, y: np.ndarray,
                    step: int) -> Optional[float]:
        with obs_trace.span(spans.CLIENT_FWD):
            with obs_dispatch.step_scope(
                    self._dd, (self._ddtok, "client_fwd"),
                    sig_fn=lambda: (x.shape, str(x.dtype))):
                with obs_trace.span(spans.H2D, bytes=obs_trace.nbytes(x)):
                    x_dev = jnp.asarray(x)
                acts = self._fwd(self.state.params, x_dev)
                del x_dev  # a temporary, as when the call made it inline
            with obs_dispatch.expected_d2h(self._dd), \
                    obs_trace.span(spans.D2H, bytes=obs_trace.nbytes(acts)):
                acts_host = np.asarray(acts)

        attempt = 0
        while True:
            try:
                if self.breaker is not None:
                    # while open this probes /health (backoff+jitter)
                    # instead of letting the full-payload POST bounce
                    # off a dead server; raises TransportError when the
                    # open budget is spent, handled below like any wire
                    # failure
                    self.breaker.before_attempt()
                with obs_trace.span(spans.TRANSPORT):
                    g_acts, loss = self.transport.split_step(
                        acts_host, np.asarray(y), step, self.client_id)
                if self.breaker is not None:
                    self.breaker.record_success()
                break
            except Backpressure as exc:
                # explicit 429/Retry-After: flow control from a healthy
                # server, not a wire failure — never counts toward the
                # breaker threshold, and the wait is the peer's advised
                # delay instead of blind exponential backoff
                attempt += 1
                if (self.failure_policy == FailurePolicy.RETRY
                        and attempt <= self.max_retries):
                    if self.breaker is not None:
                        self.breaker.backpressure_wait(exc.retry_after_s)
                    elif exc.retry_after_s > 0:
                        time.sleep(exc.retry_after_s)
                    continue
                if self.failure_policy == FailurePolicy.SKIP:
                    self.dropped_batches += 1
                    return None
                raise
            except TransportError:
                if self.breaker is not None:
                    self.breaker.record_failure()
                attempt += 1
                if (self.failure_policy == FailurePolicy.RETRY
                        and attempt <= self.max_retries):
                    # with an OPEN breaker the wait happens in
                    # before_attempt (health probes); the blind sleep is
                    # for transient blips below the breaker threshold
                    if self.retry_backoff > 0 and not (
                            self.breaker is not None
                            and self.breaker.state == "open"):
                        time.sleep(self.retry_backoff * 2 ** (attempt - 1))
                    continue
                if self.failure_policy == FailurePolicy.SKIP:
                    # reference behavior: drop the batch, keep going
                    # (src/client_part.py:127-129) — but count it.
                    self.dropped_batches += 1
                    return None
                raise

        with obs_trace.span(spans.CLIENT_BWD):
            with obs_dispatch.step_scope(
                    self._dd, (self._ddtok, "client_bwd"),
                    sig_fn=lambda: (x.shape, str(x.dtype),
                                    np.asarray(g_acts).shape)):
                with obs_trace.span(spans.H2D,
                                    bytes=obs_trace.nbytes(x, g_acts)):
                    x_dev, g_dev = jnp.asarray(x), jnp.asarray(g_acts)
                g_params = self._bwd(self.state.params, x_dev, g_dev)
                del x_dev, g_dev
        with obs_trace.span(spans.OPT_APPLY):
            self.state = self._apply_grads(self.state, g_params)
        return loss

    def train(self, data_iter: Callable[[], Iterable[Tuple[np.ndarray, np.ndarray]]],
              epochs: Optional[int] = None, start_step: int = 0,
              on_epoch_end: Optional[Callable[[int, int], None]] = None,
              prefetch: int = 0) -> List[StepRecord]:
        """Full training run ≡ train_split_learning (3 epochs default).

        ``start_step`` seeds the client-authoritative step counter (resume);
        ``on_epoch_end(epoch, next_step)`` fires after each epoch
        (checkpoint hook). ``prefetch`` > 0 wraps each epoch's iterator
        in a :class:`~split_learning_tpu.data.datasets.DevicePrefetch`
        of that depth, so batch k+1's H2D staging overlaps step k's
        round trip (same batch sequence, pinned by tests)."""
        records: List[StepRecord] = []
        step = start_step
        for epoch in range(epochs if epochs is not None else self.cfg.epochs):
            with contextlib.ExitStack() as stack:
                it: Iterable = data_iter()
                if prefetch > 0:
                    from split_learning_tpu.data.datasets import DevicePrefetch
                    it = stack.enter_context(DevicePrefetch(it, depth=prefetch))
                for x, y in it:
                    loss = self.train_step(x, y, step)
                    if loss is not None:
                        records.append(StepRecord(step=step, loss=loss,
                                                  epoch=epoch))
                        if self.logger is not None:
                            self.logger.log_metric("loss", loss, step=step)
                    step += 1
            if on_epoch_end is not None:
                on_epoch_end(epoch, step)
        return records


class USplitClientTrainer:
    """U-shaped client: owns bottom stage A and head stage C; labels and
    logits never leave the client (BASELINE.md config 5)."""

    def __init__(self, plan: SplitPlan, cfg: Config, rng: jax.Array,
                 transport: Transport, logger: Optional[Any] = None,
                 client_id: int = 0) -> None:
        if plan.owners != ("client", "server", "client"):
            raise ValueError("USplitClientTrainer expects owners "
                             "(client, server, client)")
        self.plan = plan
        self.cfg = cfg
        self.transport = transport
        self.logger = logger
        self.client_id = client_id
        self._tx = make_tx(cfg)
        self.state_a: Optional[TrainState] = None
        self.state_c: Optional[TrainState] = None
        self._rng = rng

        stage_a, _, stage_c = plan.stages

        self._fwd_a = jax.jit(lambda p, x: stage_a.apply(p, x))

        def head_step(params_c, feats, labels):
            def loss_fn(p, f):
                return final_loss(stage_c, p, f, labels)
            loss, (g_c, g_feats) = jax.value_and_grad(
                loss_fn, argnums=(0, 1))(params_c, feats)
            return loss, g_c, g_feats

        self._head_step = jax.jit(head_step)
        self._bwd_a = jax.jit(
            lambda p, x, g: stage_backward(stage_a, p, x, g))
        # one function for both owned stages: a trace per state structure
        self._apply_grads = jit_apply_grads(self._tx)
        # dispatch watchdog (slt-lint phase 2): None unless enabled
        self._dd = obs_dispatch.attach()
        self._ddtok = obs_dispatch.token()

    def ensure_init(self, sample_x: np.ndarray) -> None:
        if self.state_a is None:
            # shared-seed convention (see SplitClientTrainer.ensure_init):
            # init the whole plan, keep the client-owned stages (0 and 2);
            # the trunk params computed in passing are discarded.
            params = self.plan.init(self._rng, jnp.asarray(sample_x))
            self.state_a = make_state(params[0], self._tx)
            self.state_c = make_state(params[2], self._tx)

    def train_step(self, x: np.ndarray, y: np.ndarray, step: int) -> float:
        self.ensure_init(x)
        dd = self._dd
        sig = (x.shape, str(x.dtype)) if dd is not None else None
        with obs_dispatch.step_scope(dd, (self._ddtok, "u_fwd_a"),
                                     sig_fn=lambda: sig):
            acts = self._fwd_a(self.state_a.params, jnp.asarray(x))
        # hop 1: activations -> trunk features
        with obs_dispatch.expected_d2h(dd):
            acts_host = np.asarray(acts)
        feats = self.transport.u_forward(acts_host, step, self.client_id)
        # local head: loss + grads (labels stay here)
        with obs_dispatch.step_scope(dd, (self._ddtok, "u_head_step"),
                                     sig_fn=lambda: sig):
            loss, g_c, g_feats = self._head_step(
                self.state_c.params, jnp.asarray(feats), jnp.asarray(y))
        self.state_c = self._apply_grads(self.state_c, g_c)
        # hop 2: feature grads -> activation grads (server updates trunk)
        with obs_dispatch.expected_d2h(dd):
            g_feats_host = np.asarray(g_feats)
        g_acts = self.transport.u_backward(g_feats_host, step,
                                           self.client_id)
        with obs_dispatch.step_scope(dd, (self._ddtok, "u_bwd_a"),
                                     sig_fn=lambda: sig):
            g_a = self._bwd_a(self.state_a.params, jnp.asarray(x),
                              jnp.asarray(g_acts))
        self.state_a = self._apply_grads(self.state_a, g_a)
        with obs_dispatch.expected_d2h(dd):
            return float(loss)

    def train(self, data_iter, epochs: Optional[int] = None,
              start_step: int = 0,
              on_epoch_end: Optional[Callable[[int, int], None]] = None
              ) -> List[StepRecord]:
        records: List[StepRecord] = []
        step = start_step
        for epoch in range(epochs if epochs is not None else self.cfg.epochs):
            for x, y in data_iter():
                loss = self.train_step(x, y, step)
                records.append(StepRecord(step=step, loss=loss, epoch=epoch))
                if self.logger is not None:
                    self.logger.log_metric("loss", loss, step=step)
                step += 1
            if on_epoch_end is not None:
                on_epoch_end(epoch, step)
        return records


class FederatedClientTrainer:
    """Federated client ≡ train_federated_learning (src/client_part.py:143-198):
    local full-model epochs, per-epoch weight sync through the transport."""

    def __init__(self, plan: SplitPlan, cfg: Config, rng: jax.Array,
                 transport: Transport, logger: Optional[Any] = None) -> None:
        self.plan = plan
        self.cfg = cfg
        self.transport = transport
        self.logger = logger
        self._tx = make_tx(cfg)
        self.state: Optional[TrainState] = None
        self._rng = rng

        def step_fn(state: TrainState, x, y):
            def loss_fn(params):
                return plan_loss(plan, params, x, y)
            loss, grads = jax.value_and_grad(loss_fn)(state.params)
            return apply_grads(self._tx, state, grads), loss

        self._step = jax.jit(step_fn, donate_argnums=(0,))
        # dispatch watchdog (slt-lint phase 2): None unless enabled
        self._dd = obs_dispatch.attach()
        self._ddtok = obs_dispatch.token()

    def ensure_init(self, sample_x: np.ndarray) -> None:
        if self.state is None:
            params = tuple(self.plan.init(self._rng, jnp.asarray(sample_x)))
            self.state = make_state(params, self._tx)

    def train(self, data_iter, epochs: Optional[int] = None,
              start_step: int = 0,
              on_epoch_end: Optional[Callable[[int, int], None]] = None
              ) -> List[StepRecord]:
        records: List[StepRecord] = []
        step = start_step
        for epoch in range(epochs if epochs is not None else self.cfg.epochs):
            epoch_losses = []
            n_examples = 0
            for x, y in data_iter():
                self.ensure_init(x)
                with obs_dispatch.step_scope(
                        self._dd, (self._ddtok, "fed_step"),
                        sig_fn=lambda: (np.asarray(x).shape,
                                        np.asarray(y).shape)):
                    self.state, loss = self._step(
                        self.state, jnp.asarray(x), jnp.asarray(y))
                with obs_dispatch.expected_d2h(self._dd):
                    epoch_losses.append(float(loss))
                n_examples += len(y)
                step += 1
            avg_loss = float(np.mean(epoch_losses)) if epoch_losses else float("nan")
            # per-epoch sync ≡ src/client_part.py:171-194, weighted by
            # this client's example count (canonical FedAvg)
            with obs_dispatch.expected_d2h(self._dd):
                params_np = jax.tree_util.tree_map(np.asarray,
                                                   self.state.params)
            agg = self.transport.aggregate(params_np, epoch, avg_loss, step,
                                           num_examples=n_examples or None)
            agg = jax.tree_util.tree_map(jnp.asarray, agg)
            self.state = TrainState(params=agg, opt_state=self.state.opt_state,
                                    step=self.state.step)
            records.append(StepRecord(step=step, loss=avg_loss, epoch=epoch))
            if self.logger is not None:
                self.logger.log_metric("loss", avg_loss, step=step)
                self.logger.log_metric("epoch", epoch, step=step)
            if on_epoch_end is not None:
                on_epoch_end(epoch, step)
        return records
