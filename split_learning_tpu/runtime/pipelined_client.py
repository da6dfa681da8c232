"""Pipelined split client — W batches in flight over the transport.

The reference's hot loop is strictly lock-step: one batch in flight, the
client idle for the full pickle/HTTP round trip every step
(``src/client_part.py:110-133``). The fused path removes the round trip
entirely on-chip; for the two-party *network* topology the classic fix
(PiPar, arXiv:2302.12803; overlap scheduling) is to keep a bounded window
of W cut-layer exchanges in flight, so client compute and the wire overlap
and steady-state throughput approaches ``1 / max(server_step, wire)``
instead of ``1 / (client_fwd + round_trip + client_bwd)``.

Semantics (explicit, opt-in):

- **Bounded staleness W.** The forward for step k runs under the params
  that have absorbed gradients of steps <= k-W (asynchronous SGD with
  delay < W). W=1 degenerates to the synchronous loop exactly — pinned by
  tests/test_pipelined_client.py against SplitClientTrainer.
- **Consistent gradients.** Each in-flight step stashes the param tree its
  forward used; the backward re-runs the forward under THOSE params
  (rematerialization, same as stage_backward) so the vjp is the true
  gradient of the function that actually produced the shipped activations.
  The (delayed) update is then applied to the current state.
- **Ordered application.** Cut-layer gradients are applied in step order
  regardless of wire completion order, so the client's param trajectory is
  deterministic given server replies.
- **Server side**: requests may ARRIVE out of order (W lanes), so the
  server must run with ``strict_steps=False`` when W > 1; its lock
  serializes the actual half-steps (arrival-order async SGD on the server
  half — the server's own params see no staleness, only reordering).

Failure policy is RAISE: a perf-oriented pipeline has no sensible
batch-drop semantics; wrap the transport in retries if the link flakes.
"""

from __future__ import annotations

import contextlib
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Callable, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from split_learning_tpu.core.stage import stage_backward
from split_learning_tpu.obs import spans
from split_learning_tpu.obs import trace as obs_trace
from split_learning_tpu.runtime.client import StepRecord
from split_learning_tpu.runtime.state import (
    TrainState, jit_apply_grads, make_state, make_tx)
from split_learning_tpu.transport.base import Transport
from split_learning_tpu.utils.config import Config


class PipelinedSplitClientTrainer:
    """Split client with a depth-W in-flight window over the transport."""

    def __init__(self, plan: Any, cfg: Config, rng: jax.Array,
                 transport: Transport, depth: int = 2,
                 transport_factory: Optional[Callable[[], Transport]] = None,
                 logger: Optional[Any] = None, client_id: int = 0) -> None:
        """``transport`` serves lane 0; when depth > 1 and the transport is
        not safe for concurrent calls (HttpTransport: one requests.Session),
        pass ``transport_factory`` to give each extra lane its own
        connection. LocalTransport is lock-serialized server-side and may be
        shared."""
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        client_idx = plan.stages_of("client")
        if client_idx != (0,):
            raise ValueError("PipelinedSplitClientTrainer expects the "
                             "client to own exactly stage 0")
        self.plan = plan
        self.cfg = cfg
        self.depth = depth
        self.logger = logger
        self.client_id = client_id
        self.stage = plan.stages[0]
        self._tx = make_tx(cfg)
        self.state: Optional[TrainState] = None
        self._rng = rng

        self._transports: List[Transport] = [transport]
        for _ in range(depth - 1):
            self._transports.append(
                transport_factory() if transport_factory else transport)
        self._pool = ThreadPoolExecutor(max_workers=depth)

        stage = self.stage
        self._fwd = jax.jit(stage.apply)
        self._bwd = jax.jit(
            lambda p, x, g: stage_backward(stage, p, x, g))
        self._apply_grads = jit_apply_grads(self._tx)

    def ensure_init(self, sample_x: np.ndarray) -> None:
        if self.state is None:
            # shared-seed convention (SplitClientTrainer.ensure_init)
            params = self.plan.init(self._rng, jnp.asarray(sample_x))[0]
            self.state = make_state(params, self._tx)

    # ------------------------------------------------------------------ #
    def _submit(self, lane: int, acts: np.ndarray, y: np.ndarray,
                step: int) -> Future:
        transport = self._transports[lane]
        # copy the labels: the lane thread serializes them up to depth-1
        # batches later, and np.asarray of a caller-recycled buffer would
        # hand it different data (same hazard as x, fixed in train())
        y_copy = np.array(y, copy=True)
        # the trace id must ride the LANE thread's CTX (thread-local),
        # so the transport span opens there; its tid is the lane, the
        # Chrome-trace row that makes the W-deep overlap visible
        def call():
            with obs_trace.span(spans.TRANSPORT, tid=lane, step=step,
                                trace=(self.client_id, step)):
                return transport.split_step(acts, y_copy, step,
                                            self.client_id)

        return self._pool.submit(call)

    def _apply(self, entry) -> float:
        """Apply one completed exchange (in step order): remat backward
        under the params the forward used, update current state."""
        params_then, xd, future = entry
        g_acts, loss = future.result()
        with obs_trace.span(spans.CLIENT_BWD, tid=self.client_id):
            g_params = self._bwd(params_then, xd, jnp.asarray(g_acts))
            self.state = self._apply_grads(self.state, g_params)
        return loss

    def train(self, data_iter: Callable[[], Iterable[Tuple[np.ndarray, np.ndarray]]],
              epochs: Optional[int] = None, start_step: int = 0,
              on_epoch_end: Optional[Callable[[int, int], None]] = None,
              prefetch: int = 0) -> List[StepRecord]:
        """Full run; the in-flight window drains at every epoch boundary so
        ``on_epoch_end`` (checkpoint hook) sees a quiesced client.
        ``prefetch`` > 0 wraps each epoch's iterator in a DevicePrefetch
        of that depth (batch k+1's H2D overlaps the in-flight window)."""
        records: List[StepRecord] = []
        step = start_step
        for epoch in range(epochs if epochs is not None else self.cfg.epochs):
            with contextlib.ExitStack() as stack:
                it: Iterable = data_iter()
                if prefetch > 0:
                    from split_learning_tpu.data.datasets import DevicePrefetch
                    it = stack.enter_context(
                        DevicePrefetch(it, depth=prefetch))
                window: List[Tuple[Any, np.ndarray, Future, int]] = []
                for x, y in it:
                    self.ensure_init(x)
                    if len(window) == self.depth:
                        entry = window.pop(0)
                        loss = self._apply(entry[:3])
                        self._record(records, entry[3], epoch, loss)
                    # stash the MATERIALIZED device array, not the caller's
                    # buffer: the remat backward re-reads it up to depth-1
                    # batches later, and a loader that recycles one numpy
                    # buffer per batch would silently hand it different data
                    with obs_trace.span(spans.CLIENT_FWD,
                                        tid=self.client_id, step=step):
                        xd = jnp.asarray(x)
                        acts = np.asarray(self._fwd(self.state.params, xd))
                    lane = step % self.depth
                    window.append((self.state.params, xd,
                                   self._submit(lane, acts, y, step), step))
                    step += 1
                for entry in window:  # drain
                    loss = self._apply(entry[:3])
                    self._record(records, entry[3], epoch, loss)
            if on_epoch_end is not None:
                on_epoch_end(epoch, step)
        return records

    def _record(self, records: List[StepRecord], step: int, epoch: int,
                loss: float) -> None:
        records.append(StepRecord(step=step, loss=loss, epoch=epoch))
        if self.logger is not None:
            self.logger.log_metric("loss", loss, step=step)

    def close(self) -> None:
        self._pool.shutdown(wait=True)
        for t in self._transports[1:]:
            t.close()

    @property
    def stats(self):
        """Merged TransportStats over ALL lanes — lane 0's view alone
        undercounts round trips and bytes by ~depth."""
        from split_learning_tpu.transport.base import TransportStats
        # dedupe: without a transport_factory every lane shares one
        # transport object, and merging it depth times would double-count
        unique = {id(t): t for t in self._transports}
        return TransportStats.merged([t.stats for t in unique.values()])

    @property
    def params(self):
        return self.state.params
