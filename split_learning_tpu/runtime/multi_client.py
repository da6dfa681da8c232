"""Multi-client split learning — BASELINE.md config 3 at the MPMD level.

The reference pins client replicas to 1 (``k8s/split-learning.yaml:49``)
and its server would data-race with more (module-global model mutated in
handlers, SURVEY.md §5). Here N clients — each owning its own bottom-stage
weights and data shard — interleave steps against one shared server half.
The server applies each client's step sequentially under its lock with a
per-client handshake (the "SplitFed v2"-style relay schedule), and the
client bottoms can optionally be FedAvg'd each round.

For the fused/ICI form of the same capability (shared bottom weights,
per-step psum over the ``data`` mesh axis) see
:class:`~split_learning_tpu.runtime.fused.FusedSplitTrainer`.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import numpy as np

from split_learning_tpu.core.stage import SplitPlan
from split_learning_tpu.obs import spans
from split_learning_tpu.obs import trace as obs_trace
from split_learning_tpu.runtime.client import SplitClientTrainer
from split_learning_tpu.runtime.state import TrainState
from split_learning_tpu.transport.base import Transport
from split_learning_tpu.utils.config import Config


class MultiClientSplitRunner:
    """Drives N split clients round-robin against one server party."""

    def __init__(self, plan: SplitPlan, cfg: Config, rng: jax.Array,
                 transport_factory: Callable[[int], Transport],
                 num_clients: Optional[int] = None,
                 sync_bottoms_every: int = 0,
                 logger: Optional[Any] = None,
                 concurrent: bool = False,
                 sync_compress: Optional[str] = None,
                 sync_density: float = 0.1) -> None:
        """transport_factory(client_id) -> a Transport for that client.
        sync_bottoms_every: if > 0, FedAvg the client bottom stages every
        that many rounds (0 = fully personal bottoms).
        concurrent: submit each round's per-client steps from a thread
        pool instead of round-robin — what actually puts concurrent
        traffic in front of a coalescing server (ServerRuntime
        coalesce_max > 1). Round-robin stays the default: it is the
        deterministic relay schedule the interleaving tests pin.
        sync_compress: None (default) keeps sync_bottoms dense and
        bit-for-bit legacy. "topk8"/"clapping" route each client's
        contribution through the wire codec as a delta from the last
        agreed mean (state.compressed_sync_contribution — raw params
        are dense, drift is sparse), with error feedback carrying the
        dropped drift into the next round. The first sync is always
        dense (no reference yet). Byte savings accumulate on
        ``sync_raw_bytes`` / ``sync_wire_bytes``."""
        n = num_clients if num_clients is not None else cfg.num_clients
        if n < 1:
            raise ValueError("need at least one client")
        self.cfg = cfg
        self.sync_bottoms_every = sync_bottoms_every
        self.logger = logger
        self.concurrent = concurrent
        self._pool: Optional[ThreadPoolExecutor] = None
        self.clients: List[SplitClientTrainer] = [
            SplitClientTrainer(
                plan, cfg, jax.random.fold_in(rng, i) if n > 1 else rng,
                transport_factory(i), client_id=i)
            for i in range(n)
        ]
        self._steps = [0] * n
        self._rounds = 0
        if sync_compress not in (None, "topk8", "clapping"):
            raise ValueError(
                f"unknown sync compression {sync_compress!r}")
        self.sync_compress = sync_compress
        self.sync_density = float(sync_density)
        self._sync_ef = None
        self._sync_ref = None  # last agreed mean (the delta reference)
        self.sync_raw_bytes = 0
        self.sync_wire_bytes = 0
        if sync_compress is not None:
            from split_learning_tpu.transport import codec
            self._sync_ef = codec.make_wire_ef(sync_compress)

    def train_round(self, batches_per_client: Sequence[Tuple[np.ndarray, np.ndarray]]
                    ) -> List[float]:
        """One round: each client takes one step — in turn (default), or
        all in flight at once (``concurrent=True``). Either way every
        client's step lands before the round returns, so per-client step
        counters stay sequential and the strict handshake holds."""
        if len(batches_per_client) != len(self.clients):
            raise ValueError(
                f"expected {len(self.clients)} batches, "
                f"got {len(batches_per_client)}")

        # ``round`` is the parent-less root of the round on the driving
        # thread; a client's step (its own thread when concurrent) names
        # it by number in its first span, not as parent
        rnd = self._rounds

        def one(i: int, client: SplitClientTrainer,
                x: np.ndarray, y: np.ndarray) -> float:
            step = self._steps[i]
            loss = client.train_step(x, y, step, round_no=rnd)
            self._steps[i] += 1
            if loss is not None and self.logger is not None:
                self.logger.log_metric(f"loss_client{i}", loss, step=step)
            return loss

        with obs_trace.span(spans.ROUND, step=rnd,
                            clients=len(self.clients)):
            if self.concurrent and len(self.clients) > 1:
                if self._pool is None:
                    self._pool = ThreadPoolExecutor(
                        max_workers=len(self.clients),
                        thread_name_prefix="slt-client")
                futures = [
                    self._pool.submit(one, i, client, x, y)
                    for i, (client, (x, y)) in enumerate(
                        zip(self.clients, batches_per_client))]
                losses = [f.result() for f in futures]
            else:
                losses = [one(i, client, x, y)
                          for i, (client, (x, y)) in enumerate(
                              zip(self.clients, batches_per_client))]
        self._rounds += 1
        if (self.sync_bottoms_every
                and self._rounds % self.sync_bottoms_every == 0):
            self.sync_bottoms()
        return losses

    def train_rounds(self, batch_iters: Sequence[Any],
                     rounds: Optional[int] = None,
                     prefetch: int = 0) -> List[List[float]]:
        """Drive whole rounds from per-client batch iterators (one
        iterable of ``(x, y)`` per client). Stops after ``rounds``
        rounds, or when any client's iterator drains (every round needs
        all clients). ``prefetch`` > 0 wraps each client's iterator in a
        :class:`~split_learning_tpu.data.datasets.DevicePrefetch` of
        that depth, so every client's next batch stages H2D while the
        current round's traffic is in flight; the wrappers are drained
        and joined on every exit path."""
        if len(batch_iters) != len(self.clients):
            raise ValueError(
                f"expected {len(self.clients)} batch iterators, "
                f"got {len(batch_iters)}")
        its: List[Any] = [iter(b) for b in batch_iters]
        wrapped: List[Any] = []
        if prefetch > 0:
            from split_learning_tpu.data.datasets import DevicePrefetch
            its = [DevicePrefetch(it, depth=prefetch) for it in its]
            wrapped = its
        losses: List[List[float]] = []
        try:
            done = 0
            while rounds is None or done < rounds:
                batch = []
                for it in its:
                    try:
                        batch.append(next(it))
                    except StopIteration:
                        return losses
                losses.append(self.train_round(batch))
                done += 1
            return losses
        finally:
            for w in wrapped:
                w.close()

    def close(self) -> None:
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None

    def _flush_server_halves(self) -> None:
        """Flush any in-process server's deferred-apply queue
        (ServerRuntime.flush_deferred, --decouple-bwd). sync_bottoms is
        the fleet's consistency barrier — rounds after it are usually
        checkpointed/evaluated as one unit, so the shared top half must
        not stay up to apply_lag updates behind the bottoms being
        averaged. Duck-typed through the transports (unwrapping chaos/
        delay wrappers via ``.inner``): a LocalTransport exposes its
        ``server``; HTTP transports don't, and a remote decoupled
        server flushes at its own barriers (predict/checkpoint/close)."""
        seen = set()
        for c in self.clients:
            t = getattr(c, "transport", None)
            while t is not None:
                srv = getattr(t, "server", None)
                if srv is not None:
                    flush = getattr(srv, "flush_deferred", None)
                    if callable(flush) and id(srv) not in seen:
                        seen.add(id(srv))
                        flush()
                    break
                t = getattr(t, "inner", None)

    def sync_bottoms(self) -> None:
        """FedAvg the client bottom stages that have actually trained
        (optimizer state stays local). A client whose state is None or
        whose step counter never advanced — fresh init, or every batch
        dropped under the skip policy — is excluded AND left untouched:
        averaging an untrained init into the round would drag every
        bottom toward initialization, and overwriting the dropout's
        params would hide that it never contributed."""
        from split_learning_tpu.runtime.state import (
            compressed_sync_contribution, fedavg_mean)
        self._flush_server_halves()
        ready = [c for c in self.clients
                 if c.state is not None and int(c.state.step) > 0]
        if len(ready) < 2:
            return
        if self._sync_ef is not None and self._sync_ref is not None:
            # compressed round: each contribution is ref + topk8(drift);
            # EF repays each client's dropped drift next round
            contribs = []
            for c in ready:
                rec, raw_b, wire_b = compressed_sync_contribution(
                    self._sync_ef, f"sync_bottom{c.client_id}",
                    c.state.params, self._sync_ref, self.sync_density)
                self.sync_raw_bytes += raw_b
                self.sync_wire_bytes += wire_b
                contribs.append(rec)
            mean_params = fedavg_mean(contribs)
        else:
            # dense round: no reference yet (first sync), or
            # compression off — bit-for-bit the legacy path
            mean_params = fedavg_mean([c.state.params for c in ready])
        if self._sync_ef is not None:
            self._sync_ref = mean_params
        for c in ready:
            c.state = TrainState(params=mean_params,
                                 opt_state=c.state.opt_state,
                                 step=c.state.step)
