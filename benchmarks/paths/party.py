"""The two-party path: one ``ServerRuntime`` that owns the top of the model,
``clients`` ``SplitClientTrainer``s that each own a bottom, driven by
``MultiClientSplitRunner(concurrent=True)`` over ``LocalTransport`` in a
closed loop.  The job's ``coalesce_max`` is the server's; its flush policy
and window stay at the constructor's defaults.

The benchmark's own wrapper sits around each client's transport: it times
the reply, counts the bytes of the cut tensor up and its gradient down, and
puts a ``party.split_step`` span into the trace.  During the check steps
and the warm-up it also holds the clients at a gate, so that which requests
share a group is fixed (the reference follows full groups) and every padded
group shape is compiled before the window; in the window the gate is off.
"""

from __future__ import annotations

import threading
import time

import jax
import numpy as np

from split_learning_tpu.runtime import ServerRuntime
from split_learning_tpu.runtime.multi_client import MultiClientSplitRunner
from split_learning_tpu.transport.local import LocalTransport

from . import first_moment


class _Gate:
    """Lets the clients through in stated groups: a group goes together,
    and only when every client of the groups before it has its reply."""

    def __init__(self, groups: list) -> None:
        self._turn = {c: k for k, group in enumerate(groups) for c in group}
        self._size = [len(g) for g in groups]
        self._waiting = [0] * len(groups)
        self._replied = [0] * len(groups)
        self._cond = threading.Condition()

    def _open(self, k: int) -> bool:
        return (self._waiting[k] == self._size[k]
                and all(r == s for r, s in zip(self._replied[:k], self._size[:k])))

    def arrive(self, client: int) -> None:
        k = self._turn[client]
        with self._cond:
            self._waiting[k] += 1
            self._cond.notify_all()
            if not self._cond.wait_for(lambda: self._open(k), timeout=600):
                raise TimeoutError(f"client {client} held at the gate")

    def replied(self, client: int) -> None:
        with self._cond:
            self._replied[self._turn[client]] += 1
            self._cond.notify_all()


class _TimedTransport:
    """The client's transport, timed and counted by the benchmark."""

    device_native = False

    def __init__(self, inner, driver: "Driver") -> None:
        self.inner, self.stats, self._driver = inner, inner.stats, driver

    def split_step(self, activations, labels, step, client_id=0):
        gate = self._driver.gate
        if gate is not None:
            gate.arrive(client_id)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("party.split_step"):
            grads, loss = self.inner.split_step(activations, labels, step, client_id)
        self._driver.reply_seconds.append(time.perf_counter() - t0)
        self._driver.wire_bytes.append(
            np.asarray(activations).nbytes + np.asarray(grads).nbytes)
        if gate is not None:
            gate.replied(client_id)
        return grads, loss

    def __getattr__(self, name):
        return getattr(self.inner, name)


class Driver:
    unit = "replies"
    span = "party.train_round"

    def __init__(self, plan, cfg, key, job: dict, sample) -> None:
        self.clients = job["clients"]
        self.coalesce_max = job.get("coalesce_max", 1)
        self.gate, self._gated = None, False
        self.reply_seconds, self.wire_bytes = [], []
        self.server = ServerRuntime(plan, cfg, key, sample,
                                    coalesce_max=self.coalesce_max)
        self.runner = MultiClientSplitRunner(
            plan, cfg, key,
            lambda i: _TimedTransport(LocalTransport(self.server), self),
            num_clients=self.clients, concurrent=True)

    def check_gate(self, on: bool) -> None:
        self._gated = on and self.coalesce_max > 1

    def step(self, batch) -> list:
        if self._gated:
            self.gate = _Gate([list(range(self.clients))])
        with jax.profiler.TraceAnnotation(self.span):
            losses = self.runner.train_round(batch)
        self.gate = None
        return losses

    def warm_up(self, batch) -> None:
        """A group pads its rows to a power of two, so groups of 1, 2 and
        3-or-4 requests are three programs: meet the first two here (the
        check steps were full groups)."""
        if self.coalesce_max <= 1 or self.clients < 2:
            return
        ids = list(range(self.clients))
        for groups in ([[c] for c in ids],
                       [ids[i:i + 2] for i in range(0, len(ids), 2)]):
            self.gate = _Gate(groups)
            self.runner.train_round(batch)
        self.gate = None

    def sync(self) -> None:
        jax.block_until_ready([c.state for c in self.runner.clients])
        jax.block_until_ready(self.server.state)

    def params(self) -> dict:
        out = {f"client{i}": c.state.params for i, c in enumerate(self.runner.clients)}
        out["server"] = self.server.state.params
        return out

    def first_moments(self) -> dict:
        out = {f"client{i}": first_moment(c.state.opt_state)
               for i, c in enumerate(self.runner.clients)}
        out["server"] = first_moment(self.server.state.opt_state)
        return out

    def counters(self) -> dict:
        return dict(self.server.health().get("coalescing", {}))

    def close(self) -> None:
        self.runner.close()
        self.server.close()
