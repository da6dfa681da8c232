"""Device-native hop transport — co-located stages, zero host copies
(PR 16).

The MPMD chain (PR 14) kept the classic transport contract on every
hop: host numpy in, host numpy out. Correct everywhere, but when the
driver and its ``StageRuntime`` peers share one process the contract is
pure overhead — every cut activation bounced device -> host -> device
per wire, twice per microbatch, even with zero network between the
parties. The survey names ICI-native transport as the TPU axis the
reference never had; this transport is that axis for the MPMD chain:

- ``hop_forward`` / ``hop_backward`` / ``hop_loss`` hand the peer
  stage's :class:`~split_learning_tpu.runtime.stage.StageRuntime` the
  DEVICE buffer as-is (``device=True`` calling convention) and relay
  the device reply back to the driver untouched. No ``np.asarray``, no
  codec round-trip; on one device the very same ``jax.Array`` flows
  through the whole chain.
- With a named ``pipe`` mesh (``parallel.mesh.make_mesh``), the hop
  additionally moves the buffer between pipe ranks with the SAME
  ``jax.lax.ppermute`` collective the fused single-program trainer uses
  (``parallel.pipeline.make_hop_shift``) — the cut crosses ICI inside
  one jitted program, never through host.
- The ONE sanctioned D2H is the loss/metrics edge: ``hop_loss`` floats
  the per-microbatch loss scalar inside the dispatch watchdog's
  ``expected_d2h`` region, exactly like the runner's own loss read.

Accounting: the transfer guard is inert on the CPU backend (host
buffers are zero-copy views), so zero-copy is additionally pinned by an
explicit counter — ``stats.counters["hop_host_copies"]``
(:data:`~split_learning_tpu.obs.spans.HOP_HOST_COPIES`) increments
whenever a hop payload or reply turns out to be a host ``np.ndarray``.
On the intended path it stays exactly 0; the bench leg and
tests/test_device_transport.py gate on it.

Scope: pipeline hops + predict + health only. The 2-party ops
(``split_step`` / ``u_forward`` / ``u_backward`` / ``aggregate``) have
no co-located fast path here — use LocalTransport; calling them is a
programming error, not a transient wire fault.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np

from split_learning_tpu.obs import dispatch_debug as obs_dispatch
from split_learning_tpu.obs import flight as obs_flight
from split_learning_tpu.obs import spans
from split_learning_tpu.transport.base import (
    Backpressure, Transport, TransportError, timed)


class DeviceTransport(Transport):
    """In-process wire to one StageRuntime, device buffers end to end.

    ``mesh``: optional named mesh with a ``pipe`` axis covering the
    chain's stages. When given, each hop payload rides a ``ppermute``
    between the sending and receiving pipe ranks (forward: stage-1 ->
    stage; backward: stage+1 -> stage — the hub relays, the collective
    moves the bytes). Without it each stage receives a payload onto its
    own device (``PartyRuntime._to_dev``: a plain D2D ``device_put``, ICI
    on a multi-chip host) and this wire returns stage 1's cotangents to
    the hub's; on one device the identical buffer passes through.
    """

    device_native = True

    def __init__(self, server: Any, mesh: Optional[Any] = None) -> None:
        super().__init__()
        self.server = server
        self.stage_index = int(getattr(server, "stage_index", -1))
        self._num_stages = int(server.plan.num_stages) \
            if hasattr(server, "plan") else 0
        self._mesh = mesh
        if mesh is not None:
            from split_learning_tpu.parallel.mesh import PIPE_AXIS
            if PIPE_AXIS not in mesh.axis_names:
                raise ValueError(
                    f"DeviceTransport mesh needs a {PIPE_AXIS!r} axis")
            if mesh.shape[PIPE_AXIS] < self._num_stages:
                raise ValueError(
                    f"pipe axis size {mesh.shape[PIPE_AXIS]} < "
                    f"{self._num_stages} stages")
        # the hub's device (pipe rank 0; PipelineRunner pins its state
        # there): replies consumed by the DRIVER's own programs
        # (wire-to-stage-1 cotangents) get device_put here so the hub's
        # jits keep one stable placement — D2D only, never through host
        self._hub_dev = (mesh.devices.flat[0] if mesh is not None
                         else jax.devices()[0])
        # one jitted shuttle per (src, dst, shape, dtype) — cached so
        # steady state never recompiles (the watchdog step_scope below
        # pins that)
        self._shifts: Dict[Tuple, Any] = {}
        self._dd = obs_dispatch.attach()
        self._ddtok = obs_dispatch.token()

    # ------------------------------------------------------------------ #
    def _call(self, fn, *args, **kw):
        from split_learning_tpu.runtime.server import ProtocolError
        try:
            return fn(*args, **kw)
        except (ProtocolError, Backpressure):
            raise
        except Exception as exc:
            raise TransportError(str(exc)) from exc

    def _note_host(self, *arrays: Any) -> None:
        """The zero-copy pin: a host ndarray on the hop path means some
        layer materialized where none should — count it (the CPU
        backend's transfer guard cannot)."""
        for a in arrays:
            if isinstance(a, np.ndarray):
                self.stats.incr(spans.HOP_HOST_COPIES)

    def _shuttle(self, x: Any, src: int, dst: int) -> Any:
        """Move one hop payload src pipe rank -> dst pipe rank via the
        in-mesh ppermute collective; identity when no mesh is bound."""
        if self._mesh is None or not isinstance(x, jax.Array):
            return x
        key = (src, dst, tuple(x.shape), str(x.dtype))
        fn = self._shifts.get(key)
        if fn is None:
            from split_learning_tpu.parallel.pipeline import make_hop_shift
            fn = make_hop_shift(self._mesh, src, dst)
            self._shifts[key] = fn
        with obs_dispatch.step_scope(
                self._dd, (self._ddtok, f"hop_shift{src}to{dst}"),
                sig_fn=lambda: key):
            return fn(x)

    def _to_hub(self, g: Any) -> Any:
        """Replies the DRIVER's own programs consume (the stage-1
        wire's cotangents) move to the hub's device: stage 1 lives on
        its own device (or spans its own pjit mesh) whenever the backend
        has one per stage, and a reply left there would re-lay the hub's
        params after the first apply and retrace every hub program at
        step 2. Pure D2D — device_put across devices is the sanctioned
        move, and the same buffer when it is already on the hub's."""
        if self.stage_index == 1 and isinstance(g, jax.Array):
            return jax.device_put(g, self._hub_dev)
        return g

    def _hop_flight(self, send: bool, op: str, step: int, mb: int,
                    client_id: int) -> None:
        fl = obs_flight.get_recorder()
        if fl is None:
            return
        kw = dict(step=int(step), client_id=int(client_id),
                  party="client", op=op, mb=int(mb),
                  stage=self.stage_index)
        fl.record(spans.FL_HOP_SEND if send else spans.FL_HOP_RECV, **kw)

    # -- the three hop ops: device buffers straight through ------------- #
    def hop_forward(self, x: Any, step: int, mb: int = 0,
                    client_id: int = 0) -> Any:
        self._hop_flight(True, "hop_fwd", step, mb, client_id)
        with timed(self.stats):
            self._note_host(x)
            x = self._shuttle(x, self.stage_index - 1, self.stage_index)
            y = self._call(self.server.hop_forward, x, step, mb,
                           client_id, device=True)
            self._note_host(y)
        self._hop_flight(False, "hop_fwd", step, mb, client_id)
        return y

    def hop_backward(self, g_out: Any, step: int, mb: int = 0,
                     client_id: int = 0) -> Any:
        self._hop_flight(True, "hop_bwd", step, mb, client_id)
        with timed(self.stats):
            self._note_host(g_out)
            g_out = self._shuttle(g_out, self.stage_index + 1,
                                  self.stage_index)
            g = self._call(self.server.hop_backward, g_out, step, mb,
                           client_id, device=True)
            self._note_host(g)
            g = self._to_hub(g)
        self._hop_flight(False, "hop_bwd", step, mb, client_id)
        return g

    def hop_loss(self, x: Any, labels: Any, step: int, mb: int = 0,
                 client_id: int = 0) -> Tuple[Any, float]:
        """Reply contract unchanged for the driver: (cut cotangent —
        here a device buffer — and a HOST float loss). The scalar read
        is the chain's one sanctioned D2H, fenced by ``expected_d2h``
        so the dispatch watchdog knows it by name; labels ride in as
        the driver sliced them (host -> device is free and sanctioned —
        the guard polices D2H, and labels originate on host)."""
        self._hop_flight(True, "hop_loss", step, mb, client_id)
        with timed(self.stats):
            self._note_host(x)
            x = self._shuttle(x, self.stage_index - 1, self.stage_index)
            g, loss = self._call(self.server.hop_loss, x, labels, step,
                                 mb, client_id, device=True)
            self._note_host(g)
            g = self._to_hub(g)  # S == 2: the loss wire IS stage 1's
            with obs_dispatch.expected_d2h(self._dd):
                loss_f = float(loss)
        self._hop_flight(False, "hop_loss", step, mb, client_id)
        return g, loss_f

    # -- the rest of the Transport surface ------------------------------ #
    def predict(self, activations: Any, client_id: int = 0) -> np.ndarray:
        # inference replies host numpy like every other transport: the
        # caller is the serving edge, not another stage
        with timed(self.stats):
            return self._call(self.server.predict,
                              np.asarray(activations), client_id)

    def split_step(self, activations, labels, step, client_id=0):
        raise NotImplementedError(
            "DeviceTransport serves pipeline hops only; the 2-party "
            "split path has no co-located fast path — use LocalTransport")

    def u_forward(self, activations, step, client_id=0):
        raise NotImplementedError(
            "DeviceTransport serves pipeline hops only")

    def u_backward(self, feat_grads, step, client_id=0):
        raise NotImplementedError(
            "DeviceTransport serves pipeline hops only")

    def aggregate(self, params, epoch, loss, step, num_examples=None):
        raise NotImplementedError(
            "DeviceTransport serves pipeline hops only")

    def health(self) -> Dict[str, Any]:
        return self.server.health()
