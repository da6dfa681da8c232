"""In-process transport — the protocol-level test fake (SURVEY.md §4 item 2).

Exercises the exact split-step contract (activations down, same-shaped grad
back, step echo) with zero network, the equivalent of faking the reference's
``/forward_pass`` route. Optionally round-trips every payload through the
wire codec so serialization is covered even in-process.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import numpy as np

from split_learning_tpu.obs import flight as obs_flight
from split_learning_tpu.obs import spans
from split_learning_tpu.obs import trace as obs_trace
from split_learning_tpu.transport import codec
from split_learning_tpu.transport.base import (
    Backpressure, Transport, TransportError, timed)


class LocalTransport(Transport):
    """Exception contract (uniform across all ops): server-side
    ProtocolError propagates as-is — it is a *permanent* contract
    violation (mode mismatch, step replay) that retry/skip policies must
    not mask; anything else becomes TransportError (transient)."""

    def __init__(self, server: Any, through_codec: bool = False,
                 compress: Optional[str] = None,
                 density: float = 0.1,
                 ef_mode: str = "topk8",
                 density_controller: Optional[Any] = None,
                 wire_id: Optional[str] = None) -> None:
        """server: a ServerRuntime (duck-typed: split_step/u_forward/
        u_backward/aggregate/health) or a StageRuntime (hop ops).

        compress: None (default) is the legacy direct path — no wire
        emulation, bit-for-bit what this transport always did. Any of
        "none"/"int8"/"topk8"/"clapping" switches the step ops AND the
        pipeline hop ops to full wire emulation: each direction's
        payload goes through the real codec (encode -> byte count ->
        decode -> expand) with that compression applied, exactly like
        one HTTP hop — so compressed-path parity and convergence tests
        run in-process, no sockets. ``"none"`` emulates the dense fp32
        wire (the baseline the bench legs compare against);
        ``"clapping"`` is topk8 selection with the storage-free EF
        ledger (codec.ClappingEF). Weights (aggregate) always travel
        lossless.

        density_controller / wire_id: optional
        transport.density.DensityController; when bound, every packed
        payload reads its density from the controller under this
        wire's id and feeds the achieved byte ratio back."""
        super().__init__()
        if compress not in (None, "none", "int8", "topk8", "clapping"):
            raise ValueError(f"unknown compression {compress!r}")
        self.server = server
        self.through_codec = through_codec
        self.compress = compress
        self.density = float(density)
        mode = "clapping" if compress == "clapping" else "topk8"
        self._ef = codec.make_wire_ef(mode)       # up (client-owned)
        self._down_ef = codec.make_wire_ef(mode)  # down, bare servers
        self._dc = density_controller
        stage = getattr(server, "stage_index", None)
        self.wire_id = wire_id if wire_id is not None else (
            f"hop{stage}" if stage is not None else "cut")

    def _topk8(self) -> bool:
        return self.compress in ("topk8", "clapping")

    def _density_now(self) -> float:
        if self._dc is not None:
            return self._dc.density(self.wire_id)
        return self.density

    def _roundtrip(self, obj: Any) -> Any:
        return codec.decode(codec.encode(obj)) if self.through_codec else obj

    def _hop_payload(self, obj: Any) -> Any:
        """Hop payloads on the default path (``through_codec=False``,
        ``compress=None``) pass through UNTOUCHED — no ``np.asarray``,
        no codec round-trip (PR 16 satellite): the in-process peer takes
        the caller's buffer as-is and byte accounting is unchanged
        (hops never counted wire bytes). With ``through_codec`` the full
        encode/decode path still runs per hop, host-materializing first
        exactly as before."""
        if not self.through_codec and self.compress is None:
            return obj
        return self._roundtrip(np.asarray(obj))

    # -- wire emulation (compress != None) ------------------------------
    def _pack_up(self, arr: np.ndarray, key: Any) -> Any:
        if self.compress == "int8":
            return codec.q8_compress(np.asarray(arr))
        if self._topk8():
            return self._ef.compress(key, np.asarray(arr),
                                     self._density_now(),
                                     decay=codec.ef_decay_for(key[0]))
        return np.asarray(arr)

    def _pack_down(self, arr: np.ndarray, key: Any) -> Any:
        if self.compress == "int8":
            return codec.q8_compress(np.asarray(arr))
        if self._topk8():
            # same buffer the HTTP server uses, same (client, op) keying
            ef = getattr(self.server, "wire_ef", None) or self._down_ef
            return ef.compress(key, np.asarray(arr), self._density_now(),
                               decay=codec.ef_decay_for(key[1]))
        return np.asarray(arr)

    def _wire(self, payload: dict) -> Tuple[dict, int]:
        """One direction of the emulated wire: real encode, real byte
        count, real decode + expansion — what HTTP does minus the socket."""
        body = codec.encode(payload)
        raw_b, wire_b = codec.compressed_leaf_bytes(payload)
        if wire_b:
            self.stats.record_compression(raw_b, wire_b)
            if self._dc is not None:
                self._dc.note_ratio(self.wire_id, raw_b, wire_b)
            # mirror the HTTP server: the peer runtime folds the same
            # bytes into its own /metrics (stage-labeled for hops)
            nwc = getattr(self.server, "note_wire_compression", None)
            if nwc is not None:
                nwc(raw_b, wire_b)
        return codec.decompress_tree(codec.decode(body)), len(body)

    def _call(self, fn, *args):
        from split_learning_tpu.runtime.server import ProtocolError
        try:
            return fn(*args)
        except ProtocolError:
            raise
        except Backpressure:
            # in-process equivalent of the HTTP 429 + Retry-After path:
            # the typed signal (with its advised delay) reaches the
            # caller intact instead of flattening into TransportError
            raise
        except Exception as exc:
            raise TransportError(str(exc)) from exc

    def split_step(self, activations: np.ndarray, labels: np.ndarray,
                   step: int, client_id: int = 0) -> Tuple[np.ndarray, float]:
        # flight journal (obs/flight.py): one send/recv pair per
        # delivery attempt, client party — gated exactly like the
        # tracer, so the recorder-off path touches nothing
        fl = obs_flight.get_recorder()
        if fl is not None:
            fl.record(spans.FL_SEND, step=int(step),
                      client_id=int(client_id), party="client",
                      op="split_step")
        if self.compress is not None:
            res = self._split_step_wire(activations, labels, step,
                                        client_id)
        else:
            res = self._split_step_plain(activations, labels, step,
                                         client_id)
        if fl is not None:
            fl.record(spans.FL_RECV, step=int(step),
                      client_id=int(client_id), party="client",
                      op="split_step")
        return res

    def _split_step_wire(self, activations, labels, step, client_id):
        """Emulated-wire variant: both directions go through the real
        codec with the configured compression. No rollback on failure —
        an in-process call that raised still *delivered* the payload
        (the server decoded it before failing), unlike a lost POST."""
        with timed(self.stats):
            req, up = self._wire({
                "activations": self._pack_up(np.asarray(activations),
                                             ("acts", client_id)),
                "labels": np.asarray(labels)})
            grads, loss = self._call(self.server.split_step,
                                     req["activations"], req["labels"],
                                     step, client_id)
            resp, down = self._wire({
                "grads": self._pack_down(grads,
                                         (client_id, "/forward_pass")),
                "loss": float(loss)})
            self.stats.add_bytes(sent=up, received=down)
            return resp["grads"], float(resp["loss"])

    def _split_step_plain(self, activations, labels, step, client_id):
        """The uncompressed in-process exchange, one path traced or not:
        the server runs on this thread, reads ``CTX.trace_id`` directly
        and (only while recording) writes ``CTX.server_spans`` back;
        ``wire`` is then pure call overhead (server time subtracted),
        the in-process floor the HTTP wire numbers compare against."""
        with timed(self.stats):
            with obs_trace.span(spans.ENCODE, tid=client_id,
                                step=step) as up:
                acts = self._roundtrip(np.asarray(activations))
                labs = self._roundtrip(np.asarray(labels))
            with obs_trace.span(spans.WIRE, tid=client_id, step=step,
                                trace=(client_id, step)) as wire:
                obs_trace.CTX.server_spans = None
                grads, loss = self._call(self.server.split_step, acts,
                                         labs, step, client_id)
                srv = obs_trace.CTX.server_spans or {}
                obs_trace.CTX.server_spans = None
                wire.subtract(sum(srv.values()))
            with obs_trace.span(spans.ENCODE, tid=client_id,
                                step=step) as down:
                out = self._roundtrip(grads), float(loss)
            if wire.recording:
                self.stats.record_span(
                    spans.ENCODE, up.duration_s + down.duration_s)
                self.stats.record_span(spans.WIRE, wire.duration_s)
                for name, secs in srv.items():
                    self.stats.record_span(str(name), float(secs))
            return out

    def u_forward(self, activations: np.ndarray, step: int,
                  client_id: int = 0) -> np.ndarray:
        with timed(self.stats):
            if self.compress is not None:
                req, up = self._wire({"activations": self._pack_up(
                    np.asarray(activations), ("u_acts", client_id))})
                feats = self._call(self.server.u_forward,
                                   req["activations"], step, client_id)
                resp, down = self._wire({"features": self._pack_down(
                    feats, (client_id, "/u_forward"))})
                self.stats.add_bytes(sent=up, received=down)
                return resp["features"]
            feats = self._call(
                self.server.u_forward,
                self._roundtrip(np.asarray(activations)), step, client_id)
            return self._roundtrip(feats)

    def predict(self, activations: np.ndarray,
                client_id: int = 0) -> np.ndarray:
        with timed(self.stats):
            if self.compress is not None:
                # inference is stateless on both ends: no error feedback
                a = np.asarray(activations)
                if self._topk8():
                    packed = codec.topk8_compress(a,
                                                  self._density_now())[0]
                elif self.compress == "int8":
                    packed = codec.q8_compress(a)
                else:
                    packed = a
                req, up = self._wire({"activations": packed})
                out = self._call(self.server.predict, req["activations"],
                                 client_id)
                if self._topk8():
                    packed_out = codec.topk8_compress(
                        np.asarray(out), self._density_now())[0]
                elif self.compress == "int8":
                    packed_out = codec.q8_compress(np.asarray(out))
                else:
                    packed_out = np.asarray(out)
                resp, down = self._wire({"outputs": packed_out})
                self.stats.add_bytes(sent=up, received=down)
                return resp["outputs"]
            out = self._call(self.server.predict,
                             self._roundtrip(np.asarray(activations)),
                             client_id)
            return self._roundtrip(out)

    def u_backward(self, feat_grads: np.ndarray, step: int,
                   client_id: int = 0) -> np.ndarray:
        with timed(self.stats):
            if self.compress is not None:
                req, up = self._wire({"feat_grads": self._pack_up(
                    np.asarray(feat_grads), ("u_grads", client_id))})
                g = self._call(self.server.u_backward, req["feat_grads"],
                               step, client_id)
                resp, down = self._wire({"grads": self._pack_down(
                    g, (client_id, "/u_backward"))})
                self.stats.add_bytes(sent=up, received=down)
                return resp["grads"]
            g = self._call(
                self.server.u_backward,
                self._roundtrip(np.asarray(feat_grads)), step, client_id)
            return self._roundtrip(g)

    # -- MPMD pipeline hops (PR 14): peer is a StageRuntime ------------- #
    def _hop_flight(self, send: bool, op: str, step: int, mb: int,
                    client_id: int) -> None:
        fl = obs_flight.get_recorder()
        if fl is None:
            return
        kw = dict(step=int(step), client_id=int(client_id),
                  party="client", op=op, mb=int(mb),
                  stage=getattr(self.server, "stage_index", -1))
        if send:
            fl.record(spans.FL_HOP_SEND, **kw)
        else:
            fl.record(spans.FL_HOP_RECV, **kw)

    def hop_forward(self, x: np.ndarray, step: int, mb: int = 0,
                    client_id: int = 0) -> np.ndarray:
        self._hop_flight(True, "hop_fwd", step, mb,
                         client_id)
        with timed(self.stats):
            if self.compress is not None:
                # the compressed hop wire (emulated, like the step ops):
                # EF keys by role + client, and this transport is bound
                # to ONE stage, so the ledger keying is effectively
                # (client, stage, op) — the HTTP chain's contract
                req, up = self._wire({"x": self._pack_up(
                    np.asarray(x), ("hop_x", client_id))})
                y = self._call(self.server.hop_forward, req["x"],
                               step, mb, client_id)
                resp, down = self._wire({"y": self._pack_down(
                    y, (client_id, "/hop_forward"))})
                self.stats.add_bytes(sent=up, received=down)
                res = resp["y"]
            else:
                y = self._call(self.server.hop_forward,
                               self._hop_payload(x), step, mb,
                               client_id)
                res = self._roundtrip(y)
        self._hop_flight(False, "hop_fwd", step, mb,
                         client_id)
        return res

    def hop_backward(self, g_out: np.ndarray, step: int, mb: int = 0,
                     client_id: int = 0) -> np.ndarray:
        self._hop_flight(True, "hop_bwd", step, mb,
                         client_id)
        with timed(self.stats):
            if self.compress is not None:
                req, up = self._wire({"g": self._pack_up(
                    np.asarray(g_out), ("hop_g", client_id))})
                g = self._call(self.server.hop_backward, req["g"],
                               step, mb, client_id)
                resp, down = self._wire({"g": self._pack_down(
                    g, (client_id, "/hop_backward"))})
                self.stats.add_bytes(sent=up, received=down)
                res = resp["g"]
            else:
                g = self._call(self.server.hop_backward,
                               self._hop_payload(g_out), step, mb,
                               client_id)
                res = self._roundtrip(g)
        self._hop_flight(False, "hop_bwd", step, mb,
                         client_id)
        return res

    def hop_loss(self, x: np.ndarray, labels: np.ndarray, step: int,
                 mb: int = 0,
                 client_id: int = 0) -> Tuple[np.ndarray, float]:
        self._hop_flight(True, "hop_loss", step, mb,
                         client_id)
        with timed(self.stats):
            if self.compress is not None:
                # labels travel lossless (integer classes quantize to
                # garbage); the loss scalar is dense by construction
                req, up = self._wire({
                    "x": self._pack_up(np.asarray(x),
                                       ("hop_loss_x", client_id)),
                    "labels": np.asarray(labels)})
                g, loss = self._call(self.server.hop_loss, req["x"],
                                     req["labels"], step, mb, client_id)
                resp, down = self._wire({
                    "g": self._pack_down(g, (client_id, "/hop_loss")),
                    "loss": float(loss)})
                self.stats.add_bytes(sent=up, received=down)
                res = resp["g"], float(resp["loss"])
            else:
                g, loss = self._call(self.server.hop_loss,
                                     self._hop_payload(x),
                                     self._hop_payload(labels),
                                     step, mb, client_id)
                res = self._roundtrip(g), float(loss)
        self._hop_flight(False, "hop_loss", step, mb,
                         client_id)
        return res

    def aggregate(self, params: Any, epoch: int, loss: float, step: int,
                  num_examples: int | None = None) -> Any:
        with timed(self.stats):
            return self._roundtrip(self._call(
                self.server.aggregate,
                self._roundtrip(params), epoch, loss, step, num_examples))

    def health(self) -> Dict[str, Any]:
        return self.server.health()
