"""HTTP transport — the reference-shaped wire protocol, made safe.

Route layout mirrors the reference server exactly (for conceptual parity
and latency baselining): ``POST /forward_pass`` (``src/server_part.py:25``),
``POST /aggregate_weights`` (``src/server_part.py:60``), ``GET /health``
(``src/server_part.py:95``), plus ``/u_forward``/``/u_backward`` for the
U-shaped mode. Bodies are raw octet streams like the reference
(``src/server_part.py:58,93``) but encoded with the msgpack codec instead
of pickle (the reference's pickle wire format is insecure by design —
SURVEY.md §2 "must not be reproduced").

Status mapping: 400 = mode guard (reference behavior,
``src/server_part.py:31-36``), 409 = step-handshake violation (permanent),
500 = server fault (transient). The client raises ProtocolError for
400/409 and TransportError otherwise, preserving the permanent/transient
split the failure policies rely on.

Server runs the same ServerRuntime as every other transport — one step
logic, N wire formats (SURVEY.md §7 layering).
"""

from __future__ import annotations

import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, Optional, Tuple

import numpy as np
import requests

from split_learning_tpu.obs import flight as obs_flight
from split_learning_tpu.obs import spans
from split_learning_tpu.obs import trace as obs_trace
from split_learning_tpu.transport import codec
from split_learning_tpu.transport.base import (
    Backpressure, Transport, TransportError, backoff_delays, timed)
from split_learning_tpu.transport.chaos import _AttemptCounter, CHAOS_OPS

CRC_HEADER = "X-SLT-CRC32"
# ops that carry a per-step trace id when tracing is on (predict and
# aggregate are outside the step span taxonomy)
_TRACED_PATHS = ("/forward_pass", "/u_forward", "/u_backward")
# wire path -> ServerRuntime replay-cache op (runtime/replay.py)
_OP_BY_PATH = {"/forward_pass": "split_step", "/u_forward": "u_forward",
               "/u_backward": "u_backward", "/hop_forward": "hop_fwd",
               "/hop_backward": "hop_bwd", "/hop_loss": "hop_loss"}
# MPMD pipeline hops (PR 14): served by a StageRuntime behind the same
# handler. Every per-step keyed mechanism (chaos schedule, replay
# lookup, attach_reply_body) uses the composite hop_seq(step, mb)
# ordinal for these paths. Hop payloads compress like the 2-party cut
# (PR 18): each hop wire is its own EF endpoint — the client transport
# is bound to one stage and the stage's reply ledger keys (client,
# path), so residuals never mix across the chain's wires.
_HOP_PATHS = ("/hop_forward", "/hop_backward", "/hop_loss")


class SplitHTTPServer:
    """Serves a ServerRuntime over HTTP (stdlib; no FastAPI dependency)."""

    def __init__(self, runtime: Any, host: str = "127.0.0.1",
                 port: int = 0, compress: str = "none",
                 density: float = 0.1, chaos: Optional[Any] = None,
                 telemetry: Optional[Any] = None) -> None:
        """compress/density: server-side *defaults* for reply packing —
        a request carrying its own ``compress``/``density`` keys always
        wins (the client picks the wire format; these let ``serve
        --compress ...`` force one for clients that don't).

        chaos: optional ChaosPolicy (transport/chaos.py) injecting
        server-side faults on the seeded schedule: http500 / drop_req
        before the runtime applies anything, drop_resp (reply discarded
        after apply — the lost-response case) / corrupt (bad reply CRC)
        after, delay before. None = the untouched wire.

        telemetry: optional obs/telemetry.py TelemetryRing backing
        ``GET /telemetry`` for THIS server (multi-server processes give
        each server its own ring); None falls back to the process-global
        ring, and 404 when both are off — the off-path serves exactly
        the legacy routes."""
        if compress not in ("none", "int8", "topk8", "clapping"):
            raise ValueError(f"unknown compression {compress!r}")
        self.runtime = runtime
        self.chaos = chaos
        self.telemetry = telemetry
        self._chaos_attempts = _AttemptCounter()
        self.default_compress = compress
        self.default_density = float(density)
        # reply-direction error feedback: prefer the runtime's buffer
        # (survives transport restarts, reset by resume_from); this local
        # one is the fallback for bare runtimes in tests
        self._wire_ef = codec.make_wire_ef(
            "clapping" if compress == "clapping" else "topk8")
        outer = self

        class Handler(BaseHTTPRequestHandler):
            # quiet: the reference leans on uvicorn access logs; we expose
            # stats through TransportStats instead
            def log_message(self, *args):
                pass

            def _reply(self, status: int, body: bytes,
                       ctype: str = "application/octet-stream",
                       crc: Optional[int] = None,
                       headers: Optional[Dict[str, str]] = None) -> None:
                self.send_response(status)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                # extra response headers (the 429 path's Retry-After —
                # a header, not a body field, so the payload-key contract
                # between client and server codecs stays unchanged)
                for k, v in (headers or {}).items():
                    self.send_header(k, v)
                # frame integrity the reference's raw pickle bodies lack
                # (crc override: the chaos 'corrupt' fault ships a frame
                # the client's checksum gate must refuse)
                self.send_header(CRC_HEADER,
                                 str(crc if crc is not None
                                     else codec.checksum(body)))
                self.end_headers()
                self.wfile.write(body)

            def _send_200(self, body: bytes, fault) -> None:
                """Final send, honoring a post-apply chaos fault: the
                runtime already absorbed the update — only the reply is
                sabotaged (dropped mid-flight or CRC-corrupted)."""
                if fault is not None and fault[0] == "drop_resp":
                    # no status line at all: the client sees the
                    # connection die and maps it to TransportError
                    self.close_connection = True
                    return
                if fault is not None and fault[0] == "corrupt":
                    self._reply(200, body,
                                crc=codec.checksum(body) ^ 0x5A5A5A5A)
                    return
                self._reply(200, body)

            def do_GET(self):
                if self.path == "/health":
                    self._reply(200, codec.encode(outer.runtime.health()))
                elif self.path == "/metrics":
                    # Prometheus text exposition, served alongside
                    # /health (scrape-time snapshot — never touches the
                    # step hot path)
                    from split_learning_tpu.obs.metrics import (
                        render_prometheus)
                    from split_learning_tpu.version import __version__
                    snap = (outer.runtime.metrics()
                            if hasattr(outer.runtime, "metrics") else {})
                    text = render_prometheus(snap)
                    # build-info gauge with a version label — the one
                    # labeled series we export, so it is rendered here
                    # (render_prometheus's snapshot names are label-free)
                    text += (f'slt_build_info{{version="{__version__}"}}'
                             f" 1\n")
                    self._reply(
                        200, text.encode("utf-8"),
                        ctype="text/plain; version=0.0.4; charset=utf-8")
                elif self.path == "/debug/flight":
                    # flight-recorder dump trigger #3 (obs/flight.py):
                    # the in-memory ring as JSON. 404 with the recorder
                    # off — the off-path serves exactly the legacy
                    # routes. Authenticated-free by design, like /health
                    # and /metrics: the journal carries event metadata
                    # (steps, ids, names), never tensor payloads.
                    fl = obs_flight.get_recorder()
                    if fl is None:
                        self._reply(404, codec.encode(
                            {"error": "flight recorder off "
                                      "(SLT_FLIGHT/--flight)"}))
                    else:
                        body = json.dumps(
                            fl.dump(reason="http")).encode("utf-8")
                        self._reply(200, body, ctype="application/json")
                elif self.path == "/telemetry":
                    # windowed time-series (obs/telemetry.py): advance
                    # the ring (at most one snapshot per elapsed window;
                    # the snapshot is the runtime's own scrape path) and
                    # serialize the dump HERE, outside any runtime lock
                    # (SLT001 — the acceptance gate on this route). 404
                    # when telemetry is off, the /debug/flight precedent.
                    from split_learning_tpu.obs import (
                        telemetry as obs_telemetry)
                    ring = outer.telemetry or obs_telemetry.get_ring()
                    if ring is None:
                        self._reply(404, codec.encode(
                            {"error": "telemetry off "
                                      "(SLT_TELEMETRY/--telemetry)"}))
                    else:
                        ring.advance()
                        body = json.dumps(ring.dump()).encode("utf-8")
                        self._reply(200, body, ctype="application/json")
                else:
                    self._reply(404, codec.encode({"error": "not found"}))

            def do_POST(self):
                from split_learning_tpu.runtime.server import ProtocolError
                length = int(self.headers.get("Content-Length", 0))
                raw = self.rfile.read(length)
                sent_crc = self.headers.get(CRC_HEADER)
                if sent_crc is not None:
                    try:
                        crc_ok = int(sent_crc) == codec.checksum(raw)
                    except ValueError:  # malformed header is a bad frame too
                        crc_ok = False
                    if not crc_ok:
                        self._reply(400, codec.encode(
                            {"error": "frame checksum mismatch"}))
                        return
                tid = None
                try:
                    tree = codec.decode(raw)
                    req = codec.decompress_tree(tree)
                    cid = int(req.get("client_id", 0))
                    # the key every per-(client, step) mechanism below
                    # uses: the bare step, except hops where it is the
                    # composite (step, microbatch) ordinal — one replay
                    # entry and one chaos schedule PER HOP
                    key_seq = None
                    if "step" in req:
                        key_seq = int(req["step"])
                        if self.path in _HOP_PATHS:
                            from split_learning_tpu.runtime.stage import (
                                hop_seq)
                            key_seq = hop_seq(key_seq,
                                              int(req.get("mb", 0)))
                    # server-side chaos: one seeded draw per delivery
                    # attempt of a step op. Pre-apply kinds act here;
                    # drop_resp/corrupt ride to _send_200 so they fire
                    # AFTER the runtime has applied the update.
                    fault = None
                    if (outer.chaos is not None and self.path in CHAOS_OPS
                            and key_seq is not None):
                        attempt = outer._chaos_attempts.next(
                            (cid, self.path, key_seq))
                        fault = outer.chaos.draw(self.path,
                                                 key_seq, attempt)
                    fl = obs_flight.get_recorder()
                    if fl is not None:
                        # CTX adoption happens below; pass the client's
                        # trace id explicitly so even pre-adoption
                        # events correlate across the wire
                        _tid = req.get("trace_id")
                        fl.record(spans.FL_RECV, step=int(
                                      req.get("step", -1)),
                                  client_id=cid, party="server",
                                  trace_id=(str(_tid) if _tid is not None
                                            else None),
                                  path=self.path)
                    if fault is not None:
                        outer.chaos.count(fault[0])
                        kind, arg = fault
                        if fl is not None:
                            fl.record(spans.FL_CHAOS, step=int(
                                          req.get("step", -1)),
                                      client_id=cid, party="server",
                                      kind=kind, path=self.path)
                        if kind == "delay":
                            time.sleep(arg / 1e3)
                            fault = None
                        elif kind == "http500":
                            self._reply(500, codec.encode(
                                {"error": "chaos: injected http500"}))
                            return
                        elif kind == "drop_req":
                            # request "lost" before the server saw it
                            self.close_connection = True
                            return
                        elif kind == "dup":
                            # duplication is a client/network act; the
                            # server can't re-deliver its own reply
                            fault = None
                    tid = req.get("trace_id")
                    if tid is not None:
                        # adopt the client's trace id on this handler
                        # thread so the runtime's server spans join the
                        # same per-step trace; echoed back below
                        obs_trace.CTX.trace_id = str(tid)
                        obs_trace.CTX.server_spans = None
                    in_raw, in_wire = codec.compressed_leaf_bytes(tree)
                    # reply with the wire compression the client asked for
                    # (request keys win over the server's serve-time
                    # defaults)
                    mode = req.get("compress") or outer.default_compress
                    density = float(req.get("density",
                                            outer.default_density))
                    if mode in ("topk8", "clapping"):
                        # per-(client, op) error feedback on the reply
                        # direction — handler threads serving a coalesced
                        # group pack concurrently, so buffers must never
                        # be shared across clients (TopK8EF locks)
                        ef = getattr(outer.runtime, "wire_ef",
                                     None) or outer._wire_ef
                        key = (cid, self.path)
                        if self.path == "/predict":
                            # inference is stateless: no next step ever
                            # repays a residual, so feed nothing back
                            pack = (lambda a: codec.topk8_compress(
                                np.asarray(a), density)[0])
                        else:
                            decay = codec.ef_decay_for(self.path)
                            pack = (lambda a: ef.compress(
                                key, np.asarray(a), density, decay=decay))
                    elif mode == "int8":
                        pack = codec.q8_compress
                    else:
                        pack = (lambda a: a)
                    # exactly-once: a redelivered step is served the
                    # reply its original apply produced, never
                    # re-dispatched into the runtime
                    op = _OP_BY_PATH.get(self.path)
                    if (op is not None and key_seq is not None
                            and hasattr(outer.runtime, "replay_lookup")):
                        cached_body, cached = outer.runtime.replay_lookup(
                            cid, op, key_seq)
                        if cached_body is not None:
                            # the original frame, byte-for-byte: same
                            # payload, same CRC, EF ledger untouched
                            self._send_200(cached_body, fault)
                            return
                        if cached is not None:
                            # result cached by an in-process first
                            # delivery (no wire bytes to replay):
                            # rebuild the reply, packing topk8
                            # statelessly — running the EF compressor
                            # again for a step it already packed would
                            # corrupt the residual ledger
                            if mode in ("topk8", "clapping"):
                                pack = (lambda a: codec.topk8_compress(
                                    np.asarray(a), density)[0])
                            if op == "split_step":
                                resp = {"grads": pack(cached[0]),
                                        "loss": cached[1],
                                        "step": req["step"]}
                            elif op == "u_forward":
                                resp = {"features": pack(cached)}
                            elif op == "hop_fwd":
                                resp = {"y": pack(cached),
                                        "step": req["step"],
                                        "mb": req.get("mb", 0)}
                            elif op == "hop_loss":
                                resp = {"grads": pack(cached[0]),
                                        "loss": cached[1],
                                        "step": req["step"],
                                        "mb": req.get("mb", 0)}
                            elif op == "hop_bwd":
                                resp = {"grads": pack(cached),
                                        "step": req["step"],
                                        "mb": req.get("mb", 0)}
                            else:
                                resp = {"grads": pack(cached)}
                            body = codec.encode(resp)
                            outer.runtime.attach_reply_body(
                                cid, op, key_seq, body)
                            self._send_200(body, fault)
                            return
                    if self.path == "/forward_pass":
                        grads, loss = outer.runtime.split_step(
                            req["activations"], req["labels"],
                            int(req["step"]), cid)
                        resp = {"grads": pack(grads), "loss": loss,
                                "step": req["step"]}
                    elif self.path == "/u_forward":
                        feats = outer.runtime.u_forward(
                            req["activations"], int(req["step"]), cid)
                        resp = {"features": pack(feats)}
                    elif self.path == "/u_backward":
                        g = outer.runtime.u_backward(
                            req["feat_grads"], int(req["step"]), cid)
                        resp = {"grads": pack(g)}
                    elif self.path == "/hop_forward":
                        y = outer.runtime.hop_forward(
                            req["x"], int(req["step"]),
                            int(req.get("mb", 0)), cid)
                        resp = {"y": pack(y), "step": req["step"],
                                "mb": req.get("mb", 0)}
                    elif self.path == "/hop_backward":
                        g = outer.runtime.hop_backward(
                            req["g"], int(req["step"]),
                            int(req.get("mb", 0)), cid)
                        resp = {"grads": pack(g), "step": req["step"],
                                "mb": req.get("mb", 0)}
                    elif self.path == "/hop_loss":
                        g, loss = outer.runtime.hop_loss(
                            req["x"], req["labels"], int(req["step"]),
                            int(req.get("mb", 0)), cid)
                        resp = {"grads": pack(g), "loss": loss,
                                "step": req["step"],
                                "mb": req.get("mb", 0)}
                    elif self.path == "/predict":
                        out = outer.runtime.predict(req["activations"], cid)
                        resp = {"outputs": pack(out)}
                    elif self.path == "/aggregate_weights":
                        n_ex = req.get("num_examples")
                        agg = outer.runtime.aggregate(
                            req["model_state"], int(req["epoch"]),
                            float(req["loss"]), int(req["step"]),
                            int(n_ex) if n_ex is not None else None)
                        resp = {"model_state": agg}
                    else:
                        self._reply(404, codec.encode({"error": "not found"}))
                        return
                    if tid is not None and obs_trace.CTX.server_spans:
                        # server-side timings ride back in the payload so
                        # the client can split wire time out of the
                        # round trip (wire = round_trip - server total)
                        resp["server_spans"] = obs_trace.CTX.server_spans
                    out_raw, out_wire = codec.compressed_leaf_bytes(resp)
                    if (in_wire or out_wire) and hasattr(
                            outer.runtime, "note_wire_compression"):
                        outer.runtime.note_wire_compression(
                            in_raw + out_raw, in_wire + out_wire)
                    body = codec.encode(resp)
                    if (op is not None and key_seq is not None and hasattr(
                            outer.runtime, "attach_reply_body")):
                        # pin the exact frame to the replay entry BEFORE
                        # sending: even a reply lost in flight leaves
                        # the retry a bit-identical copy to collect
                        outer.runtime.attach_reply_body(
                            cid, op, key_seq, body)
                    self._send_200(body, fault)
                except Backpressure as exc:
                    # admission refused the step: the canonical wire form
                    # of the typed in-process signal — 429 plus the
                    # advised delay in the standard Retry-After header
                    self._reply(
                        429, codec.encode({"error": str(exc)}),
                        headers={"Retry-After": f"{exc.retry_after_s:.3f}"})
                except ProtocolError as exc:
                    self._reply(exc.status, codec.encode({"error": str(exc)}))
                except Exception as exc:  # noqa: BLE001 — server must not die
                    self._reply(500, codec.encode({"error": str(exc)}))
                finally:
                    if tid is not None:
                        # handler threads serve many requests over one
                        # keep-alive connection: never leak a trace id
                        obs_trace.CTX.trace_id = None
                        obs_trace.CTX.server_spans = None

        self._httpd = ThreadingHTTPServer((host, port), Handler)
        self.host, self.port = self._httpd.server_address
        self._thread: Optional[threading.Thread] = None

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    def start(self) -> "SplitHTTPServer":
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()
        if self._thread is not None:
            self._thread.join(timeout=5)


class HttpTransport(Transport):
    """Client side: blocking POSTs like the reference client
    (``src/client_part.py:125,186``), with permanent/transient error
    classification instead of silent batch drops."""

    def __init__(self, base_url: str, timeout: float = 60.0,
                 compress: str = "none", density: float = 0.1,
                 pool_maxsize: int = 32,
                 density_controller: Optional[Any] = None,
                 wire_id: Optional[str] = None) -> None:
        """``compress="int8"`` quantizes the cut-layer tensors on the wire
        (4x fewer bytes; lossy — see transport/codec.py). ``"topk8"`` ships
        only the top ``density`` fraction of magnitudes as int8 with
        sender-side error feedback (~17x at density 0.1 — see
        transport/codec.py); ``"clapping"`` is the same selection with
        the storage-free EF ledger (codec.ClappingEF — nothing
        checkpointed, nothing migrated). Weights (/aggregate_weights)
        always travel lossless. Pipeline hop payloads compress too —
        one transport serves one stage, so its EF ledger is that hop
        wire's (client, stage, op) endpoint.

        density_controller / wire_id: optional
        transport.density.DensityController; when bound, every packed
        payload reads its density from the controller under this wire's
        id and feeds the achieved byte ratio back.

        ``pool_maxsize`` sizes the urllib3 connection pool mounted on
        the session. requests' default is 10; a pipelined client sharing
        one transport across W > 10 lanes would silently serialize the
        overflow on pool checkout (urllib3 blocks or discards), so
        callers with deep windows must pass ``pool_maxsize >= depth``
        (launch/run.py does)."""
        super().__init__()
        if compress not in ("none", "int8", "topk8", "clapping"):
            raise ValueError(f"unknown compression {compress!r}")
        if pool_maxsize < 1:
            raise ValueError(f"pool_maxsize must be >= 1 (got {pool_maxsize})")
        self.base_url = base_url.rstrip("/")
        self.timeout = timeout
        self.compress = compress
        self.density = float(density)
        self.pool_maxsize = int(pool_maxsize)
        self._dc = density_controller
        self.wire_id = wire_id if wire_id is not None else base_url
        # up-direction error feedback, keyed per op (one transport = one
        # client, so the op name is the whole key)
        self._ef = codec.make_wire_ef(
            "clapping" if compress == "clapping" else "topk8")
        self._session = requests.Session()
        adapter = requests.adapters.HTTPAdapter(
            pool_connections=self.pool_maxsize,
            pool_maxsize=self.pool_maxsize)
        self._session.mount("http://", adapter)
        self._session.mount("https://", adapter)

    def _topk8(self) -> bool:
        return self.compress in ("topk8", "clapping")

    def _density_now(self) -> float:
        if self._dc is not None:
            return self._dc.density(self.wire_id)
        return self.density

    def _pack(self, arr: np.ndarray, key: str = "x") -> Any:
        if self.compress == "int8":
            return codec.q8_compress(np.asarray(arr))
        if self._topk8():
            if key == "predict":
                # stateless: no later step repays an inference residual
                return codec.topk8_compress(np.asarray(arr),
                                            self._density_now())[0]
            return self._ef.compress(key, np.asarray(arr),
                                     self._density_now(),
                                     decay=codec.ef_decay_for(key))
        return np.asarray(arr)

    def _rollback(self, key: str) -> None:
        """A failed POST means this client never got its reply: undo the
        error-feedback update so the shipped mass isn't marked delivered
        (the retry/skip policies re-pack from scratch).

        Consistent with replayed delivery by determinism: TopK8EF
        rollback restores the exact pre-compress residual, so re-packing
        the SAME tensor reproduces the original payload and the original
        post-compress residual bit-for-bit. Whether the server applied
        the first delivery (lost response -> retry served from its
        replay cache) or never saw it (lost request -> retry dispatched
        fresh), the client's EF ledger ends in the same state it would
        have reached on a clean wire."""
        if self._topk8():
            self._ef.rollback(key)

    def _post(self, path: str, payload: Dict[str, Any]) -> Dict[str, Any]:
        from split_learning_tpu.runtime.server import ProtocolError
        # tracing (obs/trace.py): ``wire`` spans the whole exchange and
        # accounts for what is left of it once the codec (its two
        # ``encode`` children) and the server's own seconds (echoed back
        # as server_spans) are taken out. With nothing recording this is
        # bit-for-bit the untraced wire: no trace_id key, no clock read.
        step = int(payload.get("step", -1))
        cid = int(payload.get("client_id", 0))
        with obs_trace.span(spans.WIRE, tid=cid, step=step,
                            trace=(cid, step)) as wire:
            return self._exchange(path, payload, wire, step, cid)

    def _exchange(self, path: str, payload: Dict[str, Any],
                  wire: obs_trace.Span, step: int,
                  cid: int) -> Dict[str, Any]:
        from split_learning_tpu.runtime.server import ProtocolError
        tid = wire.trace_id if wire.recording else None
        if tid is not None:
            payload = dict(payload, trace_id=tid)
        if self.compress != "none":
            payload = dict(payload, compress=self.compress)
            if self._topk8():
                payload["density"] = self._density_now()
            raw_b, wire_b = codec.compressed_leaf_bytes(payload)
            if wire_b:
                self.stats.record_compression(raw_b, wire_b)
                if self._dc is not None:
                    self._dc.note_ratio(self.wire_id, raw_b, wire_b)
        fl = obs_flight.get_recorder()
        if fl is not None and path in _TRACED_PATHS:
            fl.record(spans.FL_SEND, step=step, client_id=cid,
                      party="client", trace_id=tid, path=path)
        with obs_trace.span(spans.ENCODE) as up:
            body = codec.encode(payload)
        try:
            resp = self._session.post(
                f"{self.base_url}{path}", data=body, timeout=self.timeout,
                headers={"Content-Type": "application/octet-stream",
                         CRC_HEADER: str(codec.checksum(body))})
        except requests.RequestException as exc:
            raise TransportError(f"POST {path} failed: {exc}") from exc
        self.stats.add_bytes(sent=len(body), received=len(resp.content))
        resp_crc = resp.headers.get(CRC_HEADER)
        if resp_crc is not None:
            try:
                crc_ok = int(resp_crc) == codec.checksum(resp.content)
            except ValueError:
                crc_ok = False
            if not crc_ok:
                raise TransportError(
                    f"POST {path}: response checksum mismatch")
        if resp.status_code == 429:
            try:
                ra = float(resp.headers.get("Retry-After", "0") or 0)
            except ValueError:
                ra = 0.0
            raise Backpressure(
                f"POST {path} -> 429: "
                f"{codec.decode(resp.content).get('error', '')}",
                retry_after_s=ra)
        if resp.status_code in (400, 409):
            raise ProtocolError(codec.decode(resp.content).get("error", ""))
        if resp.status_code != 200:
            raise TransportError(
                f"POST {path} -> {resp.status_code}: {resp.content[:200]!r}")
        if fl is not None and path in _TRACED_PATHS:
            fl.record(spans.FL_RECV, step=step, client_id=cid,
                      party="client", trace_id=tid, path=path)
        try:
            with obs_trace.span(spans.ENCODE) as down:
                tree = codec.decode(resp.content)
                if self.compress != "none":
                    raw_b, wire_b = codec.compressed_leaf_bytes(tree)
                    if wire_b:
                        self.stats.record_compression(raw_b, wire_b)
                        if self._dc is not None:
                            self._dc.note_ratio(self.wire_id, raw_b, wire_b)
                out = codec.decompress_tree(tree)
        except codec.CodecError as exc:
            # a frame that passed the CRC gate but fails codec
            # validation (truncated bitmap, out-of-range indices) is a
            # BAD DELIVERY, not a protocol violation: surface it as the
            # transient TransportError so the retry/replay machinery
            # re-collects the original frame instead of a caller
            # stepping on a silently-wrong tensor (or the raw
            # ValueError killing the pipeline worker)
            raise TransportError(
                f"POST {path}: reply failed codec validation: "
                f"{exc}") from exc
        if tid is not None:
            srv = out.pop("server_spans", None) or {}
            enc_s = up.duration_s + down.duration_s  # codec, both ways
            others = enc_s + sum(srv.values())
            wire.subtract(others)
            self.stats.record_span(spans.ENCODE, enc_s)
            self.stats.record_span(spans.WIRE,
                                   max(wire.elapsed_s() - others, 0.0))
            # server-reported spans fold into this transport's stats so
            # merged() carries the full cross-party phase breakdown
            for name, secs in srv.items():
                self.stats.record_span(str(name), float(secs))
        return out

    def split_step(self, activations: np.ndarray, labels: np.ndarray,
                   step: int, client_id: int = 0) -> Tuple[np.ndarray, float]:
        with timed(self.stats):
            try:
                out = self._post("/forward_pass", {
                    "activations": self._pack(activations, "acts"),
                    "labels": np.asarray(labels),
                    "step": step, "client_id": client_id,
                })
            except Exception:
                self._rollback("acts")
                raise
            # the reply echoes the request step; a mismatch means the
            # frame was routed to the wrong in-flight exchange (replayed
            # frames carry the original — matching — step, so replay
            # stays transparent here)
            if int(out["step"]) != step:
                raise TransportError(
                    f"/forward_pass reply step {out['step']} does not "
                    f"echo request step {step}")
            return out["grads"], float(out["loss"])

    def u_forward(self, activations: np.ndarray, step: int,
                  client_id: int = 0) -> np.ndarray:
        with timed(self.stats):
            try:
                return self._post("/u_forward", {
                    "activations": self._pack(activations, "u_acts"),
                    "step": step, "client_id": client_id,
                })["features"]
            except Exception:
                self._rollback("u_acts")
                raise

    def u_backward(self, feat_grads: np.ndarray, step: int,
                   client_id: int = 0) -> np.ndarray:
        with timed(self.stats):
            try:
                return self._post("/u_backward", {
                    "feat_grads": self._pack(feat_grads, "u_grads"),
                    "step": step, "client_id": client_id,
                })["grads"]
            except Exception:
                self._rollback("u_grads")
                raise

    # -- MPMD pipeline hops (PR 14): peer serves a StageRuntime --------- #
    def _hop_flight(self, send: bool, op: str, step: int, mb: int,
                    client_id: int) -> None:
        fl = obs_flight.get_recorder()
        if fl is None:
            return
        kw = dict(step=int(step), client_id=int(client_id),
                  party="client", op=op, mb=int(mb), stage=-1)
        if send:
            fl.record(spans.FL_HOP_SEND, **kw)
        else:
            fl.record(spans.FL_HOP_RECV, **kw)

    def _check_hop_echo(self, path: str, out: Dict[str, Any], step: int,
                        mb: int) -> None:
        # hops multiplex M in-flight exchanges per step over one
        # session: the echoed (step, mb) is the only routing check
        if int(out.get("step", step)) != int(step) or int(
                out.get("mb", mb)) != int(mb):
            raise TransportError(
                f"{path} reply (step={out.get('step')}, "
                f"mb={out.get('mb')}) does not echo request "
                f"(step={step}, mb={mb})")

    # hop payloads are host-bound by construction here (the codec
    # frames numpy): 2 host materializations per hop — request encode +
    # reply decode — counted under spans.HOP_HOST_COPIES so the
    # co-located DeviceTransport's 0 has a measured contrast
    # (device_native stays the base class's False).

    def hop_forward(self, x: np.ndarray, step: int, mb: int = 0,
                    client_id: int = 0) -> np.ndarray:
        self._hop_flight(True, "hop_fwd", step, mb,
                         client_id)
        with timed(self.stats):
            self.stats.incr(spans.HOP_HOST_COPIES, 2)
            try:
                out = self._post("/hop_forward", {
                    "x": self._pack(x, "hop_x"), "step": step,
                    "mb": int(mb), "client_id": client_id})
            except Exception:
                # a hop POST that never got its reply must not leave
                # the shipped mass marked delivered — same EF rollback
                # contract as the 2-party step ops
                self._rollback("hop_x")
                raise
        self._check_hop_echo("/hop_forward", out, step, mb)
        self._hop_flight(False, "hop_fwd", step, mb,
                         client_id)
        return out["y"]

    def hop_backward(self, g_out: np.ndarray, step: int, mb: int = 0,
                     client_id: int = 0) -> np.ndarray:
        self._hop_flight(True, "hop_bwd", step, mb,
                         client_id)
        with timed(self.stats):
            self.stats.incr(spans.HOP_HOST_COPIES, 2)
            try:
                out = self._post("/hop_backward", {
                    "g": self._pack(g_out, "hop_g"), "step": step,
                    "mb": int(mb), "client_id": client_id})
            except Exception:
                self._rollback("hop_g")
                raise
        self._check_hop_echo("/hop_backward", out, step, mb)
        self._hop_flight(False, "hop_bwd", step, mb,
                         client_id)
        return out["grads"]

    def hop_loss(self, x: np.ndarray, labels: np.ndarray, step: int,
                 mb: int = 0,
                 client_id: int = 0) -> Tuple[np.ndarray, float]:
        self._hop_flight(True, "hop_loss", step, mb,
                         client_id)
        with timed(self.stats):
            self.stats.incr(spans.HOP_HOST_COPIES, 2)
            try:
                # labels travel lossless: integer classes quantize to
                # garbage, and their bytes are noise next to the cut
                out = self._post("/hop_loss", {
                    "x": self._pack(x, "hop_loss_x"),
                    "labels": np.asarray(labels),
                    "step": step, "mb": int(mb), "client_id": client_id})
            except Exception:
                self._rollback("hop_loss_x")
                raise
        self._check_hop_echo("/hop_loss", out, step, mb)
        self._hop_flight(False, "hop_loss", step, mb,
                         client_id)
        return out["grads"], float(out["loss"])

    def predict(self, activations: np.ndarray,
                client_id: int = 0) -> np.ndarray:
        with timed(self.stats):
            return self._post("/predict", {
                "activations": self._pack(activations, "predict"),
                "client_id": client_id,
            })["outputs"]

    def aggregate(self, params: Any, epoch: int, loss: float, step: int,
                  num_examples: int | None = None) -> Any:
        with timed(self.stats):
            payload = {"model_state": params, "epoch": epoch,
                       "loss": loss, "step": step}
            if num_examples is not None:
                payload["num_examples"] = int(num_examples)
            return self._post("/aggregate_weights", payload)["model_state"]

    def health(self) -> Dict[str, Any]:
        try:
            resp = self._session.get(f"{self.base_url}/health",
                                     timeout=self.timeout)
        except requests.RequestException as exc:
            raise TransportError(f"GET /health failed: {exc}") from exc
        if resp.status_code != 200:
            raise TransportError(
                f"GET /health -> {resp.status_code}: {resp.content[:200]!r}")
        return codec.decode(resp.content)

    def wait_ready(self, timeout: float = 60.0, interval: float = 0.5,
                   max_interval: float = 5.0, jitter: float = 0.5,
                   seed: Optional[int] = None) -> Dict[str, Any]:
        """Block until the server answers /health — the explicit readiness
        barrier the reference lacks (it silently drops every batch sent
        before the server is up, ``src/client_part.py:127-129``;
        SURVEY.md §3.4 "the client does not wait for the server").

        Polls on exponential backoff (``interval``, x2 per miss, capped
        at ``max_interval``) with up to ``jitter`` of multiplicative
        jitter, so N clients waiting out one restarting server desync
        their probes instead of thundering-herding the same instants.
        ``seed`` pins the jitter stream (tests)."""
        import time as _time
        deadline = _time.monotonic() + timeout
        rng = np.random.RandomState(seed) if seed is not None else None
        for delay in backoff_delays(interval, cap=max_interval,
                                    jitter=jitter, rng=rng):
            try:
                return self.health()
            except TransportError:
                now = _time.monotonic()
                if now >= deadline:
                    raise
                _time.sleep(min(delay, deadline - now))

    def close(self) -> None:
        self._session.close()
