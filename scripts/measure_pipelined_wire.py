#!/usr/bin/env python
"""Real-wire pipelined-client overlap measurement (VERDICT r4 #4).

Rounds 3 and 4 could only show the depth-W window's value indirectly:
HTTP loopback on shared cores measured a 0.92x *slowdown* (convoying,
honestly annotated) and the 1.63x win came from ``time.sleep`` inside
the client process — simulation, not concurrency. This script measures
the overlap with real concurrency and latency injected at the SOCKET
layer, outside both parties:

- the split server (``launch.run serve``) runs as its own OS process;
- a delay proxy runs as a THIRD OS process relaying real TCP bytes and
  delivering every chunk at ``arrival + D`` per direction — a
  propagation-delay model, so in-flight chunks overlap on the wire
  exactly as they would on a real link (NOT sleep-per-request: the
  asyncio clock stamps each chunk independently);
- the client process measures lock-step (depth 1, strict server) vs
  depth-W (``--allow-out-of-order`` server) steps/sec over the same
  batches, plus the wire's delivered one-way latency from TCP round
  trips of the server's own health route.

The preferred kernel path (netns + veth + netem) is unavailable on this
image — ``sch_netem`` is not compiled/loaded and there is no modprobe —
which the artifact's provenance records.

Writes ``artifacts/pipelined_wire.json`` and prints it as a JSON line.
Reference workload being overlapped: the per-step pickle/HTTP round
trip of ``/root/reference/src/client_part.py:110-133``.

Usage: python scripts/measure_pipelined_wire.py [--delay-ms D]
       [--steps N] [--depth W]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

SERVER_PORT = 18878
PROXY_PORT = 18877

CPU_ENV = dict(os.environ, JAX_PLATFORMS="cpu")


# --------------------------------------------------------------------- #
# Delay-proxy process: `measure_pipelined_wire.py --proxy L T D` relays
# 127.0.0.1:L -> 127.0.0.1:T adding D ms of propagation delay per
# direction. Runs under asyncio so one process carries every concurrent
# lane; per-chunk due-times (not sleep-per-chunk) keep simultaneous
# in-flight chunks overlapped, like signals on a real link.

def proxy_main(listen_port: int, target_port: int, delay_ms: float) -> None:
    import asyncio

    delay = delay_ms / 1e3

    async def pump(reader, writer):
        loop = asyncio.get_running_loop()
        queue: asyncio.Queue = asyncio.Queue()

        async def rx():
            while True:
                data = await reader.read(1 << 16)
                queue.put_nowait((loop.time() + delay, data))
                if not data:
                    return

        async def tx():
            while True:
                due, data = await queue.get()
                now = loop.time()
                if due > now:
                    await asyncio.sleep(due - now)
                if not data:
                    try:
                        writer.write_eof()
                    except OSError:
                        pass
                    return
                writer.write(data)
                await writer.drain()

        await asyncio.gather(rx(), tx())

    async def handle(client_r, client_w):
        try:
            server_r, server_w = await asyncio.open_connection(
                "127.0.0.1", target_port)
        except OSError:
            client_w.close()
            return
        try:
            await asyncio.gather(pump(client_r, server_w),
                                 pump(server_r, client_w))
        except (ConnectionResetError, BrokenPipeError):
            pass
        finally:
            for w in (client_w, server_w):
                try:
                    w.close()
                except Exception:
                    pass

    async def serve():
        server = await asyncio.start_server(handle, "127.0.0.1",
                                            listen_port)
        async with server:
            await server.serve_forever()

    asyncio.run(serve())


# --------------------------------------------------------------------- #

def measured_one_way_ms(url: str, n: int = 7) -> float:
    """Median round trip of the server's health route through the
    proxy, halved — the wire's delivered latency including HTTP/TCP
    overhead, measured on the same socket path the training loop
    uses."""
    import urllib.request
    rtts = []
    for _ in range(n):
        t0 = time.perf_counter()
        with urllib.request.urlopen(f"{url}/health", timeout=30) as r:
            r.read()
        rtts.append(time.perf_counter() - t0)
    return sorted(rtts)[len(rtts) // 2] / 2 * 1e3


def start_server(allow_out_of_order: bool) -> subprocess.Popen:
    argv = [sys.executable, "-m", "split_learning_tpu.launch.run",
            "serve", "--mode", "split", "--host", "127.0.0.1",
            "--port", str(SERVER_PORT)]
    if allow_out_of_order:
        argv.append("--allow-out-of-order")
    log = open("/tmp/slt_wire_server.log", "ab")
    return subprocess.Popen(argv, env=CPU_ENV, cwd=REPO,
                            stdout=log, stderr=log)


def start_proxy(delay_ms: float) -> subprocess.Popen:
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--proxy",
         str(PROXY_PORT), str(SERVER_PORT), str(delay_ms)],
        cwd=REPO, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)


def run_client(steps: int, depth: int, batches, plan, cfg):
    """Steps/sec of the in-process client half against the proxied
    server (three OS processes end to end; this process never sleeps)."""
    import jax

    from split_learning_tpu.runtime import (
        PipelinedSplitClientTrainer, SplitClientTrainer)
    from split_learning_tpu.transport.http import HttpTransport

    url = f"http://127.0.0.1:{PROXY_PORT}"
    transport = HttpTransport(url)
    print(f"[wire] waiting for server (depth={depth})...",
          file=sys.stderr, flush=True)
    transport.wait_ready(timeout=300)
    print(f"[wire] server ready; warming depth={depth}",
          file=sys.stderr, flush=True)
    x, y = batches
    try:
        if depth == 1:
            client = SplitClientTrainer(plan, cfg, jax.random.PRNGKey(0),
                                        transport)
            for i in range(2):   # compile + warm both parties
                client.train_step(x[i], y[i], i)
            t0 = time.perf_counter()
            for i in range(2, steps + 2):
                client.train_step(x[i], y[i], i)
            return steps / (time.perf_counter() - t0), url
        piped = PipelinedSplitClientTrainer(
            plan, cfg, jax.random.PRNGKey(0), transport, depth=depth,
            transport_factory=lambda: HttpTransport(url))
        try:
            pairs = list(zip(x, y))
            piped.train(lambda: iter(pairs[:2]), epochs=1)  # warm lanes
            t0 = time.perf_counter()
            piped.train(lambda: iter(pairs[2:steps + 2]), epochs=1,
                        start_step=2)
            dt = time.perf_counter() - t0
        finally:
            piped.close()
        return steps / dt, url
    finally:
        transport.close()


def main() -> int:
    if len(sys.argv) > 1 and sys.argv[1] == "--proxy":
        proxy_main(int(sys.argv[2]), int(sys.argv[3]),
                   float(sys.argv[4]))
        return 0

    # CPU-only measurement: the pin must exist before jax is imported
    from split_learning_tpu.utils.backend import reexec_pinned_cpu
    reexec_pinned_cpu()

    ap = argparse.ArgumentParser()
    ap.add_argument("--delay-ms", type=float, action="append",
                    dest="delays",
                    help="one-way propagation delay per direction; "
                         "repeatable — each value becomes one measured "
                         "point (default: 25 and 100)")
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--depth", type=int, default=4)
    ap.add_argument("--out", default=os.path.join(
        REPO, "artifacts", "pipelined_wire.json"))
    args = ap.parse_args()
    delays = args.delays or [25.0, 100.0]

    # a stale server/proxy from a killed run would silently serve the
    # wrong strictness (or the wrong wire) — refuse to measure over one
    import socket
    for port in (PROXY_PORT, SERVER_PORT):
        with socket.socket() as s:
            if s.connect_ex(("127.0.0.1", port)) == 0:
                print(json.dumps({"error": f"port {port} already in "
                                  "use — kill the stale process first"}))
                return 1

    from split_learning_tpu.models import get_plan
    from split_learning_tpu.utils import Config

    cfg = Config(mode="split")
    plan = get_plan(mode="split")
    import numpy as np
    rs = np.random.RandomState(0)
    n = args.steps + 2
    x = rs.rand(n, cfg.batch_size, 28, 28, 1).astype(np.float32)
    y = rs.randint(0, 10, (n, cfg.batch_size))
    batches = (x, y)

    out = {
        "provenance": {
            "date": time.strftime("%Y-%m-%d"),
            "command": "scripts/measure_pipelined_wire.py "
                       + " ".join(f"--delay-ms {d:g}" for d in delays)
                       + f" --steps {args.steps} --depth {args.depth}",
            "topology": "client process <-> delay-proxy process "
                        "(socket-layer propagation delay) <-> server "
                        "process; three OS processes, no in-process "
                        "sleeps",
            "host_cores": os.cpu_count(),
            "netem": "unavailable (sch_netem not in kernel, no "
                     "modprobe) — socket-layer proxy used instead",
            "note": ("with host_cores=1 the parties' COMPUTE convoys "
                     "on the single CPU; the depth-W window hides the "
                     "injected wire AND the per-request overheads "
                     "(serialization in lane threads, socket/kernel "
                     "costs, process-switch dead time) — all real "
                     "per-step costs of the reference's lock-step "
                     "loop. Per-point compute/wire decomposition is "
                     "noise-limited here: the sync baseline's compute "
                     "share moves with probe-subprocess contention on "
                     "the single core, so only the depth cap is "
                     "asserted, not a wire-only cap."),
        },
        "depth": args.depth,
        "steps": args.steps,
        "points": [],
    }

    for delay in delays:
        point = {"one_way_delay_configured_ms": delay}
        proxy = start_proxy(delay)
        try:
            for key, depth, ooo in (
                    ("sync", 1, False),
                    (f"depth{args.depth}", args.depth, True)):
                srv = start_server(allow_out_of_order=ooo)
                try:
                    sps, url = run_client(args.steps, depth, batches,
                                          plan, cfg)
                    print(f"[wire] {delay:g}ms {key}: {sps:.3f} "
                          "steps/s", file=sys.stderr, flush=True)
                    if key == "sync":
                        point["one_way_delay_measured_ms"] = round(
                            measured_one_way_ms(url), 1)
                    point[f"steps_per_sec_{key}"] = round(sps, 4)
                finally:
                    srv.terminate()
                    srv.wait(timeout=30)
        finally:
            proxy.terminate()
            proxy.wait(timeout=10)
        point["pipelining_speedup"] = round(
            point[f"steps_per_sec_depth{args.depth}"]
            / point["steps_per_sec_sync"], 3)
        out["points"].append(point)

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    tmp = args.out + ".tmp"
    with open(tmp, "w") as f:
        json.dump(out, f, indent=1)
    os.replace(tmp, args.out)
    print(json.dumps({"metric": "pipelined_wire_speedup",
                      "points": [{
                          "one_way_ms": p.get(
                              "one_way_delay_measured_ms"),
                          "speedup": p["pipelining_speedup"]}
                          for p in out["points"]],
                      "artifact": args.out}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
