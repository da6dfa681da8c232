#!/usr/bin/env python
"""Quantify how generous bench.py's HTTP baseline (BASELINE.json
configs[0]) is to the reference.

The ~6 steps/sec HTTP baseline runs THIS repo's stack (jitted
JAX half-steps, msgpack+CRC codec). The actual reference pays a different
stack: torch CPU halves and **pickle** serialization of torch tensors over
HTTP (``src/client_part.py:117-131``, ``src/server_part.py:38-58``). This
script measures a faithful reference-style loop — torch ModelPartA/B
semantics (Conv2d(1→32,k3)+ReLU client; Conv2d(32→64,k3)+ReLU → MaxPool2
→ Flatten → Linear(9216,10) server; SGD lr=0.01 both sides; pickle wire)
— over HTTP loopback, and emits the measured gap.

Caveat (stated, not hidden): FastAPI/uvicorn are not installed in this
image, so the server half is a stdlib ThreadingHTTPServer — strictly
*less* framework overhead than the reference's uvicorn+FastAPI route
dispatch, i.e. this measurement still flatters the reference slightly.
The models are re-implemented from the reference's architecture spec, not
copied (``src/model_def.py:5-28``).

Writes ``artifacts/reference_gap.json``.
"""

from __future__ import annotations

import json
import os
import pickle
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# Both legs must run CPU-only: the JAX leg on the default backend would
# take the accelerator while the torch leg stays on CPU — a
# cross-backend "gap". Pinning must exist before the interpreter loads
# jax, so __main__ re-execs via utils.reexec_pinned_cpu (import stays
# side-effect-free).

BATCH = 64
WARMUP, STEPS = 5, 40  # same window as bench.py measure_baseline


def build_server():
    import torch
    from torch import nn

    model_b = nn.Sequential(  # ≡ ModelPartB, src/model_def.py:15-28
        nn.Conv2d(32, 64, 3), nn.ReLU(), nn.MaxPool2d(2),
        nn.Flatten(), nn.Linear(9216, 10))
    opt = torch.optim.SGD(model_b.parameters(), lr=0.01)
    criterion = nn.CrossEntropyLoss()

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, *args):
            pass

        def do_POST(self):
            # ≡ /forward_pass, src/server_part.py:25-58: unpickle, splice
            # the tape via requires_grad_, half-step, return pickled grad
            body = self.rfile.read(int(self.headers["Content-Length"]))
            payload = pickle.loads(body)
            acts = payload["activations"].requires_grad_(True)
            opt.zero_grad()
            loss = criterion(model_b(acts), payload["labels"])
            loss.backward()
            opt.step()
            out = pickle.dumps(acts.grad)
            self.send_response(200)
            self.send_header("Content-Type", "application/octet-stream")
            self.send_header("Content-Length", str(len(out)))
            self.end_headers()
            self.wfile.write(out)

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    return httpd


def run_reference_style() -> dict:
    import numpy as np
    import requests
    import torch
    from torch import nn

    torch.manual_seed(0)
    httpd = build_server()
    url = f"http://127.0.0.1:{httpd.server_address[1]}/forward_pass"

    model_a = nn.Sequential(nn.Conv2d(1, 32, 3), nn.ReLU())  # ≡ ModelPartA
    opt = torch.optim.SGD(model_a.parameters(), lr=0.01)
    rs = np.random.RandomState(0)
    x = torch.from_numpy(
        rs.randn(WARMUP + STEPS, BATCH, 1, 28, 28).astype(np.float32))
    y = torch.from_numpy(
        rs.randint(0, 10, (WARMUP + STEPS, BATCH)).astype(np.int64))
    session = requests.Session()
    rtts = []

    def step(i: int) -> None:
        # ≡ the split-mode hot loop, src/client_part.py:110-133
        opt.zero_grad()
        acts = model_a(x[i])
        payload = pickle.dumps({
            "activations": acts.clone().detach(), "labels": y[i], "step": i})
        t0 = time.perf_counter()
        resp = session.post(url, data=payload)
        grads = pickle.loads(resp.content)
        rtts.append(time.perf_counter() - t0)
        acts.backward(grads)
        opt.step()

    for i in range(WARMUP):
        step(i)
    rtts.clear()
    t0 = time.perf_counter()
    for i in range(WARMUP, WARMUP + STEPS):
        step(i)
    dt = time.perf_counter() - t0
    httpd.shutdown()
    rtts_sorted = sorted(rtts)
    return {
        "steps_per_sec": STEPS / dt,
        "roundtrip_p50_ms": rtts_sorted[len(rtts_sorted) // 2] * 1e3,
        "stack": "torch CPU + pickle + stdlib HTTP (reference-style; "
                 "FastAPI absent, so server framework overhead is a "
                 "slight underestimate)",
    }


def main() -> None:
    from bench import measure_baseline

    ref = run_reference_style()
    print(f"[gap] reference-style: {ref['steps_per_sec']:.2f} steps/s, "
          f"p50 {ref['roundtrip_p50_ms']:.1f} ms", file=sys.stderr)
    ours = measure_baseline(quick=False)
    print(f"[gap] repo baseline:   {ours['steps_per_sec']:.2f} steps/s, "
          f"p50 {ours['roundtrip_p50_ms']:.1f} ms", file=sys.stderr)
    out = {
        "reference_style_pickle_torch": ref,
        "repo_baseline_msgpack_jax": ours,
        "baseline_generosity_ratio": ours["steps_per_sec"] / ref["steps_per_sec"],
    }
    path = os.path.join(REPO, "artifacts", "reference_gap.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"[gap] wrote {path}", file=sys.stderr)


if __name__ == "__main__":
    from split_learning_tpu.utils import reexec_pinned_cpu
    reexec_pinned_cpu()
    main()
