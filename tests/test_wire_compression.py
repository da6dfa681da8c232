"""topk8 wire mode end-to-end: the none-path pin (--compress none must be
bit-for-bit the legacy wire), error-feedback semantics (rollback on a lost
POST, no rollback in-process), the bitmap/index encoding switch, and the
compression-ratio accounting surfaced on /metrics; and the int8 mode's
payload size, alone and over HTTP."""

import math

import jax
import numpy as np
import pytest
import requests

from split_learning_tpu.models import get_plan
from split_learning_tpu.runtime import ServerRuntime, SplitClientTrainer
from split_learning_tpu.transport import LocalTransport, TransportError
from split_learning_tpu.transport.http import HttpTransport, SplitHTTPServer
from split_learning_tpu.transport import codec
from split_learning_tpu.utils import Config

BATCH = 8


def make_server(seed=0):
    cfg = Config(mode="split", batch_size=BATCH)
    plan = get_plan(mode="split")
    sample = np.zeros((BATCH, 28, 28, 1), np.float32)
    return plan, cfg, ServerRuntime(plan, cfg, jax.random.PRNGKey(seed),
                                    sample)


def train_steps(plan, cfg, transport, n, seed=1):
    client = SplitClientTrainer(plan, cfg, jax.random.PRNGKey(0), transport)
    rs = np.random.RandomState(seed)
    losses = []
    for step in range(n):
        x = rs.randn(BATCH, 28, 28, 1).astype(np.float32)
        y = rs.randint(0, 10, (BATCH,)).astype(np.int64)
        losses.append(client.train_step(x, y, step))
    return client, losses


# --------------------------------------------------------------------- #
# the none pin: adding the compression layer must not move a single bit
# of the uncompressed path
# --------------------------------------------------------------------- #
def test_local_compress_none_matches_legacy_bitwise():
    """LocalTransport(compress=None) is the legacy direct path;
    compress="none" adds the full wire emulation — the step math must be
    bit-for-bit identical between them."""
    plan, cfg, rt_a = make_server()
    _, _, rt_b = make_server()
    _, losses_a = train_steps(plan, cfg, LocalTransport(rt_a), 6)
    client_b, losses_b = train_steps(
        plan, cfg, LocalTransport(rt_b, compress="none"), 6)
    assert losses_a == losses_b  # float equality: identical trajectories
    for la, lb in zip(jax.tree_util.tree_leaves(rt_a.state.params),
                      jax.tree_util.tree_leaves(rt_b.state.params)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def test_http_compress_none_payload_unchanged():
    """With --compress none the POSTed tree must carry no compress/
    density keys and raw float32 activations — the wire format of every
    previous release, pinned."""
    plan, cfg, runtime = make_server()
    server = SplitHTTPServer(runtime).start()
    transport = HttpTransport(server.url)  # compress defaults to "none"
    sent = []
    orig = transport._session.post

    def capture(url, data=None, **kw):
        sent.append(codec.decode(data))
        return orig(url, data=data, **kw)

    transport._session.post = capture
    try:
        train_steps(plan, cfg, transport, 2)
    finally:
        transport.close()
        server.stop()
    assert sent
    for tree in sent:
        assert "compress" not in tree and "density" not in tree
        acts = tree["activations"]
        assert isinstance(acts, np.ndarray) and acts.dtype == np.float32
    assert transport.stats.summary().get("compression_ratio") is None


# --------------------------------------------------------------------- #
# codec wire format: encoding switch + error-feedback state machine
# --------------------------------------------------------------------- #
def test_bitmap_vs_index_encoding_switch():
    """density 0.1 -> packed bitmask (n/8 B < 4k B); density < 1/32 ->
    int32 indices win. Both must round-trip exactly."""
    rs = np.random.RandomState(0)
    a = rs.randn(64, 64).astype(np.float32)
    dense, _ = codec.topk8_compress(a, 0.1)
    assert "m" in dense and "idx" not in dense
    sparse, _ = codec.topk8_compress(a, 0.01)
    assert "idx" in sparse and "m" not in sparse
    for packed, density in ((dense, 0.1), (sparse, 0.01)):
        out = codec.decompress_tree(codec.decode(codec.encode(packed)))
        assert out.shape == a.shape and out.dtype == a.dtype
        k = math.ceil(density * a.size)
        assert int(np.count_nonzero(out)) <= k


def test_topk8_wire_is_smaller_than_q8():
    a = np.random.RandomState(1).randn(64, 26, 26, 32).astype(np.float32)
    raw = len(codec.encode({"x": a}))
    q8 = len(codec.encode({"x": codec.q8_compress(a)}))
    tk = len(codec.encode({"x": codec.topk8_compress(a, 0.1)[0]}))
    assert raw / tk >= 8.0
    assert q8 / tk >= 2.5


def test_ef_rollback_restores_state():
    """compress -> rollback -> compress must equal a fresh compress (the
    failed send never happened); without rollback the residual feeds the
    next selection and the packed tensors differ."""
    rs = np.random.RandomState(2)
    a = rs.randn(32, 32).astype(np.float32)
    ef = codec.TopK8EF()
    p1 = ef.compress("k", a, 0.1)
    ef.rollback("k")
    p2 = ef.compress("k", a, 0.1)
    np.testing.assert_array_equal(p1["q"], p2["q"])
    np.testing.assert_array_equal(p1["m"], p2["m"])
    assert p1["scale"] == p2["scale"]
    p3 = ef.compress("k", a, 0.1)  # no rollback: residual now in play
    assert (not np.array_equal(p2["q"], p3["q"])
            or not np.array_equal(p2["m"], p3["m"]))


def test_ef_residual_reduces_two_step_error():
    """The point of error feedback: over two steps on the same input,
    shipped mass accumulates — reconstruction error after step 2 is
    strictly below the stateless single-shot error."""
    rs = np.random.RandomState(3)
    a = rs.randn(64, 64).astype(np.float32)
    stateless, _ = codec.topk8_compress(a, 0.05)
    err0 = float(np.linalg.norm(a - codec.topk8_decompress(stateless)))
    ef = codec.TopK8EF()
    d1 = codec.topk8_decompress(ef.compress("k", a, 0.05))
    d2 = codec.topk8_decompress(ef.compress("k", a, 0.05))
    err_ef = float(np.linalg.norm(2 * a - (d1 + d2))) / 2
    assert err_ef < err0
    # and the second step ships mass the first one dropped, instead of
    # re-sending the same top coordinates forever (the stateless failure
    # mode EF exists to fix)
    nz1 = set(np.flatnonzero(d1.reshape(-1)))
    nz2 = set(np.flatnonzero(d2.reshape(-1)))
    assert len(nz2 - nz1) > len(nz1) // 2


def test_http_transport_rolls_back_ef_on_failed_post():
    """A POST that never reached the server must not leave the shipped
    mass marked delivered: the client's EF buffer for that role is
    restored to its pre-call state."""
    transport = HttpTransport("http://127.0.0.1:9", timeout=0.2,
                              compress="topk8", density=0.1)
    rs = np.random.RandomState(4)
    acts = rs.randn(BATCH, 26, 26, 32).astype(np.float32)
    labels = rs.randint(0, 10, (BATCH,)).astype(np.int64)
    with pytest.raises(TransportError):
        transport.split_step(acts, labels, 0)
    assert transport._ef._res.get("acts") is None  # rolled back to fresh
    transport.close()


# --------------------------------------------------------------------- #
# end-to-end: training through the compressed wire
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("mode", ["int8", "topk8"])
def test_local_wire_emulation_trains(mode):
    plan, cfg, runtime = make_server()
    transport = LocalTransport(runtime, compress=mode, density=0.1)
    _, losses = train_steps(plan, cfg, transport, 12)
    assert all(np.isfinite(l) for l in losses)
    assert np.mean(losses[-3:]) < np.mean(losses[:3])
    s = transport.stats.summary()
    if mode == "topk8":
        assert s["compression_ratio"] > 8.0
    else:
        assert s["compression_ratio"] > 3.5


def test_http_topk8_end_to_end_with_metrics_gauge():
    """Full loopback run with both parties in topk8 mode: training
    converges, the client records its ratio, and the server publishes
    wire_compression_ratio on /metrics."""
    plan, cfg, runtime = make_server()
    server = SplitHTTPServer(runtime, compress="topk8",
                             density=0.1).start()
    transport = HttpTransport(server.url, compress="topk8", density=0.1)
    try:
        _, losses = train_steps(plan, cfg, transport, 8)
        assert all(np.isfinite(l) for l in losses)
        s = transport.stats.summary()
        assert s["compression_ratio"] > 8.0
        body = requests.get(f"{server.url}/metrics", timeout=10).text
        line = [l for l in body.splitlines()
                if l.startswith("slt_wire_compression_ratio")]
        assert line, body
        assert float(line[0].split()[-1]) > 8.0
    finally:
        transport.close()
        server.stop()


def test_http_server_honors_client_requested_mode():
    """The request's compress key overrides the server default, so a
    dense client against a topk8-default server still gets dense replies
    (and vice versa) — mixed fleets stay correct."""
    plan, cfg, runtime = make_server()
    server = SplitHTTPServer(runtime, compress="topk8",
                             density=0.1).start()
    dense = HttpTransport(server.url)  # compress="none"
    try:
        _, losses = train_steps(plan, cfg, dense, 3)
        assert all(np.isfinite(l) for l in losses)
        # no compressed leaves travelled in either direction
        assert dense.stats.summary().get("compression_ratio") is None
    finally:
        dense.close()
        server.stop()


def test_q8_wire_shrinks_payload(rng):
    x = np.asarray(jax.random.normal(rng, (64, 26, 26, 32), np.float32))
    raw = codec.encode(x)
    compressed = codec.encode(codec.q8_compress(x))
    assert len(compressed) < len(raw) / 3.5  # ~4x minus header overhead
    back = codec.decompress_tree(codec.decode(compressed))
    assert back.shape == x.shape and back.dtype == x.dtype


def test_http_transport_int8_compression(rng, mnist_batch):
    """End-to-end split step over HTTP with int8 wire compression."""
    x, y = mnist_batch
    x, y = np.asarray(x[:16]), np.asarray(y[:16])
    cfg = Config(mode="split", batch_size=16)
    plan = get_plan(mode="split")
    runtime = ServerRuntime(plan, cfg, rng, x)
    server = SplitHTTPServer(runtime).start()
    try:
        plain = HttpTransport(server.url)
        lossy = HttpTransport(server.url, compress="int8")
        c = SplitClientTrainer(plan, cfg, rng, lossy)
        loss = c.train_step(x, y, 0)
        assert np.isfinite(loss)
        # cut tensor is [16, 26, 26, 32]; int8 wire ~1 byte/elem vs 4 fp32
        acts_elems = 16 * 26 * 26 * 32
        assert lossy.stats.bytes_sent < acts_elems * 1.1
        assert lossy.stats.bytes_received < acts_elems * 1.1
        plain.close()
        lossy.close()
    finally:
        server.stop()
