"""Async server dispatch (PR 5): the lock covers only admission + the
jitted call, host materialization runs off-lock (``d2h``), and the
client can stage batches on device while a step is in flight
(``DevicePrefetch``). The synthetic ``d2h_delay_s`` knob widens the
materialization window so lock behavior is observable on CPU JAX, which
has no real transfer cost."""

import threading
import time

import jax
import numpy as np
import pytest

from split_learning_tpu import obs
from split_learning_tpu.data.datasets import DevicePrefetch
from split_learning_tpu.obs import locks
from split_learning_tpu.models import get_plan
from split_learning_tpu.obs.metrics import (Histogram, histogram_percentile,
                                            render_prometheus)
from split_learning_tpu.runtime import ServerRuntime, SplitClientTrainer
from split_learning_tpu.runtime.multi_client import MultiClientSplitRunner
from split_learning_tpu.transport.http import HttpTransport
from split_learning_tpu.transport.local import LocalTransport
from split_learning_tpu.utils import Config

BATCH = 4


def _server(**kw):
    cfg = Config(mode="split", batch_size=BATCH, num_clients=2)
    plan = get_plan(mode="split")
    sample = np.zeros((BATCH, 28, 28, 1), np.float32)
    return cfg, plan, ServerRuntime(plan, cfg, jax.random.PRNGKey(2),
                                    sample, **kw)


def _batch(seed=0):
    rs = np.random.RandomState(seed)
    return (rs.randn(BATCH, 28, 28, 1).astype(np.float32),
            rs.randint(0, 10, BATCH).astype(np.int64))


# ---------------------------------------------------------------------- #
# the tentpole: materialization runs off the lock
# ---------------------------------------------------------------------- #

def _health_latency_during_step(overlap: bool) -> float:
    """Start a step whose materialization is padded to 0.4 s, then time
    health() — which needs the runtime lock — while it runs."""
    cfg, plan, server = _server(overlap=overlap, d2h_delay_s=0.4)
    client = SplitClientTrainer(plan, cfg, jax.random.PRNGKey(0),
                                LocalTransport(server))
    x, y = _batch()
    client.train_step(x, y, 0)  # compile + first padded materialization

    t = threading.Thread(target=client.train_step, args=(x, y, 1))
    t.start()
    # by now the step thread is inside the server: dispatch is a few ms
    # after warmup, so it is sitting in the 0.4 s materialization window
    time.sleep(0.1)
    t0 = time.perf_counter()
    server.health()
    dt = time.perf_counter() - t0
    t.join()
    server.close()
    return dt


def test_materialization_does_not_hold_the_lock():
    """With overlap on, health() gets the lock while the step's D2H is
    still in flight; with overlap off the same call blocks behind the
    materialization — the direct observable of the async-dispatch
    restructure."""
    assert _health_latency_during_step(overlap=True) < 0.15
    assert _health_latency_during_step(overlap=False) > 0.15


def test_overlap_loss_series_bit_identical():
    """Moving the D2H off the lock cannot change numerics: same jitted
    program, same application order — the sequential loss series must
    match bit for bit."""
    def series(overlap):
        cfg, plan, server = _server(overlap=overlap)
        client = SplitClientTrainer(plan, cfg, jax.random.PRNGKey(0),
                                    LocalTransport(server))
        try:
            return [client.train_step(*_batch(i), i) for i in range(4)]
        finally:
            server.close()

    assert series(True) == series(False)


def test_concurrent_smoke_records_d2h_off_lock():
    """N=2 concurrent clients, traced: every step records a ``d2h`` span
    at least as long as the synthetic delay while the ``dispatch`` span
    (the lock-held window) stays well under it — i.e. the transfer
    really left the lock — and the ``lock_hold`` histogram populates and
    renders as slt_lock_hold_seconds. This is the CI overlap smoke."""
    d2h = 0.08
    cfg, plan, server = _server(overlap=True, d2h_delay_s=d2h)
    runner = MultiClientSplitRunner(
        plan, cfg, jax.random.PRNGKey(1),
        lambda i: LocalTransport(server),
        num_clients=2, concurrent=True)
    rs = np.random.RandomState(0)
    x = rs.randn(3, 2, BATCH, 28, 28, 1).astype(np.float32)
    y = rs.randint(0, 10, (3, 2, BATCH)).astype(np.int64)
    try:
        runner.train_round(list(zip(x[0], y[0])))  # untraced warmup
        tr = obs.enable()
        try:
            for r in range(1, 3):
                runner.train_round(list(zip(x[r], y[r])))
        finally:
            obs.disable()
        snap = server.metrics()
    finally:
        runner.close()
        server.close()

    spans = tr.spans()
    # the client's copy of the cut tensor to host is a d2h too: a
    # record's party tells them apart
    d2h_spans = [s for s in spans
                 if s["name"] == "d2h" and s["party"] == "server"]
    assert len(d2h_spans) == 4  # 2 rounds x 2 clients
    assert all(s["duration"] >= d2h for s in d2h_spans)

    hists = snap["histograms"]
    assert hists["d2h"]["count"] == 4
    text = render_prometheus(snap)
    assert "slt_d2h_seconds_count 4" in text
    # under SLT_LOCK_DEBUG=1 the obs/locks.py watchdog also feeds
    # lock_hold (one observation per outermost acquisition, warmup
    # included), so the exact traced-step tally only holds watchdog-off
    if locks.enabled():
        assert hists["lock_hold"]["count"] >= 4
    else:
        assert hists["lock_hold"]["count"] == 4
        assert "slt_lock_hold_seconds_count 4" in text
        # lock-held window excludes the materialization: its p50 sits
        # far below the padded transfer the old taxonomy would have
        # absorbed
        assert histogram_percentile(hists["lock_hold"], 50) < d2h / 2
    assert histogram_percentile(hists["dispatch"], 50) < d2h / 2


def test_histogram_percentile():
    h = Histogram(buckets=(0.01, 0.1, 1.0))
    assert histogram_percentile(h.snapshot(), 50) == 0.0  # empty
    for v in [0.005] * 50 + [0.5] * 50:
        h.observe(v)
    snap = h.snapshot()
    assert histogram_percentile(snap, 25) <= 0.01
    assert 0.1 < histogram_percentile(snap, 75) <= 1.0
    assert histogram_percentile(snap, 100) == 1.0
    h.observe(5.0)  # +Inf slot clamps to last finite bound
    assert histogram_percentile(h.snapshot(), 100) == 1.0
    with pytest.raises(ValueError):
        histogram_percentile(snap, 101)


# ---------------------------------------------------------------------- #
# satellite: HTTP connection pool must not serialize wide windows
# ---------------------------------------------------------------------- #

def test_http_transport_pool_sizing():
    """urllib3's default pool of 10 silently serializes >10 concurrent
    lanes on a shared session; the transport must mount an adapter sized
    to its pool_maxsize (default 32 >= any sane --pipeline-depth)."""
    t = HttpTransport("http://127.0.0.1:1")
    try:
        adapter = t._session.get_adapter("http://127.0.0.1:1/step")
        assert adapter._pool_maxsize == 32
        assert adapter._pool_connections == 32
    finally:
        t.close()

    t = HttpTransport("http://127.0.0.1:1", pool_maxsize=48)
    try:
        assert t.pool_maxsize == 48
        assert t._session.get_adapter("http://x")._pool_maxsize == 48
        assert t._session.get_adapter("https://x")._pool_maxsize == 48
    finally:
        t.close()

    with pytest.raises(ValueError, match="pool_maxsize"):
        HttpTransport("http://127.0.0.1:1", pool_maxsize=0)


# ---------------------------------------------------------------------- #
# satellite: DevicePrefetch
# ---------------------------------------------------------------------- #

def test_device_prefetch_yields_identical_sequence():
    batches = [(np.full((2, 3), i, np.float32), np.arange(3) + i)
               for i in range(7)]
    with DevicePrefetch(batches, depth=3) as pf:
        out = list(pf)
    assert len(out) == len(batches)
    for (x, y), (xd, yd) in zip(batches, out):
        assert isinstance(xd, jax.Array)  # staged on device
        np.testing.assert_array_equal(np.asarray(xd), x)
        np.testing.assert_array_equal(yd, y)  # labels pass through


def test_device_prefetch_drains_cleanly_on_early_exit():
    def gen():
        for i in range(10_000):
            yield np.full((2, 2), i, np.float32), i

    pf = DevicePrefetch(gen(), depth=2)
    first = next(pf)
    assert float(np.asarray(first[0])[0, 0]) == 0.0
    pf.close()
    assert not pf._thread.is_alive()  # no leaked staging thread
    with pytest.raises(StopIteration):
        next(pf)
    pf.close()  # idempotent


def test_device_prefetch_propagates_source_error():
    def bad():
        yield np.zeros((1, 1), np.float32), 0
        raise RuntimeError("boom")

    pf = DevicePrefetch(bad(), depth=1)
    next(pf)
    with pytest.raises(RuntimeError, match="boom"):
        next(pf)
    assert not pf._thread.is_alive()

    with pytest.raises(ValueError, match="depth"):
        DevicePrefetch([], depth=0)


def test_trainer_prefetch_loss_parity():
    """train(prefetch=N) must reproduce the unprefetched run bit for bit
    — device staging is value-preserving and order is FIFO."""
    batches = [(_batch(i)) for i in range(5)]

    def run(prefetch):
        cfg, plan, server = _server()
        client = SplitClientTrainer(plan, cfg, jax.random.PRNGKey(0),
                                    LocalTransport(server))
        try:
            recs = client.train(lambda: iter(batches), epochs=1,
                                prefetch=prefetch)
            return [r.loss for r in recs]
        finally:
            server.close()

    assert run(0) == run(2)


def test_multi_client_train_rounds_with_prefetch():
    cfg, plan, server = _server()
    runner = MultiClientSplitRunner(
        plan, cfg, jax.random.PRNGKey(1),
        lambda i: LocalTransport(server), num_clients=2)
    iters = [[_batch(10 * c + r) for r in range(3)] for c in range(2)]
    try:
        losses = runner.train_rounds(iters, prefetch=1)
    finally:
        runner.close()
        server.close()
    # drains when the iterators do: 3 rounds of 2 clients, finite losses
    assert len(losses) == 3 and all(len(r) == 2 for r in losses)
    assert all(np.isfinite(l) for r in losses for l in r)
