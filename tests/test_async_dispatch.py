"""Async server dispatch (PR 5): the lock covers only admission + the
jitted call, host materialization runs off-lock (``d2h``), and the
client can stage batches on device while a step is in flight
(``DevicePrefetch``). A test that needs a reply held in its
materialization window takes conftest's ``hold_host_gather``."""

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
from split_learning_tpu.runtime.stage import StageRuntime
from split_learning_tpu.transport.http import HttpTransport
from split_learning_tpu.transport.local import LocalTransport
from split_learning_tpu.utils import Config

BATCH = 4
PROBE_S = 5.0  # what health() may take before it counts as blocked


def _server(mode="split", **kw):
    cfg = Config(mode=mode, batch_size=BATCH, num_clients=2)
    plan = get_plan(mode=mode)
    sample = np.zeros((BATCH, 28, 28, 1), np.float32)
    return cfg, plan, ServerRuntime(plan, cfg, jax.random.PRNGKey(2),
                                    sample, **kw)


def _batch(seed=0):
    rs = np.random.RandomState(seed)
    return (rs.randn(BATCH, 28, 28, 1).astype(np.float32),
            rs.randint(0, 10, BATCH).astype(np.int64))


def _cut(seed=0):
    """A cut-layer batch, as a client's stage 0 would send it."""
    rs = np.random.RandomState(seed)
    return (rs.randn(BATCH, 26, 26, 32).astype(np.float32),
            rs.randint(0, 10, BATCH).astype(np.int64))


# ---------------------------------------------------------------------- #
# the tentpole: materialization runs off the lock, for every reply kind
# ---------------------------------------------------------------------- #

# each: (party, calls that warm it up together, calls whose copies are held)

def _serialized_reply(**server_kw):
    _, _, server = _server(**server_kw)
    x, y = _cut()
    return (server, [lambda: server.split_step(x, y, 0)],
            [lambda: server.split_step(x, y, 1)])


def _group_reply():
    # two clients fill a group of two: the flusher dispatches it and the
    # first waiter to redeem its thunk pays the group's one copy
    _, _, server = _server(coalesce_max=2, coalesce_window_ms=500.0)
    x, y = _cut()

    def round_of(step):
        return [(lambda c=c: server.split_step(x, y, step, client_id=c))
                for c in (0, 1)]

    return server, round_of(0), round_of(1)


def _u_forward_reply():
    _, _, server = _server(mode="u_split")
    x, _ = _cut()
    return (server, [lambda: server.u_forward(x, 0)],
            [lambda: server.u_forward(x, 1)])


def _u_backward_reply():
    _, _, server = _server(mode="u_split")
    x, _ = _cut()
    g = np.zeros((BATCH, 12 * 12 * 64), np.float32)

    def warm():
        server.u_forward(x, 0)
        server.u_backward(g, 0)
        server.u_forward(x, 1)

    return server, [warm], [lambda: server.u_backward(g, 1)]


def _stage_hop_reply():
    cfg = Config(mode="split", model="split_cnn_chain3", batch_size=BATCH,
                 num_stages=3, microbatches=1)
    plan = get_plan(model="split_cnn_chain3", mode="split")
    sample = np.zeros((BATCH, 28, 28, 1), np.float32)
    stage = StageRuntime(plan, 1, cfg, jax.random.PRNGKey(2), sample,
                         microbatches=1, apply_lag=0)
    x = np.asarray(plan.stages[0].apply(
        plan.init(jax.random.PRNGKey(2), sample)[0], sample))
    return (stage, [lambda: stage.hop_forward(x, 0)],
            [lambda: stage.hop_forward(x, 1)])


REPLY_KINDS = {
    "split_step": _serialized_reply,
    "coalesced_group": _group_reply,
    "2bp_reply": lambda: _serialized_reply(decouple_bwd=True, apply_lag=2),
    "u_forward": _u_forward_reply,
    "u_backward": _u_backward_reply,
    "stage_hop": _stage_hop_reply,
}


@pytest.mark.parametrize("kind", list(REPLY_KINDS))
def test_materialization_does_not_hold_the_lock(kind, hold_host_gather):
    """While a reply's device-to-host copy is in flight, ``health()``,
    which takes the runtime lock, returns: no reply path keeps the lock
    across its copy. The linter says so of the source (SLT001); this
    says so of each path as it runs."""
    party, warm, ops = REPLY_KINDS[kind]()
    try:
        hold_host_gather.join(hold_host_gather.spawn(*warm))  # compile
        with hold_host_gather(party) as hold:
            threads = hold.spawn(*ops)
            assert hold.entered.wait(hold.WAIT_S), "no reply reached its copy"
            probe = threading.Thread(target=party.health, daemon=True)
            probe.start()
            probe.join(PROBE_S)
            blocked = probe.is_alive()
            hold.release.set()
            hold.join(threads)
        assert not blocked, f"{kind}: health() waited for the lock the copy held"
    finally:
        party.close()


def test_concurrent_smoke_records_d2h_off_lock():
    """N=2 concurrent clients, traced: every step records a server
    ``d2h`` span that covers its (here slowed) copy while ``dispatch``,
    the lock-held window, stays well under it, so the transfer really
    left the lock; and the ``lock_hold`` histogram populates and renders
    as slt_lock_hold_seconds. This is the CI overlap smoke."""
    d2h = 0.05
    cfg, plan, server = _server()
    gather = server._host_gather

    def slow_gather(x, rows=None):  # the test's stand-in for a transfer
        time.sleep(d2h)
        return gather(x, rows=rows)

    server._host_gather = slow_gather
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
