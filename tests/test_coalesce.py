"""Request coalescing (runtime/coalesce.py + ServerRuntime group
dispatch): concurrent split-step traffic batches into one jitted
dispatch per group, with the serialized path pinned bit-for-bit at
``coalesce_max=1`` and a group of one reproducing serialized semantics
(the acceptance criteria of the coalescing issue)."""

import sys
import threading
import time
from collections import deque

import jax
import numpy as np
import pytest

from split_learning_tpu.models import get_plan
from split_learning_tpu.runtime import (
    ProtocolError, ServerRuntime, SplitClientTrainer)
from split_learning_tpu.runtime.coalesce import (
    FLUSH_REASONS, CoalesceRequest, RequestCoalescer, pow2_bucket)
from split_learning_tpu.runtime.multi_client import MultiClientSplitRunner
from split_learning_tpu.transport import LocalTransport
from split_learning_tpu.transport.base import TransportStats
from split_learning_tpu.utils import Config

BATCH = 8


def make_server(coalesce_max=1, window_ms=50.0, n_clients=1, strict=True):
    cfg = Config(mode="split", batch_size=BATCH, num_clients=n_clients)
    plan = get_plan(mode="split")
    sample = np.zeros((BATCH, 28, 28, 1), np.float32)
    server = ServerRuntime(plan, cfg, jax.random.PRNGKey(0), sample,
                           strict_steps=strict, coalesce_max=coalesce_max,
                           coalesce_window_ms=window_ms)
    return cfg, plan, server


def batch(seed, n=BATCH):
    rs = np.random.RandomState(seed)
    y = rs.randint(0, 10, (n,))
    x = rs.randn(n, 28, 28, 1).astype(np.float32)
    return x, y.astype(np.int64)


# --------------------------------------------------------------------- #
# unit: the queue half, no jax involved
# --------------------------------------------------------------------- #

def test_pow2_bucket():
    assert [pow2_bucket(n) for n in (1, 2, 3, 5, 8, 9, 33)] == \
        [1, 2, 4, 8, 8, 16, 64]
    with pytest.raises(ValueError):
        pow2_bucket(0)


def _resolve_all(group, reason):
    for r in group:
        r.result = (r.acts, float(len(group)))
        r.done.set()


def test_coalescer_full_and_window_flush_reasons():
    groups = []

    def dispatch(group, reason):
        groups.append((len(group), reason))
        _resolve_all(group, reason)

    c = RequestCoalescer(dispatch, max_group=2, window_s=0.2)
    try:
        a = batch(0)
        # two concurrent same-shape submits -> one FULL group of 2
        t = threading.Thread(target=c.submit, args=(a[0], a[1], 0, 0))
        t.start()
        c.submit(a[0], a[1], 0, 1)
        t.join(timeout=10)
        # a lone submit -> the window closes on a group of 1
        _, n = c.submit(a[0], a[1], 1, 0)
        assert n == 1.0
        assert sorted(groups) == [(1, "window"), (2, "full")]
        counters = c.counters()
        assert counters["groups_flushed"] == 2
        assert counters["requests_coalesced"] == 3
        assert counters["flush_full"] == 1
        assert counters["flush_window"] == 1
        assert counters["mean_occupancy"] == pytest.approx(1.5)
    finally:
        c.close()


def test_coalescer_mixed_shapes_never_share_a_group():
    seen = []

    def dispatch(group, reason):
        seen.append({r.shape_key() for r in group})
        _resolve_all(group, reason)

    c = RequestCoalescer(dispatch, max_group=4, window_s=0.3)
    try:
        a, b = batch(0), batch(1, n=4)
        threads = [
            threading.Thread(target=c.submit, args=(a[0], a[1], 0, 0)),
            threading.Thread(target=c.submit,
                             args=(b[0].astype(np.float64), b[1], 0, 1)),
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10)
        # each flushed group is shape-homogeneous
        assert all(len(keys) == 1 for keys in seen)
        assert len(seen) == 2
    finally:
        c.close()


def test_coalescer_dispatch_error_reaches_waiter_and_thread_survives():
    calls = {"n": 0}

    def dispatch(group, reason):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("boom")
        _resolve_all(group, reason)

    c = RequestCoalescer(dispatch, max_group=2, window_s=0.01)
    try:
        a = batch(0)
        with pytest.raises(RuntimeError, match="boom"):
            c.submit(a[0], a[1], 0, 0)
        # the flusher survived the failed dispatch
        _, n = c.submit(a[0], a[1], 1, 0)
        assert n == 1.0
    finally:
        c.close()


def test_coalescer_config_and_close_contract():
    with pytest.raises(ValueError):
        RequestCoalescer(_resolve_all, max_group=1, window_s=0.01)
    with pytest.raises(ValueError):
        RequestCoalescer(_resolve_all, max_group=2, window_s=-1.0)
    c = RequestCoalescer(_resolve_all, max_group=2, window_s=0.01)
    c.close()
    c.close()  # idempotent
    a = batch(0)
    with pytest.raises(RuntimeError):
        c.submit(a[0], a[1], 0, 0)


# --------------------------------------------------------------------- #
# unit: the cut by measured step times (shed), still no jax
# --------------------------------------------------------------------- #

# seconds from dispatch to the first reply, by padded row bucket, of
# requests of 8 rows: the party cell's (a step costs its rows), and a
# step that is mostly fixed cost
BY_ROWS = {8: [0.033] * 2, 16: [0.0505] * 2, 32: [0.095] * 2}
FIXED = {8: [0.045] * 2, 16: [0.050] * 2, 32: [0.060] * 2}


def _assert_reasons_sum(counters):
    assert counters["groups_flushed"] == sum(
        counters.get(f"flush_{why}", 0) for why in FLUSH_REASONS)


def _planted(max_group, served, dispatch=_resolve_all, window_s=0.002):
    """A coalescer with planted observations (no clock read): requests
    of 8 rows from clients 0.., ``served`` the samples by bucket."""
    c = RequestCoalescer(dispatch, max_group=max_group, window_s=window_s)
    reqs = [CoalesceRequest(np.zeros((8, 4), np.float32),
                            np.zeros((8,), np.int64), 0, i)
            for i in range(max_group)]
    c._served.update({(reqs[0].shape_key(), b): deque(v, maxlen=4)
                      for b, v in served.items()})
    return c, reqs


@pytest.mark.parametrize("served, n, kept", [
    # three pad to the step of four: 2 x (95 - 50.5) gained, and the
    # device's time falls by 95 - 50.5 - 33
    (BY_ROWS, 3, 2),
    # four in two halves: 2 x 44.5 gained against 6 x (101 - 95)
    (BY_ROWS, 4, 2),
    # a pair in singles: 17.5 gained against 3 x (66 - 50.5)
    (BY_ROWS, 2, 2),
    # mostly fixed cost: 2 x 10 gained against 4 x 35, and 6 x 40
    (FIXED, 3, 3),
    (FIXED, 4, 4),
    # the smaller buckets not measured yet
    ({32: [0.095] * 2}, 3, 3),
    # one sample is the call that compiled: the bucket does not count
    ({**BY_ROWS, 8: [0.033]}, 3, 3),
])
def test_shed_cuts_a_group_where_the_measured_times_say_so(served, n, kept):
    c, reqs = _planted(4, served)
    try:
        group = reqs[:n]
        assert c._shed(reqs[0].shape_key(), group) == (kept < n)
        assert group == reqs[:kept]
        assert c._queue == reqs[kept:n]     # back at the head, in order
    finally:
        c._queue.clear()
        c.close()


@pytest.mark.parametrize("served, n, cuts", [
    (BY_ROWS, 3, [(2, "shed"), (1, "window")]),
    (BY_ROWS, 4, [(2, "shed"), (2, "window")]),
    (FIXED, 3, [(3, "window")]),
    (FIXED, 4, [(4, "full")]),
])
def test_a_round_is_cut_as_the_measured_times_say(served, n, cuts):
    """``n`` clients arrive together at a server of ``max_group`` 4 whose
    planted times decide before any of the round's own samples lands:
    the round runs in exact halves where a step costs its rows, whole
    where it is mostly fixed cost; the requests shed head the next group,
    which waits a window of its own, and every client is answered."""
    groups = []

    def dispatch(group, reason):
        groups.append(([r.client_id for r in group], reason))
        _resolve_all(group, reason)

    c, reqs = _planted(4, served, dispatch, window_s=0.25)
    errors = []

    def one(r):
        try:
            c.submit(r.acts, r.labels, 0, r.client_id, timeout=30.0)
        except Exception as exc:  # propagate to the main thread
            errors.append(exc)

    try:
        threads = [threading.Thread(target=one, args=(r,)) for r in reqs[:n]]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not errors and not any(t.is_alive() for t in threads)
        assert [(len(g), why) for g, why in groups] == cuts
        assert sorted(i for g, _ in groups for i in g) == list(range(n))
        counters = c.counters()
        assert counters.get("flush_shed", 0) == (len(cuts) - 1)
        _assert_reasons_sum(counters)
    finally:
        c.close()


@pytest.mark.parametrize("max_group", [2, 4])
def test_lone_client_flushes_on_the_window_whatever_was_measured(max_group):
    """A group of one has nothing to shed: a lone client's requests
    flush on the window, as they always did."""
    c, reqs = _planted(max_group, BY_ROWS)
    try:
        for step in range(5):
            c.submit(reqs[0].acts, reqs[0].labels, step, 0, timeout=30.0)
        counters = c.counters()
        assert counters["flush_window"] == counters["groups_flushed"] == 5
    finally:
        c.close()


def test_a_group_is_timed_once_and_a_compile_never_sets_the_cost():
    """The first waiter to hold its reply times its group, once; a
    bucket's cost is the least of its last four samples and counts from
    the second (a shape's first call compiles); a group that failed, or
    went out while the one before it had no reply yet, says nothing."""
    slow, release = {"first": True}, threading.Event()

    def dispatch(group, reason):
        if group[0].step == 9:
            raise RuntimeError("boom")

        def redeem(r):
            def thunk():
                if slow.pop("first", False):
                    time.sleep(0.2)     # the call that compiles
                if r.step == 20:
                    assert release.wait(timeout=30)  # still on the device
                return r.acts, 1.0
            return thunk

        for r in group:
            r.result = redeem(r)
            r.done.set()

    c = RequestCoalescer(dispatch, max_group=2, window_s=0.001)
    a = np.zeros((1, 4), np.float32), np.zeros((1,), np.int64)
    key = CoalesceRequest(a[0], a[1], 0, 0).shape_key()
    try:
        c.submit(a[0], a[1], 0, 0)
        singles = c._served[(key, 1)]
        assert len(singles) == 1 and c._cost(key, 1) is None
        assert singles[0] >= 0.2
        c.submit(a[0], a[1], 1, 0)
        assert c._cost(key, 1) == min(singles) < 0.2
        with pytest.raises(RuntimeError, match="boom"):
            c.submit(a[0], a[1], 9, 0)
        assert len(singles) == 2
        # a full group of two: one sample for its bucket, not two
        t = threading.Thread(target=c.submit, args=(a[0], a[1], 2, 1))
        t.start()
        c.submit(a[0], a[1], 2, 0)
        t.join(timeout=10)
        assert sum(len(v) for v in c._served.values()) == 3
        # a single that goes out while the one before it is unanswered
        # queued behind it: no sample; the one before it has its own
        t = threading.Thread(target=c.submit, args=(a[0], a[1], 20, 0))
        t.start()
        while c.counters().get("groups_flushed", 0) < 5:
            time.sleep(0.001)
        c.submit(a[0], a[1], 21, 1)
        assert len(singles) == 2
        release.set()
        t.join(timeout=10)
        assert len(singles) == 3
        for step in range(22, 28):
            c.submit(a[0], a[1], step, 0)
        assert len(singles) == 4
    finally:
        release.set()
        c.close()


def test_shed_bookkeeping_survives_many_threads():
    """More submitters than cores under a short switch interval: every
    request comes back and the flush reasons add up."""
    n_threads, n_steps = 24, 25
    c = RequestCoalescer(_resolve_all, max_group=4, window_s=0.0005)
    acts, labels = np.zeros((1, 4), np.float32), np.zeros((1,), np.int64)
    errors = []

    def run(i):
        try:
            for step in range(n_steps):
                c.submit(acts, labels, step, i % 12, timeout=30.0)
        except Exception as exc:
            errors.append((i, exc))

    prev = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(prev)
        c.close()
    assert not errors, errors
    counters = c.counters()
    assert counters["requests_coalesced"] == n_threads * n_steps
    _assert_reasons_sum(counters)


def test_transport_stats_counters_merge_and_summary():
    a, b = TransportStats(), TransportStats()
    a.incr("groups_flushed")
    a.incr("requests_coalesced", 3)
    b.incr("groups_flushed", 2)
    m = TransportStats.merged([a, b])
    assert m.counters["groups_flushed"] == 3
    assert m.counters["requests_coalesced"] == 3
    assert a.summary()["groups_flushed"] == 1


# --------------------------------------------------------------------- #
# integration: ServerRuntime group dispatch
# --------------------------------------------------------------------- #

def test_coalesce_max_1_is_the_serialized_path_bit_for_bit():
    """The pinned degenerate case: coalesce_max=1 never builds the
    coalescer, so the loss series is IDENTICAL (not merely close) to a
    server constructed without the knob."""
    losses = {}
    for name, kwargs in [("default", {}), ("max1", {"coalesce_max": 1})]:
        cfg, plan, server = make_server(**kwargs)
        if name == "max1":
            assert server._coalescer is None
        client = SplitClientTrainer(plan, cfg, jax.random.PRNGKey(1),
                                    LocalTransport(server))
        losses[name] = [client.train_step(*batch(s), step=s)
                        for s in range(4)]
        server.close()
    np.testing.assert_array_equal(losses["default"], losses["max1"])


def test_window_flush_of_one_matches_serialized():
    """A sequential client against a coalescing server only ever forms
    groups of one (window flushes); the group-of-one math must reproduce
    the serialized loss series within f32 tolerance."""
    series = {}
    for name, cmax in [("serialized", 1), ("coalesced", 4)]:
        cfg, plan, server = make_server(coalesce_max=cmax, window_ms=5.0)
        client = SplitClientTrainer(plan, cfg, jax.random.PRNGKey(1),
                                    LocalTransport(server))
        series[name] = [client.train_step(*batch(s), step=s)
                        for s in range(6)]
        if cmax > 1:
            c = server.health()["coalescing"]
            assert c["groups_flushed"] == 6
            assert c["flush_window"] == 6
            assert c["mean_occupancy"] == pytest.approx(1.0)
        server.close()
    np.testing.assert_allclose(series["coalesced"], series["serialized"],
                               rtol=0, atol=1e-4)


def test_concurrent_clients_form_groups_and_health_reports_counters():
    n_clients, n_steps = 4, 5
    cfg, plan, server = make_server(coalesce_max=n_clients, window_ms=500.0,
                                    n_clients=n_clients)
    clients = [
        SplitClientTrainer(plan, cfg, jax.random.fold_in(
            jax.random.PRNGKey(0), i), LocalTransport(server), client_id=i)
        for i in range(n_clients)
    ]
    barrier = threading.Barrier(n_clients)
    errors = []

    def run(i):
        try:
            data = batch(100 + i)
            for s in range(n_steps):
                barrier.wait(timeout=60)  # arrive together: full groups
                loss = clients[i].train_step(*data, step=s)
                assert np.isfinite(loss)
        except Exception as exc:  # propagate to the main thread
            errors.append((i, exc))

    threads = [threading.Thread(target=run, args=(i,))
               for i in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors, errors
    assert server._last_step == {i: n_steps - 1 for i in range(n_clients)}

    c = server.health()["coalescing"]
    assert c["coalesce_max"] == n_clients
    assert c["requests_coalesced"] == n_clients * n_steps
    # barrier-released arrivals coalesce well above the 2.0 the bench
    # leg polices; exact grouping is scheduler-dependent
    assert c["mean_occupancy"] >= 2.0
    _assert_reasons_sum(c)
    # one padded pow2 shape (4*BATCH=32) -> one compile
    assert c["compile_count"] == 1
    server.close()


def test_replayed_step_served_from_cache_without_poisoning_the_group():
    """A duplicate delivery of an applied step is resolved from the
    replay cache (exactly-once: the ORIGINAL step-0 loss comes back even
    though the retry carries different batch data — the server's answer
    to a step is whatever its first apply produced), never enters the
    batch, and its groupmate's fresh step still goes through."""
    cfg, plan, server = make_server(coalesce_max=2, window_ms=500.0,
                                    n_clients=2, strict=True)
    clients = [
        SplitClientTrainer(plan, cfg, jax.random.PRNGKey(i),
                           LocalTransport(server), client_id=i)
        for i in range(2)
    ]
    orig = clients[0].train_step(*batch(0), step=0)  # window flush of one

    barrier = threading.Barrier(2)
    out = {}

    def replay():
        barrier.wait(timeout=30)
        out["replay"] = clients[0].train_step(*batch(1), step=0)

    def fresh():
        barrier.wait(timeout=30)
        out["fresh"] = clients[1].train_step(*batch(2), step=0)

    threads = [threading.Thread(target=replay),
               threading.Thread(target=fresh)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert out.get("replay") == orig  # cached first-apply reply, verbatim
    assert server.replay.hits >= 1
    assert np.isfinite(out.get("fresh"))
    assert server._last_step == {0: 0, 1: 0}
    server.close()


def test_stale_step_below_replay_window_still_409s_in_group():
    """Genuinely stale replays — steps the cache has evicted (or never
    saw) — keep the strict-step 409 at dispatch-admission."""
    cfg, plan, server = make_server(coalesce_max=2, window_ms=2.0,
                                    n_clients=1, strict=True)
    client = SplitClientTrainer(plan, cfg, jax.random.PRNGKey(0),
                                LocalTransport(server))
    for s in range(1, server.replay.window + 3):
        client.train_step(*batch(s), step=s)
    with pytest.raises(ProtocolError):
        client.train_step(*batch(0), step=0)  # never applied, below window
    server.close()


def test_out_of_order_steps_with_strict_steps_false():
    """The pipelined-client contract (strict_steps=False) is unchanged
    under coalescing: out-of-order steps are absorbed and the
    acknowledged step never regresses."""
    cfg, plan, server = make_server(coalesce_max=4, window_ms=5.0,
                                    strict=False)
    client = SplitClientTrainer(plan, cfg, jax.random.PRNGKey(1),
                                LocalTransport(server))
    for s in [5, 2, 7, 3]:
        assert np.isfinite(client.train_step(*batch(s), step=s))
    assert server._last_step == {0: 7}
    server.close()


def test_coalesce_requires_split_mode():
    cfg = Config(mode="federated", batch_size=BATCH, num_clients=2)
    plan = get_plan(mode="federated")
    sample = np.zeros((BATCH, 28, 28, 1), np.float32)
    with pytest.raises(ValueError, match="split-mode only"):
        ServerRuntime(plan, cfg, jax.random.PRNGKey(0), sample,
                      coalesce_max=2)


# --------------------------------------------------------------------- #
# the concurrent runner and the HTTP wire
# --------------------------------------------------------------------- #

def test_concurrent_runner_against_coalescing_server():
    n_clients = 4
    cfg, plan, server = make_server(coalesce_max=n_clients, window_ms=200.0,
                                    n_clients=n_clients)
    runner = MultiClientSplitRunner(
        plan, cfg, jax.random.PRNGKey(0),
        transport_factory=lambda i: LocalTransport(server),
        num_clients=n_clients, concurrent=True)
    data = [batch(10 + i) for i in range(n_clients)]
    for _ in range(3):
        losses = runner.train_round(data)
        assert len(losses) == n_clients
        assert all(np.isfinite(l) for l in losses)
    assert server._last_step == {i: 2 for i in range(n_clients)}
    assert server.health()["coalescing"]["mean_occupancy"] > 1.0
    runner.close()
    server.close()


def test_round_robin_runner_stays_default_and_poolless():
    cfg, plan, server = make_server()
    runner = MultiClientSplitRunner(
        plan, cfg, jax.random.PRNGKey(0),
        transport_factory=lambda i: LocalTransport(server),
        num_clients=1)
    assert runner.concurrent is False
    runner.train_round([batch(0)])
    assert runner._pool is None  # serialized rounds never build a pool
    runner.close()
    server.close()


def test_http_concurrent_handler_threads_coalesce():
    """The real wire: ThreadingHTTPServer handler threads block inside
    split_step while the flusher groups them — end-to-end over loopback
    sockets, counters visible through /health."""
    from split_learning_tpu.transport.http import (
        HttpTransport, SplitHTTPServer)

    n_clients = 2
    cfg, plan, runtime = make_server(coalesce_max=n_clients,
                                     window_ms=500.0, n_clients=n_clients)
    server = SplitHTTPServer(runtime).start()
    transports = [HttpTransport(server.url) for _ in range(n_clients)]
    try:
        clients = [
            SplitClientTrainer(plan, cfg, jax.random.PRNGKey(i),
                               transports[i], client_id=i)
            for i in range(n_clients)
        ]
        barrier = threading.Barrier(n_clients)
        errors, losses = [], {}

        def run(i):
            try:
                data = batch(20 + i)
                for s in range(2):
                    barrier.wait(timeout=60)
                    losses[(i, s)] = clients[i].train_step(*data, step=s)
            except Exception as exc:
                errors.append((i, exc))

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not errors, errors
        assert all(np.isfinite(l) for l in losses.values())
        h = transports[0].health()
        assert h["coalescing"]["requests_coalesced"] == n_clients * 2
        assert h["coalescing"]["mean_occupancy"] >= 1.0
    finally:
        for tr in transports:
            tr.close()
        server.stop()
        runtime.close()
