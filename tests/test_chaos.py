"""Chaos wire, replay cache, and circuit breaker: deterministic fault
injection (transport/chaos.py), exactly-once recovery of a response lost
after server apply (the desync the reference cannot survive), breaker
state machine, and backoff schedules. All fast — no real sleeps, tiny
models — so CI can run this file as the fault-tolerance smoke."""

import threading

import jax
import numpy as np
import pytest

from split_learning_tpu.models import get_plan
from split_learning_tpu.runtime import (
    ReplayCache, ServerRuntime, SplitClientTrainer)
from split_learning_tpu.runtime.breaker import (
    CLOSED, HALF_OPEN, OPEN, CircuitBreaker)
from split_learning_tpu.runtime.client import FailurePolicy
from split_learning_tpu.transport import (
    ChaosPolicy, ChaosTransport, LocalTransport, TransportError)
from split_learning_tpu.transport.base import backoff_delays
from split_learning_tpu.transport.chaos import parse_chaos_spec
from split_learning_tpu.transport.codec import TopK8EF, topk8_compress
from split_learning_tpu.transport.http import HttpTransport, SplitHTTPServer
from split_learning_tpu.utils import Config

BATCH = 8


def _runtime():
    cfg = Config(mode="split", batch_size=BATCH)
    plan = get_plan(mode="split")
    sample = np.zeros((BATCH, 28, 28, 1), np.float32)
    return cfg, plan, ServerRuntime(plan, cfg, jax.random.PRNGKey(2),
                                    sample)


# ---------------------------------------------------------------------- #
# spec grammar + schedule determinism
# ---------------------------------------------------------------------- #

def test_parse_chaos_spec_grammar():
    f = parse_chaos_spec("drop_resp=0.1,dup,delay=0.02:250")
    assert list(f) == ["drop_resp", "dup", "delay"]  # order preserved
    assert f["drop_resp"] == (0.1, 50.0)   # default delay arg unused
    assert f["dup"][0] == 0.05             # DEFAULT_RATE
    assert f["delay"] == (0.02, 250.0)

    with pytest.raises(ValueError, match="unknown chaos kind"):
        parse_chaos_spec("drop_response=0.1")
    with pytest.raises(ValueError, match="bad chaos rate"):
        parse_chaos_spec("dup=lots")
    with pytest.raises(ValueError, match="in \\[0, 1\\]"):
        parse_chaos_spec("dup=1.5")
    with pytest.raises(ValueError, match="sum to > 1"):
        parse_chaos_spec("dup=0.6,drop_resp=0.6")


def test_chaos_policy_schedule_is_seeded_and_bounded():
    """Same (spec, seed) = the same faults at the same (path, step,
    attempt) keys — a chaotic run is exactly reproducible — and every
    key goes clean at attempt >= max_faults_per_key."""
    spec = "drop_resp=0.15,dup=0.1,http500=0.05"
    keys = [(p, s, a) for p in ("/forward_pass", "/u_backward")
            for s in range(60) for a in range(3)]
    a = ChaosPolicy(spec, seed=7)
    b = ChaosPolicy(spec, seed=7)
    sched_a = [a.draw(*k) for k in keys]
    assert sched_a == [b.draw(*k) for k in keys]
    assert any(f is not None for f in sched_a)
    assert sched_a != [ChaosPolicy(spec, seed=8).draw(*k) for k in keys]
    # bounded chaos: attempt 2 is clean for every key (max_faults=2),
    # so RETRY with max_retries >= 2 always completes the step
    assert all(a.draw(p, s, 2) is None
               for p in ("/forward_pass",) for s in range(200))


def test_chaos_off_path_is_bitwise_legacy():
    """A zero-rate policy injects nothing and perturbs nothing: a
    chaos-wrapped twin trains bit-identically to the bare transport
    (and the CLI never even constructs the wrapper without --chaos)."""
    runs = {}
    for wrap in (False, True):
        cfg, plan, runtime = _runtime()
        transport = LocalTransport(runtime)
        if wrap:
            transport = ChaosTransport(
                transport, ChaosPolicy("drop_resp=0.0", seed=3))
        client = SplitClientTrainer(plan, cfg, jax.random.PRNGKey(2),
                                    transport)
        rs = np.random.RandomState(5)
        losses = []
        for step in range(5):
            x = rs.randn(BATCH, 28, 28, 1).astype(np.float32)
            y = rs.randint(0, 10, (BATCH,)).astype(np.int64)
            losses.append(client.train_step(x, y, step))
        runs[wrap] = losses
    assert runs[True] == runs[False]


# ---------------------------------------------------------------------- #
# the killer case: response lost AFTER the server applied the update
# ---------------------------------------------------------------------- #

def test_lost_response_recovered_bit_identical_over_http():
    """Regression for the reference's silent desync: the server applies
    step N, the reply dies on the wire, the client retries N. Without
    the replay cache the retry would either 409 (strict steps) or apply
    N twice; with it, the retry is served the *original bytes*."""
    cfg, plan, runtime = _runtime()
    # drop_resp=1.0 with max_faults_per_key=2: attempts 0 and 1 lose
    # the reply (after apply/cache), attempt 2 is clean
    server = SplitHTTPServer(
        runtime, chaos=ChaosPolicy("drop_resp=1.0", seed=0)).start()
    transport = HttpTransport(server.url)
    # a fault-free twin: what the bytes *should* decode to
    cfg2, plan2, runtime2 = _runtime()
    clean_srv = SplitHTTPServer(runtime2).start()
    clean = HttpTransport(clean_srv.url)
    try:
        rs = np.random.RandomState(4)
        acts = rs.randn(BATCH, 26, 26, 32).astype(np.float32)
        labels = rs.randint(0, 10, (BATCH,)).astype(np.int64)
        with pytest.raises(TransportError):
            transport.split_step(acts, labels, 0)   # applied, reply lost
        assert runtime.health()["step"] == 0        # it DID apply
        with pytest.raises(TransportError):
            transport.split_step(acts, labels, 0)   # cached reply lost too
        g, loss = transport.split_step(acts, labels, 0)  # clean attempt
        g_ref, loss_ref = clean.split_step(acts, labels, 0)
        np.testing.assert_array_equal(g, g_ref)
        assert loss == loss_ref
        assert runtime.health()["step"] == 0        # applied exactly once
        assert runtime.replay.body_hits >= 1        # original bytes reused
    finally:
        transport.close()
        clean.close()
        server.stop()
        clean_srv.stop()


def test_trainer_retry_survives_server_chaos_without_losing_batches():
    """Satellite regression: SplitClientTrainer on RETRY + a chaotic
    server = zero dropped batches and finite losses, end to end."""
    cfg, plan, runtime = _runtime()
    server = SplitHTTPServer(
        runtime,
        chaos=ChaosPolicy("drop_resp=0.3,http500=0.2", seed=11)).start()
    transport = HttpTransport(server.url)
    try:
        client = SplitClientTrainer(plan, cfg, jax.random.PRNGKey(2),
                                    transport,
                                    failure_policy=FailurePolicy.RETRY,
                                    max_retries=3, retry_backoff=0.0)
        rs = np.random.RandomState(6)
        for step in range(12):
            x = rs.randn(BATCH, 28, 28, 1).astype(np.float32)
            y = rs.randint(0, 10, (BATCH,)).astype(np.int64)
            assert np.isfinite(client.train_step(x, y, step))
        assert client.dropped_batches == 0
        assert runtime.health()["step"] == 11
        assert sum(server.chaos.injected.values()) > 0
    finally:
        transport.close()
        server.stop()


def test_chaotic_wire_with_retry_reproduces_the_clean_loss_series():
    """Exactly-once means a fault schedule changes the wire, never the
    math: the same seeded stream trained over a clean wire and over one
    that loses replies, duplicates deliveries and 5xx-es, with the
    client on the bounded-retry policy, gives the same loss at every
    step, with no batch dropped, faults really injected and the replay
    cache really engaged (the old chaos_soak leg's gates)."""
    rs = np.random.RandomState(8)
    data = [(rs.randn(BATCH, 28, 28, 1).astype(np.float32),
             rs.randint(0, 10, BATCH).astype(np.int64)) for _ in range(30)]
    series = {}
    for run in ("clean", "chaos"):
        cfg, plan, runtime = _runtime()
        transport = LocalTransport(runtime)
        if run == "chaos":
            policy = ChaosPolicy("drop_resp=0.10,dup=0.05,http500=0.05",
                                 seed=1234)
            transport = ChaosTransport(transport, policy)
        client = SplitClientTrainer(plan, cfg, jax.random.PRNGKey(0),
                                    transport,
                                    failure_policy=FailurePolicy.RETRY,
                                    max_retries=3, retry_backoff=0.0)
        try:
            series[run] = [client.train_step(x, y, i)
                           for i, (x, y) in enumerate(data)]
            assert client.dropped_batches == 0
        finally:
            runtime.close()
    assert sum(policy.injected.values()) > 0
    assert runtime.replay.counters()["replay_hits"] > 0
    assert series["chaos"] == series["clean"]


def test_client_side_dup_served_from_replay_cache():
    """ChaosTransport dup delivers twice; the duplicate must come back
    from the server's replay cache bit-equal, with one apply."""
    cfg, plan, runtime = _runtime()
    transport = ChaosTransport(LocalTransport(runtime),
                               ChaosPolicy("dup=1.0", seed=0))
    g, loss = transport.split_step(
        np.ones((BATCH, 26, 26, 32), np.float32),
        np.zeros((BATCH,), np.int64), 0)
    assert np.all(np.isfinite(g))
    assert runtime.health()["step"] == 0
    assert runtime.replay.hits >= 1
    assert transport.stats.counters.get("chaos_dup") == 1


# ---------------------------------------------------------------------- #
# replay cache unit behaviour
# ---------------------------------------------------------------------- #

def test_replay_cache_first_apply_wins_and_evicts():
    rc = ReplayCache(window=2, max_total=64)
    rc.put(0, "split_step", 1, "first")
    rc.put(0, "split_step", 1, "second")          # duplicate apply race
    assert rc.get(0, "split_step", 1) == "first"  # original wins
    rc.attach_body(0, "split_step", 1, b"bytes")
    rc.attach_body(0, "split_step", 1, b"other")  # body is set-once too
    assert rc.get_body(0, "split_step", 1) == b"bytes"
    rc.put(0, "split_step", 2, "r2")
    rc.put(0, "split_step", 3, "r3")              # window=2: evicts step 1
    assert rc.get(0, "split_step", 1) is None
    assert rc.get(1, "split_step", 1) is None     # other client: miss
    c = rc.counters()
    assert c["replay_evictions"] == 1
    assert c["replay_cache_size"] == 2
    rc.clear()
    assert rc.counters()["replay_cache_size"] == 0


# ---------------------------------------------------------------------- #
# breaker + backoff
# ---------------------------------------------------------------------- #

def test_backoff_delays_schedule_and_jitter():
    gen = backoff_delays(0.5, jitter=0.0)
    assert [next(gen) for _ in range(6)] == [0.5, 1.0, 2.0, 4.0, 5.0, 5.0]
    # seeded jitter is deterministic and bounded to [d, d * (1+jitter)]
    g1 = backoff_delays(0.5, jitter=0.5, rng=np.random.RandomState(0))
    g2 = backoff_delays(0.5, jitter=0.5, rng=np.random.RandomState(0))
    d1 = [next(g1) for _ in range(6)]
    assert d1 == [next(g2) for _ in range(6)]
    for base, d in zip([0.5, 1.0, 2.0, 4.0, 5.0, 5.0], d1):
        assert base <= d <= base * 1.5


def test_circuit_breaker_state_machine():
    up = {"ok": True}

    def probe():
        if not up["ok"]:
            raise TransportError("down")
        return {"status": "healthy"}

    slept = []
    br = CircuitBreaker(probe, failure_threshold=3, probe_jitter=0.0,
                        seed=0, sleep=slept.append)
    assert br.state == CLOSED
    br.before_attempt()                      # closed: free pass, no sleep
    assert slept == []
    br.record_failure()
    br.record_failure()
    assert br.state == CLOSED                # below threshold
    br.record_failure()
    assert br.state == OPEN
    assert br.counters["breaker_opened"] == 1
    br.before_attempt()                      # probe succeeds immediately
    assert br.state == HALF_OPEN
    assert br.counters["breaker_probes"] == 1
    assert slept == [0.5]                    # one backoff sleep, no jitter
    br.record_failure()                      # the trial request failed
    assert br.state == OPEN
    assert br.counters["breaker_reopened"] == 1
    br.before_attempt()
    assert br.state == HALF_OPEN
    br.record_success()                      # trial succeeded: re-close
    assert br.state == CLOSED
    assert br.counters["breaker_reclosed"] == 1


def test_circuit_breaker_gives_up_after_max_open_s():
    def dead():
        raise TransportError("down forever")

    br = CircuitBreaker(dead, failure_threshold=1, max_open_s=0.0,
                        probe_jitter=0.0, sleep=lambda _s: None)
    br.record_failure()
    assert br.state == OPEN
    with pytest.raises(TransportError, match="circuit open"):
        br.before_attempt()
    assert br.counters["breaker_probe_failures"] >= 1


# ---------------------------------------------------------------------- #
# EF rollback/replay consistency
# ---------------------------------------------------------------------- #

def test_ef_rollback_then_repack_is_bit_identical():
    """The invariant the HTTP retry path and the server's cached-result
    replay both lean on: rollback restores the exact pre-compress
    residual, so re-packing the same tensor reproduces the same wire
    dict bit for bit — a replayed delivery and a retried send carry
    identical payloads."""
    ef = TopK8EF()
    arr = np.random.RandomState(0).randn(64, 64).astype(np.float32)
    warm = np.random.RandomState(1).randn(64, 64).astype(np.float32)
    ef.compress("k", warm, 0.05)             # leave a non-zero residual
    p1 = ef.compress("k", arr, 0.05)
    ef.rollback("k")
    p2 = ef.compress("k", arr, 0.05)
    assert p1.keys() == p2.keys()
    for key in p1:
        if isinstance(p1[key], np.ndarray):
            np.testing.assert_array_equal(p1[key], p2[key])
        else:
            assert p1[key] == p2[key]
    # and the stateless core is itself deterministic
    d1, r1 = topk8_compress(arr, 0.05)
    d2, r2 = topk8_compress(arr, 0.05)
    np.testing.assert_array_equal(d1["q"], d2["q"])
    np.testing.assert_array_equal(r1, r2)


# ---------------------------------------------------------------------- #
# async dispatch (PR 5): exactly-once across the off-lock window
# ---------------------------------------------------------------------- #

@pytest.mark.parametrize("kind", ["split_step", "coalesced_group",
                                  "2bp_reply"])
def test_duplicate_during_materialization_blocks_on_inflight_future(
        kind, hold_host_gather):
    """Async dispatch opens a window the old cache could not cover: the
    step is applied but its reply is still materializing off the lock.
    A duplicate landing there must block on the in-flight future and be
    served the ONE materialized reply — not 409 (the step is not a
    stale replay), not a second apply and not a second copy. The same
    holds behind a coalesced group's ``_GroupD2H`` latch, which a second
    member waits on meanwhile, and for a 2BP reply, whose weight update
    must enter the deferred queue once."""
    kw = {"split_step": {},
          "coalesced_group": {"coalesce_max": 2, "coalesce_window_ms": 500.0},
          "2bp_reply": {"decouple_bwd": True, "apply_lag": 2}}[kind]
    clients = (0, 1) if kind == "coalesced_group" else (0,)
    cfg = Config(mode="split", batch_size=BATCH)
    plan = get_plan(mode="split")
    sample = np.zeros((BATCH, 28, 28, 1), np.float32)
    runtime = ServerRuntime(plan, cfg, jax.random.PRNGKey(2), sample, **kw)
    rs = np.random.RandomState(0)
    x = rs.randn(BATCH, 26, 26, 32).astype(np.float32)  # cut-layer acts
    y = rs.randint(0, 10, BATCH).astype(np.int64)
    results = {}
    spawn, join, wait_s = (hold_host_gather.spawn, hold_host_gather.join,
                           hold_host_gather.WAIT_S)

    def step(tag, s, c):
        return lambda: results.__setitem__(
            tag, runtime.split_step(x, y, s, client_id=c))

    try:
        join(spawn(*[step(("warm", c), 0, c) for c in clients]))  # compile

        # the duplicate says when it has lost the claim and is about to
        # block on the original's entry, and whether that was pending
        dup_waiting = threading.Event()
        pending = []
        replay_wait = runtime.replay.wait

        def wait(entry, *a, **k):
            pending.append(not entry.done)
            dup_waiting.set()
            return replay_wait(entry, *a, **k)

        runtime.replay.wait = wait
        with hold_host_gather(runtime) as hold:
            originals = spawn(*[step(("orig", c), 1, c) for c in clients])
            assert hold.entered.wait(wait_s)  # applied; the copy is held
            dup = spawn(step("dup", 1, 0))    # duplicate delivery
            assert dup_waiting.wait(wait_s)
            assert dup[0].is_alive() and "dup" not in results
            hold.release.set()
            join(originals + dup)
            copies = hold.calls
    finally:
        runtime.close()

    assert pending == [True]  # it waited on the in-flight future
    np.testing.assert_array_equal(results["dup"][0], results["orig", 0][0])
    assert results["dup"][1] == results["orig", 0][1]  # identical reply
    assert runtime.replay.hits == 1       # served from the future, once
    # one copy of the gradient a serialized reply, two a group (gradient
    # and per-example loss, once for both members): never one more
    assert copies == (2 if kind == "coalesced_group" else 1)
    assert runtime.health()["step"] == 1
    if kind == "2bp_reply":
        # close() drained the queue: warmup + ONE update entered it
        assert runtime._deferred.counters()["deferred_enqueued"] == 2
    assert int(runtime.state.step) == 2   # warmup + ONE apply, not two
