"""Decoupled backward / 2BP (PR 10): the reply path returns the
cut-layer gradient immediately while the server weight update drains
off the critical path, batched up to ``apply_lag``.

Pins, in order: lag=0 is bit-identical to the legacy fused program;
``--decouple-bwd`` off leaves the PR 9 tree untouched (no decoupled
programs, no new spans, no new counters); the queue depth never exceeds
``apply_lag`` and every flush barrier catches the state up; a replayed
duplicate never re-enqueues an apply; a coalesced group's replies land
before its (still queued) weight update; a checkpoint taken mid-lag
round-trips to the same continuation trajectory; and both new jitted
programs are recompile-free at steady state."""

import jax
import numpy as np

from split_learning_tpu import obs
from split_learning_tpu.models import get_plan
from split_learning_tpu.obs import dispatch_debug
from split_learning_tpu.runtime import ServerRuntime, SplitClientTrainer
from split_learning_tpu.runtime.multi_client import MultiClientSplitRunner
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


def _series(steps=5, **kw):
    cfg, plan, server = _server(**kw)
    client = SplitClientTrainer(plan, cfg, jax.random.PRNGKey(0),
                                LocalTransport(server))
    try:
        return [client.train_step(*_batch(i), i) for i in range(steps)], \
            server
    finally:
        server.close()


# ---------------------------------------------------------------------- #
# numerics: lag=0 bit-identity, default-off pin
# ---------------------------------------------------------------------- #

def test_lag0_bit_identical_to_legacy():
    """Splitting the fused value_and_grad into reply + immediate apply
    cannot change numerics: with apply_lag=0 the update still lands
    inside the same lock-held window, in the same order, from the same
    params — the loss series must match bit for bit."""
    legacy, _ = _series()
    lag0, srv0 = _series(decouple_bwd=True, apply_lag=0)
    assert legacy == lag0
    # and the replies really went through the decoupled machinery
    dec = srv0.health()["decoupled_bwd"]
    assert dec["deferred_enqueued"] == 5
    assert dec["deferred_applied"] == 5
    assert dec["deferred_apply_depth"] == 0


def test_lag2_ends_within_the_nats_budget_of_coupled():
    """Staleness perturbs a trajectory that is going somewhere, it does
    not derail it. The regime 2BP is for: an LM head over a vocabulary
    much wider than the model, whose weight gradient and optimizer step
    dominate the server step. On four fixed batches cycled (the loss
    descends; fresh noise every step would random-walk the comparison)
    a lag-2 server's last cycle ends within 0.1 nats of the coupled
    server's (the old reply_latency_2bp leg allowed 0.35; 0.001 here)."""
    vocab, steps = 2048, 16
    plan = get_plan(model="transformer", mode="split", vocab=vocab,
                    d_model=64, num_heads=4, client_depth=1,
                    server_depth=1, lm=True)
    cfg = Config(mode="split", model="transformer", batch_size=BATCH)
    rs = np.random.RandomState(0)
    x = rs.randint(0, vocab, (4, BATCH, 16)).astype(np.int32)
    y = rs.randint(0, vocab, (4, BATCH, 16)).astype(np.int32)

    def series(**kw):
        server = ServerRuntime(plan, cfg, jax.random.PRNGKey(0), x[0], **kw)
        client = SplitClientTrainer(plan, cfg, jax.random.PRNGKey(1),
                                    LocalTransport(server))
        try:
            return [client.train_step(x[i % 4], y[i % 4], i)
                    for i in range(steps)]
        finally:
            server.close()

    coupled = series()
    lag2 = series(decouple_bwd=True, apply_lag=2)
    assert np.mean(coupled[-4:]) < np.mean(coupled[:4]) - 0.1  # descends
    assert lag2 != coupled  # the lag really moved the weights a step saw
    assert abs(np.mean(lag2[-4:]) - np.mean(coupled[-4:])) <= 0.1


def test_default_off_is_the_untouched_legacy_path():
    """--decouple-bwd off must leave the PR 9 tree bit-for-bit alone:
    no decoupled programs compiled, no deferred queue, no reply_grad /
    deferred_apply spans traced, no deferred counters exported."""
    cfg, plan, server = _server()
    client = SplitClientTrainer(plan, cfg, jax.random.PRNGKey(0),
                                LocalTransport(server))
    try:
        assert server.decouple_bwd is False
        assert server._deferred is None
        assert not hasattr(server, "_reply_step")
        assert not hasattr(server, "_deferred_apply")
        client.train_step(*_batch(0), 0)
        tr = obs.enable()
        try:
            client.train_step(*_batch(1), 1)
        finally:
            obs.disable()
        names = {s["name"] for s in tr.spans()}
        assert "reply_grad" not in names
        assert "deferred_apply" not in names
        snap = server.metrics()
        assert "decoupled_bwd" not in server.health()
        assert not any(k.startswith("deferred_") for k in snap["counters"])
        assert server.flush_deferred() == 0  # barrier no-ops when coupled
    finally:
        server.close()


def test_ctor_validation():
    import pytest
    with pytest.raises(ValueError, match="apply_lag"):
        _server(decouple_bwd=True, apply_lag=-1)
    with pytest.raises(ValueError, match="decouple_bwd"):
        _server(apply_lag=2)


# ---------------------------------------------------------------------- #
# staleness bound + flush barriers
# ---------------------------------------------------------------------- #

def test_lag_bounds_queue_depth_and_flush_catches_up():
    """The staleness invariant: after every reply the queue holds at
    most apply_lag updates (step t forwards with weights from t-k,
    k <= lag), and export_state drains everything before handing the
    state out."""
    lag = 2
    cfg, plan, server = _server(decouple_bwd=True, apply_lag=lag)
    client = SplitClientTrainer(plan, cfg, jax.random.PRNGKey(0),
                                LocalTransport(server))
    try:
        tr = obs.enable()
        try:
            for i in range(5):
                client.train_step(*_batch(i), i)
                dec = server.health()["decoupled_bwd"]
                assert dec["deferred_apply_depth"] == min(i + 1, lag)
                assert (dec["deferred_enqueued"]
                        - dec["deferred_applied"]) <= lag
        finally:
            obs.disable()
        # traced runs feed the reply/apply histograms (the
        # zero-overhead-off contract keeps them empty untraced):
        # reply_grad saw every step, deferred_apply only the drained ones
        snap = server.metrics()
        assert snap["histograms"]["reply_grad"]["count"] == 5
        assert snap["histograms"]["deferred_apply"]["count"] == 3
        names = [s["name"] for s in tr.spans()]
        assert names.count("reply_grad") == 5
        assert names.count("deferred_apply") == 3
        state = server.export_state()
        dec = server.health()["decoupled_bwd"]
        assert dec["deferred_apply_depth"] == 0
        assert dec["deferred_applied"] == dec["deferred_enqueued"] == 5
        assert int(state.step) == 5  # every update landed in the state
        # predict is a flush barrier too: after more traffic it reads
        # caught-up params
        client.train_step(*_batch(5), 5)
        assert server.health()["decoupled_bwd"]["deferred_apply_depth"] == 1
        import jax.numpy as jnp
        acts = np.asarray(plan.stages[0].apply(
            client.state.params, jnp.asarray(_batch(0)[0])))
        server.predict(acts)
        assert server.health()["decoupled_bwd"]["deferred_apply_depth"] == 0
    finally:
        server.close()


def test_close_drains_rather_than_drops():
    cfg, plan, server = _server(decouple_bwd=True, apply_lag=3)
    client = SplitClientTrainer(plan, cfg, jax.random.PRNGKey(0),
                                LocalTransport(server))
    for i in range(2):
        client.train_step(*_batch(i), i)
    assert server.health()["decoupled_bwd"]["deferred_apply_depth"] == 2
    server.close()
    dec = server.health()["decoupled_bwd"]
    assert dec["deferred_apply_depth"] == 0
    assert dec["deferred_applied"] == 2  # applied, not discarded


def test_sync_bottoms_flushes_the_server_half():
    """MultiClientSplitRunner.sync_bottoms is a fleet consistency
    barrier: it must drain the shared server's queue before FedAvg'ing
    the bottoms (the satellite fix)."""
    cfg, plan, server = _server(decouple_bwd=True, apply_lag=3)
    runner = MultiClientSplitRunner(
        plan, cfg, jax.random.PRNGKey(1),
        lambda i: LocalTransport(server), num_clients=2)
    try:
        runner.train_round([_batch(0), _batch(1)])
        assert server.health()["decoupled_bwd"]["deferred_apply_depth"] == 2
        runner.sync_bottoms()
        assert server.health()["decoupled_bwd"]["deferred_apply_depth"] == 0
    finally:
        runner.close()
        server.close()


# ---------------------------------------------------------------------- #
# replay: a served duplicate never re-enqueues an apply
# ---------------------------------------------------------------------- #

def test_replay_duplicate_does_not_double_apply():
    cfg, plan, server = _server(decouple_bwd=True, apply_lag=2)
    client = SplitClientTrainer(plan, cfg, jax.random.PRNGKey(0),
                                LocalTransport(server))
    try:
        x, y = _batch(0)
        loss0 = client.train_step(x, y, 0)
        dec = server.health()["decoupled_bwd"]
        assert dec["deferred_enqueued"] == 1
        # the retransmit: same (client, op, step) straight at the
        # server. The replay claim is taken before the payload is even
        # looked at, so the duplicate is served the cached reply — the
        # payload here is deliberately garbage to prove it
        _g_dup, loss_dup = server.split_step(
            np.zeros((1, 1), np.float32), y, 0, 0)
        assert loss_dup == loss0  # served the original reply
        dec = server.health()["decoupled_bwd"]
        assert dec["deferred_enqueued"] == 1  # no second enqueue
        assert server.replay.hits >= 1
    finally:
        server.close()


# ---------------------------------------------------------------------- #
# coalesced groups: replies land before the queued group apply
# ---------------------------------------------------------------------- #

def test_group_reply_before_apply():
    cfg, plan, server = _server(decouple_bwd=True, apply_lag=1,
                                coalesce_max=2)
    runner = MultiClientSplitRunner(
        plan, cfg, jax.random.PRNGKey(1),
        lambda i: LocalTransport(server),
        num_clients=2, concurrent=True)
    try:
        losses = runner.train_round([_batch(0), _batch(1)])
        # both replies are back (finite losses) while the round's group
        # update(s) are still queued: depth == 1 whether the round
        # coalesced into one group or dispatched two (push -> drain
        # keeps exactly lag entries pending)
        assert all(np.isfinite(l) for l in losses)
        dec = server.health()["decoupled_bwd"]
        assert dec["deferred_apply_depth"] == 1
        assert dec["deferred_enqueued"] - dec["deferred_applied"] == 1
        applied = server.flush_deferred()
        assert applied == 1
        assert server.health()["decoupled_bwd"]["deferred_apply_depth"] == 0
        # a second round still trains: the deferred group program is
        # compiled and the state advances
        losses2 = runner.train_round([_batch(2), _batch(3)])
        assert all(np.isfinite(l) for l in losses2)
    finally:
        runner.close()
        server.close()


# ---------------------------------------------------------------------- #
# checkpoint: mid-lag export round-trips
# ---------------------------------------------------------------------- #

def test_checkpoint_mid_lag_round_trips():
    """A checkpoint taken while updates are queued (export_state
    flushes first) must resume to the exact trajectory the original,
    flushed run continues on."""
    def run_a():
        cfg, plan, server = _server(decouple_bwd=True, apply_lag=2)
        client = SplitClientTrainer(plan, cfg, jax.random.PRNGKey(0),
                                    LocalTransport(server))
        try:
            for i in range(3):
                client.train_step(*_batch(i), i)
            server.export_state()  # the mid-lag checkpoint flush
            return [client.train_step(*_batch(i), i) for i in range(3, 6)]
        finally:
            server.close()

    def run_b():
        cfg, plan, server = _server(decouple_bwd=True, apply_lag=2)
        client = SplitClientTrainer(plan, cfg, jax.random.PRNGKey(0),
                                    LocalTransport(server))
        for i in range(3):
            client.train_step(*_batch(i), i)
        tree = server.export_state()
        assert server.health()["decoupled_bwd"]["deferred_apply_depth"] == 0
        server.close()
        # restart: a fresh server adopts the checkpoint; the client's
        # transport is repointed (its own bottom state carries over,
        # exactly the single-party-restart topology of test_checkpoint)
        cfg2, plan2, server2 = _server(decouple_bwd=True, apply_lag=2)
        client.transport.server = server2
        try:
            server2.resume_from(tree, 3)
            return [client.train_step(*_batch(i), i) for i in range(3, 6)]
        finally:
            server2.close()

    assert run_a() == run_b()


# ---------------------------------------------------------------------- #
# dispatch hygiene: both new programs are steady-state recompile free
# ---------------------------------------------------------------------- #

def test_decoupled_programs_steady_state_recompile_free():
    dd = dispatch_debug.tracker()
    g0 = dd.gauges()
    dispatch_debug.force(True)
    try:
        cfg, plan, server = _server(decouple_bwd=True, apply_lag=1)
        client = SplitClientTrainer(plan, cfg, jax.random.PRNGKey(0),
                                    LocalTransport(server))
        try:
            for i in range(5):
                client.train_step(*_batch(i), i)
            server.flush_deferred()
        finally:
            server.close()
    finally:
        dispatch_debug.force(False)
    g1 = dd.gauges()
    assert (g1["steady_state_recompiles"]
            - g0["steady_state_recompiles"]) == 0
