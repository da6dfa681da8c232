"""K-stage MPMD split pipeline (PR 14): StageRuntime parties chained by
the GPipe-microbatched PipelineRunner.

Pins, in order: the M=1 lag=0 chain is bit-identical to driving the
same three hops sequentially by hand (the pipeline machinery adds
threads and queues, never arithmetic); the M=4 microbatched chain stays
within an absolute-nats budget of the M=1 trajectory on the same data
(the 1/M loss-hop scaling reproduces the batch-mean gradient); chaos
dup/drop on the hop wires never double-applies a weight update — the
loss series matches the clean twin bit for bit and the hop counters
still tally exactly once; a mid-run joint checkpoint (client + every
stage, per-stage extras sidecars) round-trips to the same continuation
trajectory; and the ``mpmd_pipeline`` bench leg carries its contract
fields with every gate green."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from split_learning_tpu.models import get_plan
from split_learning_tpu.runtime.checkpoint import (
    extras_valid, read_latest_extras, write_extras)
from split_learning_tpu.runtime.pipeline_runner import PipelineRunner
from split_learning_tpu.runtime.stage import StageRuntime
from split_learning_tpu.runtime.state import (
    apply_grads, make_state, make_tx)
from split_learning_tpu.transport.chaos import ChaosPolicy, ChaosTransport
from split_learning_tpu.transport.local import LocalTransport
from split_learning_tpu.utils import Config

BATCH = 8
SEED = 2


def _cfg(microbatches, batch=BATCH):
    return Config(mode="split", model="split_cnn_chain3",
                  batch_size=batch, num_stages=3,
                  microbatches=microbatches, seed=SEED)


def _chain(microbatches, apply_lag, wrap=None, batch=BATCH):
    """One 3-stage chain: client stage 0 + two in-process StageRuntime
    parties, every party initialized from the same plan-level seed (the
    launch path's convention — no weights ship)."""
    cfg = _cfg(microbatches, batch)
    plan = get_plan(model="split_cnn_chain3", mode="split")
    sample = np.zeros((batch, 28, 28, 1), np.float32)
    stages = [StageRuntime(plan, i, cfg, jax.random.PRNGKey(SEED),
                           sample, microbatches=microbatches,
                           apply_lag=apply_lag)
              for i in (1, 2)]
    transports = [LocalTransport(s) for s in stages]
    if wrap is not None:
        transports = [wrap(t) for t in transports]
    runner = PipelineRunner(plan, cfg, jax.random.PRNGKey(SEED), sample,
                            transports, microbatches=microbatches)
    return runner, stages, plan


def _close(runner, stages):
    runner.close()
    for s in stages:
        s.close()


def _batch(seed, batch=BATCH):
    rs = np.random.RandomState(seed)
    return (rs.randn(batch, 28, 28, 1).astype(np.float32),
            rs.randint(0, 10, batch).astype(np.int64))


# ---------------------------------------------------------------------- #
# numerics: M=1 lag=0 bit-identity, M=4 staleness budget
# ---------------------------------------------------------------------- #

def test_m1_lag0_bit_identical_to_sequential_drive():
    """With one microbatch and no apply lag every hop blocks on the
    previous one, so the worker threads and queues are pure plumbing:
    the piped loss series must equal, bit for bit, driving identically
    seeded StageRuntimes by hand through the same three hops with the
    runner's own stage-0 arithmetic."""
    steps = 4
    runner, stages, _ = _chain(1, 0)
    try:
        piped = [runner.step(*_batch(i), i) for i in range(steps)]
    finally:
        _close(runner, stages)

    cfg = _cfg(1)
    plan = get_plan(model="split_cnn_chain3", mode="split")
    sample = np.zeros((BATCH, 28, 28, 1), np.float32)
    s1, s2 = (StageRuntime(plan, i, cfg, jax.random.PRNGKey(SEED),
                           sample, microbatches=1, apply_lag=0)
              for i in (1, 2))
    stage0 = plan.stages[0]
    tx = make_tx(cfg)
    state = make_state(
        plan.init(jax.random.PRNGKey(SEED), jnp.asarray(sample))[0], tx)

    # the runner's stage-0 programs, re-jitted from the same jaxprs
    fwd0 = jax.jit(lambda p, x: stage0.apply(p, x))

    def bwd_acc_fn(params, x, g, acc):
        _, vjp = jax.vjp(lambda p: stage0.apply(p, x), params)
        (gp,) = vjp(g)
        return jax.tree_util.tree_map(jnp.add, acc, gp)

    bwd_acc = jax.jit(bwd_acc_fn)
    zeros = jax.jit(
        lambda p: jax.tree_util.tree_map(jnp.zeros_like, p))

    manual = []
    try:
        for step in range(steps):
            x, y = _batch(step)
            x_dev = jnp.asarray(x)
            y0 = np.asarray(fwd0(state.params, x_dev))
            y1 = s1.hop_forward(y0, step, 0, 0)
            g1, loss = s2.hop_loss(y1, y, step, 0, 0)
            g0 = s1.hop_backward(g1, step, 0, 0)
            acc = bwd_acc(state.params, x_dev, jnp.asarray(g0),
                          zeros(state.params))
            state = jax.jit(
                lambda s, g: apply_grads(tx, s, g))(state, acc)
            manual.append(float(np.mean([loss])))
    finally:
        s1.close()
        s2.close()
    assert piped == manual
    for s in stages:
        ctr = s.counters()
        assert ctr["deferred_enqueued"] == steps
        assert ctr["deferred_applied"] == steps
        assert ctr["deferred_apply_depth"] == 0


def test_m4_stays_within_nats_budget_of_m1():
    """GPipe microbatching re-associates the gradient sum (M
    per-microbatch vjp contributions, 1/M-scaled at the loss hop) and
    lag=1 defers each stage's apply one step: same trajectory up to
    float noise and bounded staleness. Absolute-nats budget on the
    end-of-run window, same gate style as the bench leg."""
    steps = 16
    # the bench leg's converging regime: 4 fixed batches cycled at
    # batch 32 — trajectory comparisons on an oscillating tiny-batch
    # series would measure chaos, not the pipeline
    rs = np.random.RandomState(0)
    batches = [(rs.rand(32, 28, 28, 1).astype(np.float32),
                rs.randint(0, 10, 32).astype(np.int64))
               for _ in range(4)]
    runner1, stages1, _ = _chain(1, 0, batch=32)
    try:
        m1 = [runner1.step(*batches[i % 4], i) for i in range(steps)]
    finally:
        _close(runner1, stages1)
    runner4, stages4, _ = _chain(4, 1, batch=32)
    try:
        m4 = [runner4.step(*batches[i % 4], i) for i in range(steps)]
    finally:
        _close(runner4, stages4)
    gap = abs(float(np.mean(m1[-4:])) - float(np.mean(m4[-4:])))
    assert gap <= 0.35, (gap, m1, m4)


def test_chain_tracks_the_one_cut_split_and_tallies_every_hop():
    """split_cnn_chain3 re-partitions the reference CNN's arithmetic, so
    the M=4, lag-1 chain must optimize the trajectory of the classic
    1-cut ServerRuntime split: on four fixed batches cycled its last
    cycle ends within 0.35 nats of the split's (0.02-0.14 over six
    seeds and two batch sizes when this was written). And on a clean
    wire no hop is lost or delivered twice: steps x M forward and
    backward hops at stage 1, steps x M loss hops at stage 2 (the old
    mpmd_pipeline leg's parity and tally gates)."""
    from split_learning_tpu.runtime import ServerRuntime, SplitClientTrainer
    steps, M, batch = 16, 4, 16
    rs = np.random.RandomState(0)
    x = rs.rand(4, batch, 28, 28, 1).astype(np.float32)
    y = rs.randint(0, 10, (4, batch)).astype(np.int64)
    runner, stages, _ = _chain(M, 1, batch=batch)
    try:
        chain = [runner.step(x[i % 4], y[i % 4], i) for i in range(steps)]
        tally = [s.counters() for s in stages]
    finally:
        _close(runner, stages)

    cfg = Config(mode="split", model="split_cnn", batch_size=batch,
                 seed=SEED)
    plan = get_plan(model="split_cnn", mode="split")
    server = ServerRuntime(plan, cfg, jax.random.PRNGKey(SEED), x[0])
    client = SplitClientTrainer(plan, cfg, jax.random.PRNGKey(SEED),
                                LocalTransport(server))
    try:
        one_cut = [client.train_step(x[i % 4], y[i % 4], i)
                   for i in range(steps)]
    finally:
        server.close()

    assert abs(np.mean(chain[-4:]) - np.mean(one_cut[-4:])) <= 0.35
    assert (tally[0]["hop_fwd"], tally[0]["hop_bwd"],
            tally[1]["hop_loss"]) == (steps * M,) * 3


# ---------------------------------------------------------------------- #
# chaos on the hop wires: exactly-once end to end
# ---------------------------------------------------------------------- #

def test_hop_chaos_never_double_applies():
    """Dup and dropped-response faults on both hop wires: the replay
    claims make every redelivery serve the one materialized reply, so
    the loss series is BIT-identical to the clean twin, the hop
    counters tally exactly rounds x M per stage/direction, and no
    stage enqueues more than one weight update per step."""
    steps, M = 6, 2
    runner_c, stages_c, _ = _chain(M, 1)
    try:
        clean = [runner_c.step(*_batch(i), i) for i in range(steps)]
    finally:
        _close(runner_c, stages_c)

    policy = ChaosPolicy("dup=0.3,drop_resp=0.3", seed=5)
    runner_x, stages_x, _ = _chain(
        M, 1, wrap=lambda t: ChaosTransport(t, policy))
    try:
        chaotic = [runner_x.step(*_batch(i), i) for i in range(steps)]
        assert chaotic == clean
        assert sum(policy.injected.values()) > 0
        replay_hits = 0
        for s in stages_x:
            ctr = s.counters()
            for op in ("hop_fwd", "hop_bwd") if not s.is_last \
                    else ("hop_loss",):
                assert ctr[op] == steps * M, (s.party, op, ctr)
            assert ctr["deferred_enqueued"] == steps
            replay_hits += ctr["replay_hits"]
        assert replay_hits > 0  # the faults really exercised the cache
    finally:
        _close(runner_x, stages_x)


# ---------------------------------------------------------------------- #
# durability: joint checkpoint + per-stage extras round trip
# ---------------------------------------------------------------------- #

def test_mid_pipeline_checkpoint_roundtrips(tmp_path):
    """The launch path's save_chain discipline, driven directly: after
    4 steps snapshot the client state and every stage's export_state
    (which flushes that stage's deferred queue first) plus a per-stage
    extras sidecar under stage<i>/; a fresh identically-seeded chain
    that adopts the snapshot continues on the same loss trajectory bit
    for bit."""
    M, lag, ckpt_step = 2, 1, 4
    runner_a, stages_a, _ = _chain(M, lag)
    try:
        for i in range(ckpt_step):
            runner_a.step(*_batch(i), i)
        tree = {"client": runner_a.state}
        for s in stages_a:
            tree[s.party] = s.export_state()
            assert s.counters()["deferred_apply_depth"] == 0  # flushed
            d = tmp_path / s.party
            os.makedirs(d, exist_ok=True)
            write_extras(str(d), s.export_runtime_extras(ckpt_step))
        cont_a = [runner_a.step(*_batch(i), i)
                  for i in range(ckpt_step, ckpt_step + 3)]
    finally:
        _close(runner_a, stages_a)

    runner_b, stages_b, _ = _chain(M, lag)
    try:
        runner_b.state = tree["client"]
        runner_b.steps_done = ckpt_step
        for s in stages_b:
            extras = read_latest_extras(str(tmp_path / s.party),
                                        step=ckpt_step)
            assert extras is not None and extras_valid(extras)
            s.resume_from(tree[s.party], ckpt_step, extras=extras)
        cont_b = [runner_b.step(*_batch(i), i)
                  for i in range(ckpt_step, ckpt_step + 3)]
    finally:
        _close(runner_b, stages_b)
    assert cont_a == cont_b
