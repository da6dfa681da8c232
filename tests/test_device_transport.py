"""Device-native hop transport + 1F1B schedule (PR 16).

Pins, in order: the schedule math (both schedules share T = M+S-1 ticks
and the ideal bubble; 1F1B's warmup depth is min(S, M)); schedule
validation at the Config and runner layers; the M=1 device chain is
bit-identical to the LocalTransport chain (zero-copy relay adds no
arithmetic); 1F1B is bit-identical to GPipe at M=4 (same params
snapshot per step + microbatch-order accumulation — stronger than the
M=1-only requirement); the zero-copy pin itself — ``hop_host_copies``
stays exactly 0 across the chain and the dispatch watchdog counts no
unexpected D2H and no steady-state recompiles; ppermute parity — the
in-mesh collective path computes the same losses as the meshless relay;
the report/trace surfaces carry the schedule fields; and the SLT115
invariant actually fires on depth-bound and order violations.
"""

import os

import jax
import numpy as np
import pytest

from split_learning_tpu.models import get_plan
from split_learning_tpu.obs import dispatch_debug
from split_learning_tpu.obs import spans
from split_learning_tpu.runtime.pipeline_runner import (
    PipelineRunner, SCHEDULES, bubble_fraction, onefb_warmup,
    pipeline_ticks)
from split_learning_tpu.runtime.stage import StageRuntime
from split_learning_tpu.transport.device import DeviceTransport
from split_learning_tpu.transport.local import LocalTransport
from split_learning_tpu.utils import Config

BATCH = 8
SEED = 2


def _cfg(microbatches, schedule="gpipe"):
    return Config(mode="split", model="split_cnn_chain3",
                  batch_size=BATCH, num_stages=3,
                  microbatches=microbatches, schedule=schedule,
                  seed=SEED)


def _chain(microbatches, schedule="gpipe", transport="device",
           apply_lag=0, mesh=None, devices=(None, None)):
    """One 3-stage chain: client stage 0 + two in-process StageRuntime
    parties, wired by DeviceTransport (device buffers end to end) or
    LocalTransport (the PR-14 host-numpy contract). ``devices`` places
    stages 1 and 2 (None: the backend's first)."""
    cfg = _cfg(microbatches, schedule)
    plan = get_plan(model="split_cnn_chain3", mode="split")
    sample = np.zeros((BATCH, 28, 28, 1), np.float32)
    stages = [StageRuntime(plan, i, cfg, jax.random.PRNGKey(SEED),
                           sample, microbatches=microbatches,
                           apply_lag=apply_lag, mesh=mesh, device=dev)
              for i, dev in zip((1, 2), devices)]
    if transport == "device":
        transports = [DeviceTransport(s, mesh=mesh) for s in stages]
    else:
        transports = [LocalTransport(s) for s in stages]
    runner = PipelineRunner(plan, cfg, jax.random.PRNGKey(SEED), sample,
                            transports, microbatches=microbatches,
                            schedule=schedule)
    return runner, stages, transports


def _close(runner, stages):
    runner.close()
    for s in stages:
        s.close()


def _batch(seed):
    rs = np.random.RandomState(seed)
    return (rs.randn(BATCH, 28, 28, 1).astype(np.float32),
            rs.randint(0, 10, BATCH).astype(np.int64))


def _losses(microbatches, schedule, transport, steps=4, mesh=None):
    runner, stages, _ = _chain(microbatches, schedule, transport,
                               mesh=mesh)
    try:
        return [runner.step(*_batch(i), i) for i in range(steps)]
    finally:
        _close(runner, stages)


# ---------------------------------------------------------------------- #
# schedule math: shared tick count/ideal bubble, 1F1B warmup depth
# ---------------------------------------------------------------------- #

def test_schedule_math():
    """Both schedules drain in T = M+S-1 ticks with ideal bubble
    (S-1)/T — 1F1B reduces in-flight DEPTH (memory), not length; the
    warmup depth is min(S, M)."""
    assert pipeline_ticks(4, 3) == 6
    assert bubble_fraction(4, 3) == pytest.approx(2 / 6)
    assert pipeline_ticks(1, 3) == 3
    assert bubble_fraction(1, 3) == pytest.approx(2 / 3)
    assert onefb_warmup(4, 3) == 3
    assert onefb_warmup(1, 3) == 1
    assert onefb_warmup(8, 3) == 3
    assert onefb_warmup(2, 5) == 2
    assert SCHEDULES == ("gpipe", "1f1b")


def test_schedule_validation():
    with pytest.raises(ValueError, match="schedule"):
        Config(mode="split", schedule="bogus")
    cfg = _cfg(1)
    plan = get_plan(model="split_cnn_chain3", mode="split")
    sample = np.zeros((BATCH, 28, 28, 1), np.float32)
    with pytest.raises(ValueError, match="schedule"):
        PipelineRunner(plan, cfg, jax.random.PRNGKey(SEED), sample,
                       [object(), object()], schedule="bogus")


def test_env_knob_round_trips():
    cfg = Config.from_env(env={"SLT_SCHEDULE": "1f1b"})
    assert cfg.schedule == "1f1b"


# ---------------------------------------------------------------------- #
# numerics: device == local at M=1; 1F1B == GPipe at M=4
# ---------------------------------------------------------------------- #

def test_m1_device_bit_identical_to_local():
    """At M=1 the device chain and the LocalTransport chain run the
    same programs on the same buffers — the zero-copy relay must add no
    arithmetic: loss series identical bit for bit."""
    local = _losses(1, "gpipe", "local")
    device = _losses(1, "gpipe", "device")
    assert device == local


def test_m4_1f1b_bit_identical_to_gpipe():
    """1F1B changes WHEN microbatches enter the wire, never the math:
    every microbatch still sees the same step-start params snapshot and
    cotangents accumulate in microbatch order, so the loss series is
    bit-identical to GPipe at M=4 (stronger than the M=1 contract)."""
    gpipe = _losses(4, "gpipe", "device")
    onefb = _losses(4, "1f1b", "device")
    assert onefb == gpipe


def test_m4_1f1b_device_matches_local_gpipe():
    """Cross product: the device-native 1F1B chain lands on the exact
    trajectory of the PR-14 LocalTransport GPipe chain."""
    assert _losses(4, "1f1b", "device") == _losses(4, "gpipe", "local")


# ---------------------------------------------------------------------- #
# the zero-copy pin: hop_host_copies == 0, watchdog-clean steady state
# ---------------------------------------------------------------------- #

def test_device_chain_zero_host_copies_and_watchdog_clean():
    """The hop path never materializes host numpy (the explicit
    counter, because the transfer guard is inert on the CPU backend)
    and the dispatch watchdog sees zero unexpected D2H and zero
    steady-state recompiles across warm steps."""
    dispatch_debug.force(True)
    try:
        tr = dispatch_debug.tracker()
        runner, stages, transports = _chain(4, "1f1b", "device")
        try:
            for i in range(2):  # compile steps
                runner.step(*_batch(i), i)
            g0 = tr.gauges()
            for i in range(2, 5):  # steady state
                runner.step(*_batch(i), i)
            g1 = tr.gauges()
        finally:
            _close(runner, stages)
        for t in transports:
            assert t.stats.counters.get(spans.HOP_HOST_COPIES, 0) == 0
        assert g1["unexpected_d2h_total"] == g0["unexpected_d2h_total"]
        assert g1["steady_state_recompiles"] == g0["steady_state_recompiles"]
    finally:
        dispatch_debug.force(False)


def test_stage_i_lives_on_device_i_with_the_one_device_loss_series():
    """The launcher's placement on a backend with a device per stage
    (this 8-device virtual mesh; a four-chip host): stage i of the device
    chain on jax.devices()[i], the hub on device 0 — and where the
    stages live changes nothing they compute: the loss series is
    bit-identical to every stage on device 0."""
    from split_learning_tpu.parallel.mesh import stage_devices

    def run(devices):
        runner, stages, _ = _chain(4, "1f1b", "device", devices=devices)
        try:
            losses = [runner.step(*_batch(i), i) for i in range(4)]
            placed = ([runner.trace_metadata()["hub_devices"]]
                      + [s.health()["devices"] for s in stages])
        finally:
            _close(runner, stages)
        return losses, placed

    spread, placed = run([stage_devices(i, 3)[0] for i in (1, 2)])
    assert placed == [[jax.devices()[i].id] for i in range(3)]
    together, placed = run((None, None))
    assert placed == [[jax.devices()[0].id]] * 3
    assert spread == together


def test_local_transport_hop_payload_passthrough():
    """Satellite (a): on the default path (through_codec=False,
    compress=None) LocalTransport's hop payloads pass through untouched
    — the very same object, no np.asarray, no codec round-trip."""
    plan = get_plan(model="split_cnn_chain3", mode="split")
    cfg = _cfg(1)
    sample = np.zeros((BATCH, 28, 28, 1), np.float32)
    s1 = StageRuntime(plan, 1, cfg, jax.random.PRNGKey(SEED), sample)
    try:
        t = LocalTransport(s1)
        x = np.ones((2, 2), np.float32)
        assert t._hop_payload(x) is x
        t_codec = LocalTransport(s1, through_codec=True)
        assert t_codec._hop_payload(x) is not x
    finally:
        s1.close()


# ---------------------------------------------------------------------- #
# ppermute parity: the in-mesh collective path computes the same chain
# ---------------------------------------------------------------------- #

def test_ppermute_mesh_parity():
    """With a named pipe mesh (conftest forces 8 host devices) every
    hop rides the make_hop_shift ppermute collective between pipe
    ranks; the loss trajectory must equal the meshless relay's, and the
    hop path still counts zero host copies."""
    from split_learning_tpu.parallel.mesh import make_mesh
    if len(jax.devices()) < 3:
        pytest.skip("needs >= 3 devices for a pipe mesh")
    mesh = make_mesh(1, 3)
    plain = _losses(2, "1f1b", "device", steps=3)
    runner, stages, transports = _chain(2, "1f1b", "device", mesh=mesh)
    try:
        meshed = [runner.step(*_batch(i), i) for i in range(3)]
    finally:
        _close(runner, stages)
    assert meshed == plain
    for t in transports:
        assert t.stats.counters.get(spans.HOP_HOST_COPIES, 0) == 0


def test_make_hop_shift_moves_rank_to_rank():
    from split_learning_tpu.parallel.mesh import make_mesh
    from split_learning_tpu.parallel.pipeline import make_hop_shift
    if len(jax.devices()) < 3:
        pytest.skip("needs >= 3 devices for a pipe mesh")
    mesh = make_mesh(1, 3)
    x = np.arange(12, dtype=np.float32).reshape(3, 4)
    shifted = make_hop_shift(mesh, 0, 2)(x)
    np.testing.assert_array_equal(np.asarray(shifted), x)
    with pytest.raises(ValueError):
        make_hop_shift(mesh, 1, 1)
    with pytest.raises(ValueError):
        make_hop_shift(mesh, 0, 7)


# ---------------------------------------------------------------------- #
# surfaces: report/trace schedule fields; DeviceTransport scope errors
# ---------------------------------------------------------------------- #

def test_stage_report_and_trace_carry_schedule():
    runner, stages, _ = _chain(4, "1f1b", "device")
    try:
        runner.step(*_batch(0), 0)
        rows = runner.stage_report()
        for row in rows:
            assert row["schedule"] == "1f1b"
            assert row["warmup_depth"] == 3
            assert row["bubble_theoretical_gpipe"] == pytest.approx(2 / 6)
            assert row["bubble_theoretical_1f1b"] == pytest.approx(2 / 6)
        meta = runner.trace_metadata()
        assert meta["schedule"] == "1f1b"
        assert meta["warmup_depth"] == 3
        assert meta["device_native"] is True
    finally:
        _close(runner, stages)


def test_device_transport_rejects_two_party_ops():
    plan = get_plan(model="split_cnn_chain3", mode="split")
    s1 = StageRuntime(plan, 1, _cfg(1), jax.random.PRNGKey(SEED),
                      np.zeros((BATCH, 28, 28, 1), np.float32))
    try:
        t = DeviceTransport(s1)
        for call in (lambda: t.split_step(None, None, 0),
                     lambda: t.u_forward(None, 0),
                     lambda: t.u_backward(None, 0),
                     lambda: t.aggregate(None, 0, 0.0, 0)):
            with pytest.raises(NotImplementedError):
                call()
    finally:
        s1.close()


# ---------------------------------------------------------------------- #
# SLT115: the invariant fires on depth-bound and ordering violations
# ---------------------------------------------------------------------- #

class _Run:
    def __init__(self, notes):
        self.schedule_id = "t0"
        self.notes = notes


def test_onefb_invariant_fires_on_depth_overflow():
    from split_learning_tpu.analysis.invariants import (
        Violation, onefb_hop_order)
    run = _Run([("inflight", {"depth": 4, "bound": 3})])
    with pytest.raises(Violation, match="exceeds the 1F1B window"):
        onefb_hop_order(run)


def test_onefb_invariant_relays_order_violations():
    from split_learning_tpu.analysis.invariants import (
        Violation, onefb_hop_order)
    run = _Run([
        ("hop_sent", {"stage": 1, "dir": "fwd", "step": 0, "mb": 0}),
        ("hop_sent", {"stage": 1, "dir": "bwd", "step": 0, "mb": 0}),
        ("hop_apply", {"stage": 1, "dir": "bwd", "step": 0, "mb": 0}),
        ("hop_apply", {"stage": 1, "dir": "fwd", "step": 0, "mb": 0}),
    ])
    with pytest.raises(Violation) as exc:
        onefb_hop_order(run)
    assert exc.value.invariant == "onefb_hop_order"


def test_onefb_invariant_registered_as_slt115():
    from split_learning_tpu.analysis.invariants import (
        INVARIANTS, RULE_OF_INVARIANT)
    assert "onefb_hop_order" in INVARIANTS
    assert RULE_OF_INVARIANT["onefb_hop_order"] == "SLT115"
    from split_learning_tpu.analysis.scenarios import SCENARIOS
    assert "onefb_hop_order" in SCENARIOS
    assert "onefb_hop_order" in SCENARIOS["onefb_hop_order"].invariants
