"""The compute-vs-transport split the north star is about, from the one
recorder (obs/trace.py), and the profiler session that switches it on."""

import glob

import jax
import numpy as np
import pytest

from split_learning_tpu import obs
from split_learning_tpu.models import get_plan
from split_learning_tpu.obs import spans
from split_learning_tpu.runtime import ServerRuntime, SplitClientTrainer
from split_learning_tpu.transport import LocalTransport
from split_learning_tpu.utils import Config
from split_learning_tpu.utils.profiling import device_trace

# a recorded span's start against its annotation's in the xplane: 50 us
# on the chip (ISSUE 24); the CPU rehearsal shares two cores with the
# other test workers, so a preempted first entry gets more room
CLOCK_TOLERANCE_NS = 500_000


@pytest.fixture(autouse=True)
def _tracer_off():
    obs.disable()
    yield
    obs.disable()


def _party():
    cfg = Config(mode="split", batch_size=8)
    plan = get_plan(mode="split")
    x = np.random.RandomState(0).randn(8, 28, 28, 1).astype(np.float32)
    y = np.zeros((8,), np.int64)
    server = ServerRuntime(plan, cfg, jax.random.PRNGKey(0), x)
    client = SplitClientTrainer(plan, cfg, jax.random.PRNGKey(0),
                                LocalTransport(server))
    return client, x, y


def test_split_trainer_reports_transport_fraction():
    client, x, y = _party()
    tr = obs.enable()
    try:
        for i in range(3):
            client.train_step(x, y, i)
    finally:
        obs.disable()
    s = tr.phase_summary()
    assert set(obs.CLIENT_PHASES) <= set(s)
    assert all(s[p]["count"] == 3 for p in obs.CLIENT_PHASES)
    for row in s.values():
        assert row["p50_ms"] <= row["p90_ms"]
    assert 0.0 < tr.fraction("transport") < 1.0
    assert sum(tr.fraction(p) for p in obs.CLIENT_PHASES) == pytest.approx(1.0)


def test_empty_recorder_fraction_is_zero():
    """Nothing recorded, nothing spent anywhere: every share is 0.0,
    not a NaN that poisons downstream arithmetic."""
    tr = obs.enable()
    obs.disable()
    assert tr.fraction("transport") == 0.0
    assert tr.phase_summary() == {}
    assert obs.recorded() == [] or obs.recorder() is not tr


def test_recording_follows_a_profiler_session(tmp_path):
    """Start a session, two steps, stop: spans only from between, on the
    profiler's clock — each recorded span's annotation is in that
    session's xplane by name, its start within the tolerance."""
    from jax.profiler import ProfileData
    client, x, y = _party()
    client.train_step(x, y, 0)          # before: compiled, not recorded
    assert not obs.recording()
    with device_trace(str(tmp_path)):
        assert obs.recording()
        for i in (1, 2):
            client.train_step(x, y, i)
    assert not obs.recording()
    client.train_step(x, y, 3)          # after: not recorded either
    recs = obs.recorded()               # the last session's stay readable
    assert {r["step"] for r in recs if r["name"] == spans.STEP_TOTAL} == {1, 2}
    assert sum(r["name"] == spans.OPT_APPLY for r in recs) == 2
    assert obs.recorder().fraction(spans.TRANSPORT) > 0.0

    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    data = ProfileData.from_file(path)
    origin = dict(data.find_plane_with_name("Task Environment").stats)[
        "profile_start_time"]
    for name in (spans.STEP_TOTAL, spans.CLIENT_FWD, spans.H2D,
                 spans.DISPATCH, spans.OPT_APPLY):
        annotated = sorted(
            origin + int(e.start_ns) for p in data.planes
            if p.name.startswith("/host:") for line in p.lines
            for e in line.events if e.name == name)
        recorded = sorted(r["start_ns"] for r in recs if r["name"] == name)
        assert len(annotated) == len(recorded) > 0, name
        for a, r in zip(annotated, recorded):
            assert abs(a - r) < CLOCK_TOLERANCE_NS, (name, a - r)

    # the next session starts a fresh ring
    with device_trace(str(tmp_path / "second")):
        client.train_step(x, y, 4)
    assert {r["step"] for r in obs.recorded()
            if r["name"] == spans.STEP_TOTAL} == {4}
