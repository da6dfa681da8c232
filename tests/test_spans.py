"""The one span primitive (obs/trace.py): what it records, what it costs
nothing when off, and that it never changes the step it measures."""

import threading

import jax
import numpy as np
import pytest

from split_learning_tpu import obs
from split_learning_tpu.models import get_plan
from split_learning_tpu.obs import spans
from split_learning_tpu.obs import trace as obs_trace
from split_learning_tpu.runtime import ServerRuntime, SplitClientTrainer
from split_learning_tpu.runtime.coalesce import FLUSH_REASONS
from split_learning_tpu.runtime.fused import FusedSplitTrainer
from split_learning_tpu.runtime.multi_client import MultiClientSplitRunner
from split_learning_tpu.transport import LocalTransport
from split_learning_tpu.utils import Config


@pytest.fixture(autouse=True)
def _tracer_off():
    obs.disable()
    yield
    obs.disable()


def _data(batch=8, seed=0):
    rs = np.random.RandomState(seed)
    return (rs.randn(batch, 28, 28, 1).astype(np.float32),
            rs.randint(0, 10, (batch,)).astype(np.int64))


def _party(**server_kw):
    cfg = Config(mode="split", batch_size=8)
    plan = get_plan(mode="split")
    x, y = _data()
    server = ServerRuntime(plan, cfg, jax.random.PRNGKey(0), x, **server_kw)
    client = SplitClientTrainer(plan, cfg, jax.random.PRNGKey(0),
                                LocalTransport(server))
    return server, client, x, y


def _coalesced_rounds(n_clients=3, rounds=3):
    plan = get_plan(mode="split")
    cfg = Config(mode="split", batch_size=4, num_clients=n_clients)
    rs = np.random.RandomState(0)
    x = rs.randn(rounds, n_clients, 4, 28, 28, 1).astype(np.float32)
    y = rs.randint(0, 10, (rounds, n_clients, 4)).astype(np.int64)
    server = ServerRuntime(plan, cfg, jax.random.PRNGKey(0), x[0, 0],
                           coalesce_max=n_clients, coalesce_window_ms=50.0)
    runner = MultiClientSplitRunner(
        plan, cfg, jax.random.PRNGKey(1), lambda i: LocalTransport(server),
        num_clients=n_clients, concurrent=True)
    tr = obs.enable()
    try:
        for r in range(rounds):
            runner.train_round(list(zip(x[r], y[r])))
    finally:
        obs.disable()
        runner.close()
        server.close()
    return tr.spans()


# --------------------------------------------------------------------- #
# off: an annotation and nothing else


def test_off_a_span_records_nothing_and_touches_no_thread_state():
    before = obs.recorded()
    seen = []

    def work():
        with obs.span(spans.STEP_TOTAL, trace=(3, 7), tid=3, step=7,
                      bytes=12) as sp:
            seen.append((sp.recording, obs_trace.CTX.trace_id,
                         obs_trace.CTX.stack, sp.duration_s))
            sp.set(rows=4)
            sp.restart()
            sp.subtract(1.0)
        obs.span_at(spans.QUEUE_WAIT, 0, 10)

    t = threading.Thread(target=work)
    t.start()
    t.join()
    assert seen == [(False, None, None, 0.0)]
    assert obs.stamp() is None and not obs.recording()
    assert obs.recorded() == before


def test_off_the_request_carries_no_trace_id_and_no_stamp():
    """What travels with a request while nothing records: no trace id
    (so no payload key on any wire) and no enqueue stamp."""
    server, client, x, y = _party(coalesce_max=2, coalesce_window_ms=1.0)
    got = []
    submit = server._coalescer.submit

    def spy(*a, **kw):
        got.append((kw.get("trace_id"), kw.get("t_enqueue")))
        return submit(*a, **kw)

    server._coalescer.submit = spy
    try:
        client.train_step(x, y, 0)
        tr = obs.enable()
        client.train_step(x, y, 1)
        obs.disable()
    finally:
        server.close()
    assert got[0] == (None, None)
    assert got[1][0].startswith("c0-s1-") and got[1][1] > 0
    assert server.metrics()["histograms"]["queue_wait"]["count"] == 1
    assert {r["step"] for r in tr.spans()} == {1}


# --------------------------------------------------------------------- #
# the record: ids, parents, clock, attributes


def test_parent_ids_nest_per_thread():
    tr = obs.enable()
    out = {}
    together = threading.Barrier(4)   # alive at once: four thread idents

    def work(k):
        together.wait()
        with obs.span(spans.STEP_TOTAL, tid=k, step=k, trace=(k, k)) as root:
            with obs.span(spans.CLIENT_FWD) as fwd:
                with obs.span(spans.H2D, bytes=k) as copy:
                    pass
            with obs.span(spans.TRANSPORT) as tp:
                pass
        out[k] = (root, fwd, copy, tp)
        together.wait()

    threads = [threading.Thread(target=work, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    obs.disable()
    recs = {r["span_id"]: r for r in tr.spans()}
    assert len(recs) == 16 and len({r["thread"] for r in recs.values()}) == 4
    for k, (root, fwd, copy, tp) in out.items():
        assert recs[root.span_id]["parent_id"] is None
        assert recs[fwd.span_id]["parent_id"] == root.span_id
        assert recs[copy.span_id]["parent_id"] == fwd.span_id
        assert recs[tp.span_id]["parent_id"] == root.span_id
        # place and trace id come down from the root on that thread
        for sp in (fwd, copy, tp):
            r = recs[sp.span_id]
            assert (r["tid"], r["step"], r["party"]) == (k, k, "client")
            assert r["trace_id"] == recs[root.span_id]["trace_id"]
            assert r["thread"] == recs[root.span_id]["thread"]
        assert recs[copy.span_id]["attrs"] == {"bytes": k}
        r = recs[root.span_id]
        assert r["start_ns"] <= recs[fwd.span_id]["start_ns"]
        assert recs[tp.span_id]["end_ns"] <= r["end_ns"]
        assert r["dur_ns"] == r["end_ns"] - r["start_ns"]


def test_self_time_is_duration_minus_children():
    tree = [
        {"span_id": 1, "parent_id": None, "duration": 10.0},
        {"span_id": 2, "parent_id": 1, "duration": 4.0},
        {"span_id": 3, "parent_id": 1, "duration": 3.0},
        {"span_id": 4, "parent_id": 2, "duration": 1.5},
        {"span_id": 5, "parent_id": 99, "duration": 2.0},  # parent fell off
    ]
    own = obs.self_times(tree)
    assert own == {1: 3.0, 2: 2.5, 3: 3.0, 4: 1.5, 5: 2.0}


def test_close_restart_subtract_and_span_at():
    reg = obs.Registry()
    tr = obs.enable()
    t0 = obs.stamp()
    with obs.span(spans.QUEUE_WAIT, party="server", registry=reg) as wait:
        wait.close()                       # ends here, not at the exit
        with obs.span(spans.DISPATCH, party="server") as disp:
            pass
    with obs.span(spans.REPLY_GRAD) as late:
        late.restart()
    with obs.span(spans.WIRE) as wire:
        wire.subtract(3600.0)              # more than it lasted: floor 0
    obs.span_at(spans.COMPILE, t0 - 5_000, t0, party="server", step=2,
                registry=reg)
    obs.disable()
    recs = {r["name"]: r for r in tr.spans()}
    assert recs["dispatch"]["parent_id"] is None       # not queue_wait's child
    assert recs["queue_wait"]["end_ns"] <= recs["dispatch"]["start_ns"]
    assert recs["reply_grad"]["start_ns"] == late.t0 > t0
    assert recs["wire"]["dur_ns"] == 0 and wire.duration_s == 0.0
    assert recs["wire"]["end_ns"] > recs["wire"]["start_ns"]
    assert recs["xla_compile"]["dur_ns"] == 5_000
    assert (recs["xla_compile"]["party"], recs["xla_compile"]["step"]) == (
        "server", 2)
    snap = reg.snapshot()["histograms"]
    assert set(snap) == {"queue_wait", "xla_compile"}
    assert set(tr.registry.snapshot()["histograms"]) == set(recs)


def test_a_span_open_when_the_session_ends_is_not_kept(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    with obs.span(spans.STEP_TOTAL, step=1):
        pass
    outer = obs.span(spans.STEP_TOTAL, step=2)
    outer.__enter__()
    jax.profiler.stop_trace()
    outer.__exit__(None, None, None)
    assert [r["step"] for r in obs.recorded()] == [1]
    assert obs_trace.CTX.stack == []


def test_span_names_are_registered_and_not_the_benchmarks():
    children = {spans.ROUND, spans.H2D, spans.LOSS_WAIT, spans.COUNTERS_READ}
    assert children <= set(spans.ALL_SPANS)
    for phases in (spans.CLIENT_PHASES, spans.SERVER_PHASES,
                   spans.TRANSPORT_SUB):
        assert not children & set(phases)
    # a collection's and its counters' names are no spans
    assert not {spans.STEP_COUNTERS, spans.MOE_PAIRS, spans.MOE_ROWS,
                spans.MOE_LADDER} & set(spans.ALL_SPANS)
    assert len(set(spans.ALL_SPANS)) == len(spans.ALL_SPANS)
    assert not any("." in name for name in spans.ALL_SPANS)


# --------------------------------------------------------------------- #
# the party path


def test_client_step_is_tiled_by_four_phases_with_copies_beneath():
    server, client, x, y = _party()
    tr = obs.enable()
    try:
        for i in range(3):
            client.train_step(x, y, i)
    finally:
        obs.disable()
    recs = tr.spans()
    by_id = {r["span_id"]: r for r in recs}
    for root in (r for r in recs if r["name"] == spans.STEP_TOTAL):
        kids = [r for r in recs if r["parent_id"] == root["span_id"]]
        assert [k["name"] for k in sorted(kids, key=lambda r: r["start_ns"])
                ] == list(spans.CLIENT_PHASES)
        # the phases tile the step: nothing overlaps, next to nothing is left
        for a, b in zip(kids, kids[1:]):
            assert a["end_ns"] <= b["start_ns"]
        assert obs.self_times(recs)[root["span_id"]] >= 0.0
    acts_bytes = 0
    for r in recs:
        parent = by_id.get(r["parent_id"])
        if r["name"] == spans.H2D and r["party"] == "client":
            assert parent["name"] in (spans.CLIENT_FWD, spans.CLIENT_BWD)
        if r["name"] == spans.D2H and r["party"] == "client":
            assert parent["name"] == spans.CLIENT_FWD
            acts_bytes = r["attrs"]["bytes"]
        if r["name"] in (spans.QUEUE_WAIT, spans.DISPATCH) or (
                r["name"] == spans.D2H and r["party"] == "server"):
            assert parent["name"] == spans.WIRE    # same thread, in process
        if r["name"] in (spans.ENCODE, spans.WIRE):
            assert parent["name"] == spans.TRANSPORT
    # every copy span carries the bytes of the arrays it moved
    want = {(spans.H2D, spans.CLIENT_FWD): x.nbytes,
            (spans.D2H, spans.CLIENT_FWD): acts_bytes,
            (spans.H2D, spans.CLIENT_BWD): x.nbytes + acts_bytes,
            (spans.H2D, spans.DISPATCH): acts_bytes + y.nbytes,
            (spans.D2H, spans.WIRE): acts_bytes + 4}
    acts = np.asarray(client._fwd(client.state.params, x))
    assert acts_bytes == acts.nbytes
    copies = [r for r in recs if r["name"] in (spans.H2D, spans.D2H)]
    assert len(copies) == 3 * len(want)
    for r in copies:
        assert r["attrs"]["bytes"] == want[
            (r["name"], by_id[r["parent_id"]]["name"])]


def test_a_groups_dispatch_is_one_span_naming_all_its_requests():
    recs = _coalesced_rounds(n_clients=3, rounds=3)
    groups = [r for r in recs if r["name"] == spans.DISPATCH]
    waits = [r for r in recs if r["name"] == spans.QUEUE_WAIT]
    assert len(waits) == 9
    assert sum(g["attrs"]["group"] for g in groups) == 9
    assert len(groups) < 9        # a 50 ms window: some group held several
    named = [t for g in groups for t in g["attrs"]["traces"]]
    assert sorted(named) == sorted(w["trace_id"] for w in waits)
    for g in groups:
        assert g["party"] == "server" and g["parent_id"] is None
        assert g["attrs"]["reason"] in FLUSH_REASONS
        assert g["attrs"]["rows"] == 4 * g["attrs"]["group"]
        assert g["attrs"]["padded"] >= g["attrs"]["rows"]
        assert len(g["attrs"]["traces"]) == g["attrs"]["group"]
    # one server d2h a group, on the waiter that redeemed it
    d2h = [r for r in recs if r["name"] == spans.D2H and r["party"] == "server"]
    assert len(d2h) == len(groups)
    assert all(r["attrs"]["bytes"] > 0 for r in d2h)


def test_queue_wait_runs_from_enqueue_to_the_groups_pickup():
    recs = _coalesced_rounds(n_clients=3, rounds=2)
    waits = {r["trace_id"]: r for r in recs if r["name"] == spans.QUEUE_WAIT}
    assert len(waits) == 6        # one a request
    for g in (r for r in recs if r["name"] == spans.DISPATCH):
        members = [waits[t] for t in g["attrs"]["traces"]]
        # the same pickup ends every member's wait, before the lock
        assert len({w["end_ns"] for w in members}) == 1
        assert members[0]["end_ns"] <= g["start_ns"]
        for w in members:
            wire = next(r for r in recs if r["name"] == spans.WIRE
                        and r["trace_id"] == w["trace_id"])
            assert wire["start_ns"] <= w["start_ns"] <= w["end_ns"]
            assert (w["party"], w["tid"]) == ("server", wire["tid"])


def test_round_is_the_root_on_the_driving_thread():
    plan = get_plan(mode="split")
    cfg = Config(mode="split", batch_size=4, num_clients=2)
    x, y = _data(4)
    server = ServerRuntime(plan, cfg, jax.random.PRNGKey(0), x)
    runner = MultiClientSplitRunner(
        plan, cfg, jax.random.PRNGKey(1), lambda i: LocalTransport(server),
        num_clients=2, concurrent=True)
    tr = obs.enable()
    try:
        for _ in range(2):
            runner.train_round([(x, y), (x, y)])
    finally:
        obs.disable()
        runner.close()
    recs = tr.spans()
    rounds = [r for r in recs if r["name"] == spans.ROUND]
    assert [r["step"] for r in rounds] == [0, 1]
    assert all(r["parent_id"] is None and r["attrs"] == {"clients": 2}
               for r in rounds)
    steps = [r for r in recs if r["name"] == spans.STEP_TOTAL]
    assert sorted(s["attrs"]["round"] for s in steps) == [0, 0, 1, 1]
    for s in steps:   # named in an attribute; another thread, no parent
        assert s["parent_id"] is None
        assert s["thread"] != rounds[0]["thread"]
        rnd = rounds[s["attrs"]["round"]]
        assert rnd["start_ns"] <= s["start_ns"] and s["end_ns"] <= rnd["end_ns"]


# --------------------------------------------------------------------- #
# tracing changes nothing about the step


def _counted(obj, name, counts):
    fn = getattr(obj, name)

    def call(*a, **kw):
        counts[name] = counts.get(name, 0) + 1
        return fn(*a, **kw)

    setattr(obj, name, call)


def test_traced_and_untraced_steps_are_the_same_program(monkeypatch,
                                                        tmp_path):
    """Traced as the benchmark means it: under a profiler session."""
    from split_learning_tpu.utils.profiling import device_trace

    def run(traced):
        counts = {}
        syncs = []
        server, client, x, y = _party()
        _counted(client, "_fwd", counts)
        _counted(client, "_bwd", counts)
        _counted(server, "_split_step", counts)
        monkeypatch.setattr(jax, "block_until_ready",
                            lambda v: syncs.append(1) or v)
        try:
            with device_trace(str(tmp_path) if traced else None):
                losses = [client.train_step(x, y, i) for i in range(3)]
        finally:
            monkeypatch.undo()
        return losses, counts, syncs, obs.recorded() if traced else []

    plain, traced = run(False), run(True)
    assert traced[0] == plain[0]                       # bit-equal losses
    assert traced[1] == plain[1] == {"_fwd": 3, "_bwd": 3, "_split_step": 3}
    assert traced[2] == plain[2] == []                 # no sync added
    assert len(traced[3]) > 30 and plain[3] == []


def test_fused_step_spans_and_bytes():
    cfg = Config(mode="split", batch_size=8)
    plan = get_plan(mode="split")
    x, y = _data()
    trainer = FusedSplitTrainer(plan, cfg, jax.random.PRNGKey(0), x)
    plain = trainer.train_step(x, y)
    tr = obs.enable()
    try:
        traced = [trainer.train_step(x, y) for _ in range(2)]
        trainer.train_step_async(x, y).block_until_ready()
    finally:
        obs.disable()
    assert np.isfinite(plain) and all(np.isfinite(v) for v in traced)
    recs = tr.spans()
    roots = [r for r in recs if r["name"] == spans.STEP_TOTAL]
    assert len(roots) == 3
    kids = [[k["name"] for k in sorted(
        (r for r in recs if r["parent_id"] == root["span_id"]),
        key=lambda r: r["start_ns"])] for root in roots]
    assert kids == [[spans.H2D, spans.DISPATCH, spans.LOSS_WAIT]] * 2 + [
        [spans.H2D, spans.DISPATCH]]
    assert all(r["attrs"]["bytes"] == x.nbytes + y.nbytes
               for r in recs if r["name"] == spans.H2D)
