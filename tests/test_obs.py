"""Observability subsystem (obs/): per-step tracing, latency histograms,
Prometheus /metrics, Chrome-trace export, and the zero-overhead-off
contract across LocalTransport, HttpTransport, and the coalescer."""

import json
import threading
import time
import urllib.request

import jax
import numpy as np
import pytest

from split_learning_tpu import obs
from split_learning_tpu.models import get_plan
from split_learning_tpu.obs.metrics import (
    Histogram, Registry, render_prometheus)
from split_learning_tpu.runtime import ServerRuntime, SplitClientTrainer
from split_learning_tpu.runtime.multi_client import MultiClientSplitRunner
from split_learning_tpu.transport import LocalTransport
from split_learning_tpu.transport.http import HttpTransport, SplitHTTPServer
from split_learning_tpu.utils import Config


@pytest.fixture(autouse=True)
def _tracer_off():
    """The global tracer must never leak between tests (the rest of the
    suite pins the untraced wire format)."""
    obs.disable()
    yield
    obs.disable()


def _data(batch=8, seed=0):
    rs = np.random.RandomState(seed)
    x = rs.randn(batch, 28, 28, 1).astype(np.float32)
    y = rs.randint(0, 10, (batch,)).astype(np.int64)
    return x, y


# --------------------------------------------------------------------- #
# histograms + Prometheus text


def test_histogram_bucket_monotonicity():
    h = Histogram()
    values = (0.00005, 0.0003, 0.003, 0.02, 0.7, 42.0)
    for v in values:
        h.observe(v)
    snap = h.snapshot()
    cum = snap["cumulative"]
    assert len(cum) == len(snap["buckets"]) + 1  # +Inf slot
    assert all(a <= b for a, b in zip(cum, cum[1:]))
    assert cum[-1] == snap["count"] == len(values)
    assert snap["sum"] == pytest.approx(sum(values))
    # a value beyond the last bound lands only in +Inf
    assert cum[-1] - cum[-2] == 1  # the 42.0 observation


def test_histogram_rejects_bad_buckets():
    with pytest.raises(ValueError):
        Histogram(buckets=(0.1, 0.1, 0.2))
    with pytest.raises(ValueError):
        Histogram(buckets=())


def test_render_prometheus_parses_as_exposition_text():
    reg = Registry()
    for v in (0.001, 0.02, 0.3):
        reg.observe("dispatch", v)
    reg.observe("queue_wait", 0.004)
    reg.incr("split_steps_total", 3)
    reg.set_gauge("acked_step", 2.0)
    text = render_prometheus(reg.snapshot())
    assert text.endswith("\n")
    seen = set()
    for ln in text.splitlines():
        if not ln or ln.startswith("#"):
            continue
        # every sample line is "name[{labels}] value" with a float value
        name, val = ln.rsplit(" ", 1)
        float(val)
        seen.add(name.split("{")[0])
    assert {"slt_dispatch_seconds_bucket", "slt_dispatch_seconds_sum",
            "slt_dispatch_seconds_count", "slt_queue_wait_seconds_bucket",
            "slt_phase_fraction", "slt_split_steps_total",
            "slt_acked_step"} <= seen
    # cumulative bucket counts are monotone in exposition order and the
    # +Inf bucket equals _count
    cum = [float(ln.rsplit(" ", 1)[1]) for ln in text.splitlines()
           if ln.startswith("slt_dispatch_seconds_bucket")]
    assert cum == sorted(cum)
    assert 'slt_dispatch_seconds_bucket{le="+Inf"} 3' in text
    assert "slt_dispatch_seconds_count 3" in text


# --------------------------------------------------------------------- #
# trace-ID propagation: LocalTransport (same thread)


def test_trace_id_propagates_through_local_transport():
    cfg = Config(mode="split", batch_size=8)
    plan = get_plan(mode="split")
    x, y = _data()
    server = ServerRuntime(plan, cfg, jax.random.PRNGKey(0), x)
    client = SplitClientTrainer(plan, cfg, jax.random.PRNGKey(0),
                                LocalTransport(server))
    tr = obs.enable()
    try:
        for i in range(3):
            client.train_step(x, y, i)
    finally:
        obs.disable()
    spans = tr.spans()
    names = {s["name"] for s in spans}
    assert {"client_fwd", "encode", "wire", "transport", "client_bwd",
            "opt_apply", "step_total", "queue_wait", "dispatch"} <= names
    # every span of one step carries the SAME trace id, client and
    # server parties both
    by_tid = {}
    for s in spans:
        assert s["trace_id"], f"span {s['name']} lost its trace id"
        by_tid.setdefault(s["trace_id"], set()).add(
            (s["name"], s["party"]))
    assert len(by_tid) == 3  # one trace per step
    for group in by_tid.values():
        assert ("client_fwd", "client") in group
        assert ("queue_wait", "server") in group
        assert ("dispatch", "server") in group
    # the transport span fully contains its encode + wire sub-spans
    summary = tr.phase_summary()
    assert summary["transport"]["total_s"] >= (
        summary["encode"]["total_s"] + summary["wire"]["total_s"]) * 0.99
    # spans aggregate into the tracer's registry histograms
    snap = tr.registry.snapshot()
    assert {"queue_wait", "dispatch", "transport"} <= set(snap["histograms"])


def test_tracing_off_leaves_transport_stats_untouched():
    """Zero-overhead-off: with the tracer off (the default) no span
    counters appear anywhere — the hot path is the untraced one."""
    cfg = Config(mode="split", batch_size=8)
    plan = get_plan(mode="split")
    x, y = _data()
    server = ServerRuntime(plan, cfg, jax.random.PRNGKey(0), x)
    transport = LocalTransport(server)
    client = SplitClientTrainer(plan, cfg, jax.random.PRNGKey(0), transport)
    for i in range(2):
        client.train_step(x, y, i)
    assert not any(k.startswith("span_") for k in transport.stats.counters)
    # and the server-side registry stayed empty
    assert server.metrics()["histograms"] == {}


# --------------------------------------------------------------------- #
# trace-ID propagation: HttpTransport + GET /metrics over the wire


def test_http_transport_propagates_spans_and_serves_metrics():
    cfg = Config(mode="split", batch_size=8)
    plan = get_plan(mode="split")
    x, y = _data()
    runtime = ServerRuntime(plan, cfg, jax.random.PRNGKey(0), x)
    server = SplitHTTPServer(runtime).start()
    transport = HttpTransport(server.url)
    client = SplitClientTrainer(plan, cfg, jax.random.PRNGKey(0), transport)
    tr = obs.enable()
    try:
        for i in range(3):
            client.train_step(x, y, i)
        with urllib.request.urlopen(f"{server.url}/metrics") as resp:
            assert resp.status == 200
            assert "version=0.0.4" in resp.headers.get("Content-Type", "")
            text = resp.read().decode()
    finally:
        obs.disable()
        transport.close()
        server.stop()
    # client side saw the full taxonomy, server spans folded back via
    # the response payload
    names = {s["name"] for s in tr.spans()}
    assert {"client_fwd", "encode", "wire", "transport", "queue_wait",
            "dispatch", "step_total"} <= names
    counters = transport.stats.counters
    for k in ("span_encode_s", "span_wire_s", "span_queue_wait_s",
              "span_dispatch_s"):
        assert counters.get(k, 0.0) > 0.0
        assert counters[k.replace("_s", "_n")] == 3
    # the scraped exposition carries the server-party histograms
    assert "slt_queue_wait_seconds_bucket" in text
    assert "slt_dispatch_seconds_bucket" in text
    assert "slt_split_steps_total 3" in text
    for ln in text.splitlines():
        if ln and not ln.startswith("#"):
            float(ln.rsplit(" ", 1)[1])  # parseable exposition


def test_http_payload_unchanged_when_tracing_off():
    """The wire format with tracing off is bit-for-bit the untraced one:
    no trace_id in the request, no server_spans in the response."""
    from split_learning_tpu.transport import codec
    cfg = Config(mode="split", batch_size=8)
    plan = get_plan(mode="split")
    x, y = _data()
    runtime = ServerRuntime(plan, cfg, jax.random.PRNGKey(0), x)
    server = SplitHTTPServer(runtime).start()
    try:
        # do one normal (untraced) step to initialize, then speak the
        # raw wire protocol for the next step and inspect both payloads
        transport = HttpTransport(server.url)
        trainer = SplitClientTrainer(plan, cfg, jax.random.PRNGKey(0),
                                     transport)
        trainer.train_step(x, y, 0)
        acts = np.asarray(trainer._fwd(trainer.state.params,
                                       jax.numpy.asarray(x)))
        payload = codec.encode({"activations": acts, "labels": y,
                                "step": 1, "client_id": 0})
        req = urllib.request.Request(
            f"{server.url}/forward_pass", data=payload,
            headers={"Content-Type": "application/octet-stream"})
        with urllib.request.urlopen(req) as resp:
            out = codec.decode(resp.read())
        assert set(out) == {"grads", "loss", "step"}  # no server_spans
        transport.close()
    finally:
        server.stop()


# --------------------------------------------------------------------- #
# coalescer queue-wait spans under a concurrent burst


def test_coalescer_records_queue_wait_spans_under_burst():
    n_clients, rounds = 3, 3
    plan = get_plan(mode="split")
    cfg = Config(mode="split", batch_size=4, num_clients=n_clients)
    rs = np.random.RandomState(0)
    x = rs.randn(rounds, n_clients, 4, 28, 28, 1).astype(np.float32)
    y = rs.randint(0, 10, (rounds, n_clients, 4)).astype(np.int64)
    server = ServerRuntime(plan, cfg, jax.random.PRNGKey(0), x[0, 0],
                           coalesce_max=n_clients, coalesce_window_ms=20.0)
    runner = MultiClientSplitRunner(
        plan, cfg, jax.random.PRNGKey(1),
        lambda i: LocalTransport(server),
        num_clients=n_clients, concurrent=True)
    tr = obs.enable()
    try:
        for r in range(rounds):
            runner.train_round(list(zip(x[r], y[r])))
    finally:
        obs.disable()
        runner.close()
        server.close()
    qw = [s for s in tr.spans() if s["name"] == "queue_wait"]
    assert len(qw) == rounds * n_clients
    # enqueue -> group pickup includes the coalescer window wait, and
    # each request keeps its own client's trace id
    assert all(s["party"] == "server" for s in qw)
    assert all(s["trace_id"] for s in qw)
    client_ids = {s["tid"] for s in qw}
    assert client_ids == set(range(n_clients))
    # the window wait is real time: a full group closes on arrival of
    # the last member, so SOME request waited a measurable while
    assert max(s["duration"] for s in qw) > 0.0
    # server metrics picked the spans up as histograms
    snap = server.metrics()
    assert snap["histograms"]["queue_wait"]["count"] == rounds * n_clients
    assert snap["counters"]["split_steps_total"] == rounds * n_clients
    assert snap["counters"]["coalesce_groups_flushed"] >= rounds


# --------------------------------------------------------------------- #
# Chrome export + trace_report.py agreement with the recorder


def _load_trace_report():
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(__file__), "..", "scripts",
                        "trace_report.py")
    spec = importlib.util.spec_from_file_location("trace_report", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chrome_export_and_trace_report_reproduce_fraction(tmp_path):
    cfg = Config(mode="split", batch_size=8)
    plan = get_plan(mode="split")
    x, y = _data()
    server = ServerRuntime(plan, cfg, jax.random.PRNGKey(0), x)
    client = SplitClientTrainer(plan, cfg, jax.random.PRNGKey(0),
                                LocalTransport(server))
    tr = obs.enable()
    try:
        for i in range(4):
            client.train_step(x, y, i)
    finally:
        obs.disable()
    path = tr.export_chrome(str(tmp_path / "trace.json"))

    # the export is a valid Chrome trace: whole-file JSON, complete
    # events with absolute µs timestamps on the spans' clock, per-party
    # process metadata, span and parent ids in args
    events = json.load(open(path))
    metas = [e for e in events if e.get("ph") == "M"]
    assert {m["args"]["name"] for m in metas} == {"slt-client", "slt-server"}
    xs = [e for e in events if e.get("ph") == "X"]
    assert xs and all(e["dur"] >= 0 and e["ts"] >= 0 for e in xs)
    assert {e["pid"] for e in xs} == {1, 2}
    by_id = {e["args"]["span_id"]: e for e in xs}
    now_us = time.time_ns() / 1e3
    assert all(now_us - 600e6 < e["ts"] <= now_us for e in xs)
    for e in xs:
        parent = by_id.get(e["args"]["parent_id"])
        if e["name"] in ("client_fwd", "transport", "client_bwd",
                         "opt_apply"):
            assert parent["name"] == "step_total"
        if e["name"] == "step_total":
            assert parent is None

    # ...and line-parseable (the tolerant path trace_report also takes)
    report = _load_trace_report()
    lines_events = report.load_events(path)
    assert len(lines_events) == len(events)

    rep = report.summarize(lines_events)
    # the report's transport fraction reproduces the recorder's view of
    # the same run, and both are the transport spans' share of the four
    # phases that tile a step
    assert rep["transport_fraction"] == pytest.approx(
        tr.fraction("transport"), abs=1e-9)
    recs = tr.spans()
    total = {n: sum(r["duration"] for r in recs if r["name"] == n)
             for n in obs.CLIENT_PHASES}
    assert tr.fraction("transport") == pytest.approx(
        total["transport"] / sum(total.values()), abs=1e-9)
    assert 0.0 < tr.fraction("transport") < 1.0
    # acceptance gate: per-step client spans sum to within 10% of the
    # measured step_total wall clock
    assert rep["steps_with_wall_clock"] == 4
    assert 0.9 <= rep["span_sum_over_wall_clock"] <= 1.01
    # the rendered table mentions every phase
    text = report.render(rep)
    for name in ("client_fwd", "transport", "queue_wait", "dispatch"):
        assert name in text


def test_trace_report_tolerates_truncated_file(tmp_path):
    """A live/crashed export (no closing bracket, torn last line) still
    yields every complete event."""
    tr = obs.enable()
    try:
        t0 = time.time_ns()
        for i in range(5):
            obs.span_at("client_fwd", t0 + i * 10**9, t0 + i * 10**9 + 10**7,
                        trace_id=f"t{i}")
    finally:
        obs.disable()
    full = tr.export_chrome(str(tmp_path / "full.json"))
    content = open(full).read()
    torn = tmp_path / "torn.json"
    torn.write_text(content.rsplit("\n", 3)[0] + '\n{"name": "client_')
    report = _load_trace_report()
    events = report.load_events(str(torn))
    assert len(events) >= 5  # metadata + all complete span lines


def test_trace_report_compile_summary(tmp_path):
    """xla_compile spans (the dispatch watchdog's trace export) get
    their own section: count/total/max plus the steady-state count
    (args.step >= 2 — a recompile storm). Tolerant: missing or
    non-numeric step fields count as non-steady, and a trace without
    compile events renders with no compile section at all."""
    report = _load_trace_report()
    lines = [
        '{"ph": "X", "name": "client_fwd", "ts": 0, "dur": 1000, '
        '"pid": 1, "tid": 1}',
        '{"ph": "X", "name": "xla_compile", "ts": 0, "dur": 250000, '
        '"pid": 2, "tid": 1, "args": {"step": 0}}',
        '{"ph": "X", "name": "xla_compile", "ts": 1, "dur": 50000, '
        '"pid": 2, "tid": 1, "args": {"step": 3}}',
        '{"ph": "X", "name": "xla_compile", "ts": 2, "dur": 10000, '
        '"pid": 2, "tid": 1}',
        '{"ph": "X", "name": "xla_compile", "ts": 3, "dur": 10000, '
        '"pid": 2, "tid": 1, "args": {"step": "?"}}',
        '{"ph": "X", "name": "xla_comp',  # torn tail of a live file
    ]
    torn = tmp_path / "live.json"
    torn.write_text("[\n" + ",\n".join(lines))
    events = report.load_events(str(torn))
    rep = report.summarize(events)
    comp = rep["compile"]
    assert comp["count"] == 4
    assert comp["total_s"] == pytest.approx(0.32)
    assert comp["max_ms"] == pytest.approx(250.0)
    assert comp["steady_state_count"] == 1
    text = report.render(rep)
    assert "xla compiles: 4" in text and "recompile storm" in text
    rep0 = report.summarize(
        [e for e in events if e.get("name") != "xla_compile"])
    assert rep0["compile"]["count"] == 0
    assert "xla compiles" not in report.render(rep0)


def test_trace_report_schedules_section(tmp_path):
    """--schedules summarizes an slt-check explorer report: per-scenario
    schedules/pruned/pruning-ratio/max-preemption rows, skipped
    scenarios marked, and each violation rendered with its replayable
    schedule id."""
    report = _load_trace_report()
    check = {
        "total_schedules": 110,
        "scenarios": {
            "replay_dup_storm": {
                "schedules": 100, "pruned": 50, "pruning_ratio": 1 / 3,
                "exhausted": False, "max_preemptions": 3,
                "max_transitions": 80, "invariants": ["no_errors"],
                "violations": [], "sample_fingerprints": {}},
            "toy_broken": {
                "schedules": 10, "pruned": 0, "pruning_ratio": 0.0,
                "exhausted": True, "max_preemptions": 1,
                "max_transitions": 9,
                "invariants": ["exactly_once_claims"],
                "violations": [{"invariant": "exactly_once_claims",
                                "schedule_id": "toy_broken:3F",
                                "message": "step 0 applied 2 times"}],
                "sample_fingerprints": {}},
            "needs_jax": {"skipped": "jax"},
        },
    }
    p = tmp_path / "check.json"
    p.write_text(json.dumps(check))
    rep = report.summarize_schedules(str(p))
    assert rep["totals"] == {"schedules": 110, "pruned": 50,
                             "violations": 1, "skipped": 1}
    text = report.render_schedules(rep)
    assert "replay_dup_storm" in text and "exhausted" in text
    assert "budget-capped" in text
    assert "skipped (requires jax)" in text
    assert "--schedule toy_broken:3F" in text
    # CLI: --schedules alone is a valid invocation (no trace positional)
    assert report.main(["--schedules", str(p)]) == 0


def test_trace_report_crash_subsection(tmp_path):
    """slt-crash entries (``"crash": true``) get their own subsection —
    bases, crash points, pruning ratio — and crash violations render
    with the full replayable ``@crash:`` id. Reports from the crash-off
    checker (no crash keys anywhere) must render exactly as before."""
    report = _load_trace_report()
    check = {
        "total_schedules": 190,
        "crash": True,
        "scenarios": {
            "replay_dup_storm": {
                "schedules": 20, "pruned": 4, "pruning_ratio": 1 / 6,
                "exhausted": True, "max_preemptions": 2,
                "max_transitions": 40, "invariants": ["no_errors"],
                "violations": [], "sample_fingerprints": {}},
            "crash_replay_dup_storm": {
                "crash": True, "bases": 12, "crash_schedules": 168,
                "schedules": 170, "pruned": 56,
                "pruning_ratio": 56 / 226, "exhausted": True,
                "max_preemptions": 2, "max_transitions": 64,
                "invariants": ["durable_exactly_once"],
                "violations": [{
                    "invariant": "durable_exactly_once",
                    "schedule_id": "crash_replay_dup_storm:3F@crash:7",
                    "message": "step (0, 'split_step', 1) lost"}],
                "sample_fingerprints": {}},
            "crash_needs_jax": {"skipped": "jax", "crash": True},
        },
    }
    p = tmp_path / "crash-check.json"
    p.write_text(json.dumps(check))
    rep = report.summarize_schedules(str(p))
    assert rep["totals"] == {"schedules": 190, "pruned": 60,
                             "violations": 1, "skipped": 1}
    assert rep["scenarios"]["crash_replay_dup_storm"]["bases"] == 12
    assert "crash" not in rep["scenarios"]["replay_dup_storm"]
    text = report.render_schedules(rep)
    assert "crash-restart schedules" in text
    assert "--schedule crash_replay_dup_storm:3F@crash:7" in text
    assert report.main(["--schedules", str(p)]) == 0
    # tolerant fallback: a crash-off report renders with NO subsection
    old = {"total_schedules": 5, "scenarios": {
        "replay_dup_storm": {"schedules": 5, "pruned": 0}}}
    p2 = tmp_path / "old.json"
    p2.write_text(json.dumps(old))
    text2 = report.render_schedules(report.summarize_schedules(str(p2)))
    assert "crash-restart schedules" not in text2
    assert report.main(["--schedules", str(p2)]) == 0


# --------------------------------------------------------------------- #
# runtime.metrics() snapshot (the in-process twin of GET /metrics)


def test_runtime_metrics_snapshot_shape():
    cfg = Config(mode="split", batch_size=8)
    plan = get_plan(mode="split")
    x, y = _data()
    server = ServerRuntime(plan, cfg, jax.random.PRNGKey(0), x)
    client = SplitClientTrainer(plan, cfg, jax.random.PRNGKey(0),
                                LocalTransport(server))
    tr = obs.enable()
    try:
        for i in range(2):
            client.train_step(x, y, i)
    finally:
        obs.disable()
    snap = server.metrics()
    assert set(snap) == {"histograms", "counters", "gauges",
                         "phase_fractions"}
    assert snap["histograms"]["queue_wait"]["count"] == 2
    assert snap["histograms"]["dispatch"]["count"] == 2
    assert snap["counters"]["split_steps_total"] == 2
    assert snap["gauges"]["acked_step"] == 1.0  # last acked step
    fr = snap["phase_fractions"]
    assert pytest.approx(sum(fr.values()), abs=1e-6) == 1.0
    # the same snapshot renders (the /metrics body) without error
    assert "slt_dispatch_seconds_count 2" in render_prometheus(snap)
