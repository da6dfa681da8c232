"""scripts/fleet_sim.py's JSON summary: the schema-stable blocks capacity
sweeps and dashboards read (utilization, replication, autoscale,
telemetry), each at a tiny fleet on the CPU."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_fleet_sim_summary_utilization_schema(monkeypatch, capsys):
    """scripts/fleet_sim.py's JSON summary carries the utilization /
    saturation block capacity sweeps bisect on: steady-state occupancy
    as a fraction of --coalesce-max, the admission reject rate, and the
    pooled step p99 measured against --slo-ms. Run in-process (the
    suite's JAX is already warm) on a tiny quota'd fleet so every field
    takes its non-null arm."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "fleet_sim", os.path.join(REPO, "scripts", "fleet_sim.py"))
    fleet_sim = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fleet_sim)

    monkeypatch.setattr(sys, "argv", [
        "fleet_sim.py", "--clients", "4", "--tenants", "2",
        "--steps", "1", "--rate", "5.0", "--batch", "4",
        "--batching", "continuous", "--coalesce-max", "4",
        "--quota", "100", "--slo-ms", "5000"])
    assert fleet_sim.main() == 0
    out = capsys.readouterr().out
    summary = json.loads(out[out.index("{"):])

    util = summary["utilization"]
    assert set(util) == {"mean_occupancy", "steady_state_occupancy",
                         "admission_reject_rate", "step_p99_over_slo",
                         "slo_attained"}
    assert util["mean_occupancy"] >= 1.0
    assert 0.0 < util["steady_state_occupancy"] <= 1.0
    assert util["steady_state_occupancy"] == pytest.approx(
        util["mean_occupancy"] / 4, abs=5e-4)
    # quota'd run: the admission layer is live, so the rate is a number
    assert 0.0 <= util["admission_reject_rate"] <= 1.0
    assert util["step_p99_over_slo"] > 0.0
    assert util["slo_attained"] == (util["step_p99_over_slo"] <= 1.0)
    # without --quota/--slo-ms the null arms must ship as nulls, not be
    # dropped from the schema (jq-stable for sweep scripts)
    monkeypatch.setattr(sys, "argv", [
        "fleet_sim.py", "--clients", "2", "--tenants", "1",
        "--steps", "1", "--rate", "5.0", "--batch", "4",
        "--batching", "continuous"])
    assert fleet_sim.main() == 0
    out = capsys.readouterr().out
    summary = json.loads(out[out.index("{"):])
    util = summary["utilization"]
    assert util["admission_reject_rate"] is None
    assert util["step_p99_over_slo"] is None
    assert util["slo_attained"] is None


REPLICATION_KEYS = {"replicas", "kill_replica_at", "kills",
                    "live_replicas", "handoff", "reroute_wait",
                    "handoff_latency", "per_replica", "replica_seconds"}
HANDOFF_KEYS = {"replica_routes", "replica_reroutes", "replica_deaths",
                "replica_handoffs", "handoff_replay_entries",
                "handoff_ef_entries", "handoff_deferred_flushed",
                "replica_syncs", "replica_fenced_waits"}


def test_fleet_sim_replication_schema(monkeypatch, capsys):
    """The ``replication`` block is schema-stable across arms: a
    --replicas 1 run ships the same keys with zeroed handoff counters,
    null latency tails and an empty per-replica list; a chaos-kill run
    ships engaged counters, the surviving router view, and per-replica
    replay detail — so a twin-run diff never branches on shape."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "fleet_sim_repl", os.path.join(REPO, "scripts", "fleet_sim.py"))
    fleet_sim = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fleet_sim)

    # null arm: plain server, nothing killed
    monkeypatch.setattr(sys, "argv", [
        "fleet_sim.py", "--clients", "2", "--steps", "1",
        "--rate", "5.0", "--batch", "4", "--workers", "2"])
    assert fleet_sim.main() == 0
    out = capsys.readouterr().out
    null_arm = json.loads(out[out.index("{"):])["replication"]
    assert set(null_arm) == REPLICATION_KEYS
    assert set(null_arm["handoff"]) == HANDOFF_KEYS
    assert null_arm["replicas"] == 1 and null_arm["kills"] == 0
    assert null_arm["live_replicas"] == [0]
    assert all(v == 0 for v in null_arm["handoff"].values())
    assert null_arm["reroute_wait"] == {"p50_ms": None, "p99_ms": None}
    assert null_arm["handoff_latency"] == {"p50_ms": None,
                                           "p99_ms": None}
    assert null_arm["per_replica"] == []
    # the one bare replica is alive for the whole run
    assert null_arm["replica_seconds"] > 0

    # chaos-kill arm: 2 replicas, kill the busiest mid-run
    monkeypatch.setattr(sys, "argv", [
        "fleet_sim.py", "--clients", "6", "--steps", "2",
        "--rate", "5.0", "--batch", "4", "--workers", "4",
        "--replicas", "2", "--kill-replica-at", "4",
        "--gate-dropped-steps"])
    assert fleet_sim.main() == 0
    out = capsys.readouterr().out
    summary = json.loads(out[out.index("{"):])
    kill_arm = summary["replication"]
    assert set(kill_arm) == REPLICATION_KEYS
    assert set(kill_arm["handoff"]) == HANDOFF_KEYS
    assert kill_arm["replicas"] == 2 and kill_arm["kills"] == 1
    assert len(kill_arm["live_replicas"]) == 1
    assert kill_arm["handoff"]["replica_deaths"] == 1
    assert kill_arm["handoff"]["replica_handoffs"] == 1
    assert kill_arm["handoff"]["replica_routes"] > 0
    assert kill_arm["handoff_latency"]["p50_ms"] is not None
    rows = kill_arm["per_replica"]
    assert [r["replica"] for r in rows] == [0, 1]
    assert sum(r["alive"] for r in rows) == 1
    # per-replica alive windows: the killed one stopped accruing, and
    # the group total is the sum of the per-replica windows
    assert all(r["alive_s"] >= 0 for r in rows)
    assert kill_arm["replica_seconds"] == pytest.approx(
        sum(r["alive_s"] for r in rows), abs=0.01)
    # gate held through the kill: every scheduled step completed
    assert summary["dropped_steps"] == 0
    assert summary["steps_completed"] == summary["steps_expected"]

    # --kill-replica-at without replication is a usage error, not a hang
    monkeypatch.setattr(sys, "argv", [
        "fleet_sim.py", "--clients", "2", "--kill-replica-at", "1"])
    assert fleet_sim.main() == 2


AUTOSCALE_KEYS = {"enabled", "min_replicas", "max_replicas",
                  "cooldown_s", "decisions", "scale_ups", "scale_downs",
                  "events", "replica_seconds",
                  "static_peak_replica_seconds", "peak_replicas",
                  "final_replicas", "p99_ms_trajectory"}


def test_fleet_sim_summary_autoscale_schema(monkeypatch, capsys):
    """The ``autoscale`` block is schema-stable across arms: an elastic
    run ships the policy config, the scale-event log, replica-seconds
    against the static-peak counterfactual and the policy-seen p99
    trajectory; a run without --autoscale ships the same keys with the
    false/empty/null arm — and constructs no policy at all (the
    zero-overhead-off pin)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "fleet_sim_as", os.path.join(REPO, "scripts", "fleet_sim.py"))
    fleet_sim = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fleet_sim)

    # elastic arm: short windows + a fast cooldown so the pump gets
    # several evaluations inside even a tiny run
    monkeypatch.setattr(sys, "argv", [
        "fleet_sim.py", "--clients", "4", "--steps", "2",
        "--rate", "5.0", "--batch", "4", "--workers", "4",
        "--autoscale", "--autoscale-min", "1", "--autoscale-max", "2",
        "--autoscale-cooldown-s", "0.1",
        "--telemetry-interval-s", "0.1"])
    assert fleet_sim.main() == 0
    out = capsys.readouterr().out
    summary = json.loads(out[out.index("{"):])
    block = summary["autoscale"]
    assert set(block) == AUTOSCALE_KEYS
    assert block["enabled"] is True
    assert block["min_replicas"] == 1 and block["max_replicas"] == 2
    assert block["cooldown_s"] == pytest.approx(0.1)
    assert block["decisions"] >= 1
    assert block["replica_seconds"] > 0
    # the counterfactual is peak * run-wall; replica_seconds spans the
    # group's whole lifetime (warmup included), so only sign-check here
    assert block["static_peak_replica_seconds"] > 0
    assert block["peak_replicas"] >= 1
    assert block["final_replicas"] >= 1
    for ev in block["events"]:
        assert set(ev) == {"t_s", "window", "direction", "reason",
                           "replica", "n_live"}
        assert ev["direction"] in ("up", "down")
    # the elastic arm fronts a group even at one replica, so the
    # replication block reports through the router view
    assert summary["replication"]["replicas"] >= 1
    assert summary["config"]["autoscale"] is True

    # null arm: same keys, false/empty/null values — exact dict
    monkeypatch.setattr(sys, "argv", [
        "fleet_sim.py", "--clients", "2", "--steps", "1",
        "--rate", "5.0", "--batch", "4"])
    assert fleet_sim.main() == 0
    out = capsys.readouterr().out
    summary = json.loads(out[out.index("{"):])
    assert summary["autoscale"] == {
        "enabled": False, "min_replicas": None, "max_replicas": None,
        "cooldown_s": None, "decisions": 0, "scale_ups": 0,
        "scale_downs": 0, "events": [], "replica_seconds": None,
        "static_peak_replica_seconds": None, "peak_replicas": None,
        "final_replicas": None, "p99_ms_trajectory": []}
    assert summary["config"]["autoscale"] is False

    # --gate-autoscale without --autoscale is a usage error, not a hang
    monkeypatch.setattr(sys, "argv", [
        "fleet_sim.py", "--clients", "2", "--gate-autoscale"])
    assert fleet_sim.main() == 2


TELEMETRY_KEYS = {"enabled", "interval_s", "windows",
                  "p99_ms_trajectory", "burn_peak", "slo_alerts",
                  "bottleneck_histogram"}


def test_fleet_sim_summary_telemetry_schema(monkeypatch, capsys):
    """scripts/fleet_sim.py's ``telemetry`` block is schema-stable
    across arms: with --telemetry it reports the windowed dispatch-p99
    trajectory, a burn-rate peak against an unattainable SLO and a
    per-window bottleneck histogram; without it the same keys carry
    the false/empty/null arm so twin-run diffs never branch on shape."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "fleet_sim", os.path.join(REPO, "scripts", "fleet_sim.py"))
    fleet_sim = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fleet_sim)

    # telemetry arm: fast windows + a 0.5ms SLO no real step can meet,
    # so the burn pair fires deterministically
    monkeypatch.setattr(sys, "argv", [
        "fleet_sim.py", "--clients", "4", "--steps", "2",
        "--rate", "5.0", "--batch", "4", "--workers", "4",
        "--telemetry", "--telemetry-interval-s", "0.1",
        "--slo-ms", "0.5"])
    assert fleet_sim.main() == 0
    out = capsys.readouterr().out
    block = json.loads(out[out.index("{"):])["telemetry"]
    assert set(block) == TELEMETRY_KEYS
    assert block["enabled"] is True
    assert block["interval_s"] == 0.1
    assert block["windows"] > 0
    assert len(block["p99_ms_trajectory"]) == block["windows"]
    assert any(v is not None for v in block["p99_ms_trajectory"])
    assert block["burn_peak"] is not None and block["burn_peak"] > 1.0
    assert block["bottleneck_histogram"]
    assert set(block["bottleneck_histogram"]) <= {"queue_wait",
                                                 "compute"}
    for alert in block["slo_alerts"]:
        assert alert["state"] in ("firing", "cleared")

    # null arm: same keys, false/empty/null values
    monkeypatch.setattr(sys, "argv", [
        "fleet_sim.py", "--clients", "2", "--steps", "1",
        "--rate", "5.0", "--batch", "4"])
    assert fleet_sim.main() == 0
    out = capsys.readouterr().out
    null_arm = json.loads(out[out.index("{"):])["telemetry"]
    assert null_arm == {"enabled": False, "interval_s": None,
                        "windows": 0, "p99_ms_trajectory": [],
                        "burn_peak": None, "slo_alerts": [],
                        "bottleneck_histogram": {}}


def test_fleet_sim_chaos_twin_loses_no_step_and_compiles_nothing_in_run(
        monkeypatch, capsys):
    """A bursty fleet over a wire that loses replies and duplicates
    deliveries, continuous batching: every scheduled step completes,
    none is dropped, the replay cache really engaged, and the warm-up's
    shape priming left the measured run no program to build (the old
    fleet_soak leg's integrity gates, at 16 clients)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "fleet_sim_chaos", os.path.join(REPO, "scripts", "fleet_sim.py"))
    fleet_sim = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fleet_sim)

    monkeypatch.setattr(sys, "argv", [
        "fleet_sim.py", "--clients", "16", "--tenants", "2",
        "--steps", "2", "--arrival", "burst", "--rate", "5.0",
        "--burst-size", "2", "--batch", "4", "--workers", "8",
        "--batching", "continuous", "--coalesce-max", "4",
        # the schedule is a function of (seed, step, attempt), the same
        # for every client: seed 2 leaves step 0 (and the warm-up's
        # un-retried shape priming) clean and loses every client's first
        # reply of step 1
        "--chaos", "--chaos-spec", "drop_resp=0.3,dup=0.3",
        "--chaos-seed", "2", "--gate-dropped-steps"])
    assert fleet_sim.main() == 0
    out = capsys.readouterr().out
    summary = json.loads(out[out.index("{"):])
    assert summary["config"]["chaos"] is True
    assert summary["steps_completed"] == summary["steps_expected"] == 32
    assert summary["dropped_steps"] == 0
    assert summary["replay"]["replay_hits"] > 0
    assert summary["compiles_in_run"] == 0


def test_fleet_sim_elastic_fleet_scales_up_and_loses_no_step(
        monkeypatch, capsys):
    """An elastic group under bursts it cannot serve inside its SLO (0.5
    ms: every window with traffic breaches it) scales up at least once,
    stays inside its bounds, and completes every scheduled step with
    none dropped through the replicas it adds and retires (the old
    autoscale_diurnal leg's integrity gates; its p99 and replica-second
    comparisons were timings)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "fleet_sim_elastic", os.path.join(REPO, "scripts", "fleet_sim.py"))
    fleet_sim = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fleet_sim)

    monkeypatch.setattr(sys, "argv", [
        "fleet_sim.py", "--clients", "8", "--steps", "6",
        "--arrival", "burst", "--rate", "1.0", "--burst-size", "3",
        "--batch", "4", "--workers", "8", "--autoscale",
        "--autoscale-min", "1", "--autoscale-max", "3",
        "--autoscale-cooldown-s", "0.2", "--telemetry-interval-s", "0.3",
        "--slo-ms", "0.5", "--gate-dropped-steps"])
    assert fleet_sim.main() == 0
    out = capsys.readouterr().out
    summary = json.loads(out[out.index("{"):])
    assert summary["steps_completed"] == summary["steps_expected"] == 48
    assert summary["dropped_steps"] == 0
    block = summary["autoscale"]
    assert block["scale_ups"] >= 1
    assert 1 <= block["final_replicas"] <= block["peak_replicas"] <= 3
