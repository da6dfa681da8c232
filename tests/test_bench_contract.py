"""bench.py contract: the driver parses exactly one JSON line
{"metric", "value", "unit", "vs_baseline"} from stdout. A broken bench
means an unscored round, so the contract gets its own test (hermetic: the
subprocesses inherit this env's CPU-forced JAX)."""

import json
import os
import subprocess
import sys
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_bench_quick_prints_contract_json():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--quick"],
        capture_output=True, text=True, timeout=900, cwd=REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    json_lines = [l for l in out.stdout.splitlines()
                  if l.strip().startswith("{")]
    assert len(json_lines) == 1, out.stdout
    rec = json.loads(json_lines[0])
    assert rec["metric"] == "mnist_split_cnn_steps_per_sec"
    assert rec["unit"] == "steps/sec"
    assert rec["value"] and rec["value"] > 0
    assert rec["vs_baseline"] and rec["vs_baseline"] > 1
    # the fused leg in the detail line must have passed the publication
    # gate: physically possible throughput + work-scaling timed window
    detail_lines = [l for l in out.stderr.splitlines()
                    if l.startswith("[bench] detail:")]
    assert detail_lines, out.stderr[-2000:]
    fused = json.loads(detail_lines[0].split("detail:", 1)[1])["fused"]
    assert fused["valid"] is True
    util = fused.get("util_vs_bf16_peak")
    assert util is None or util <= 1.0
    assert 1.5 <= fused["linearity_2x"] <= 2.6


@pytest.mark.slow
def test_bench_wire_and_pipelined_roles_quick():
    """The side legs the orchestrator adds in non-quick runs must at
    least produce their contract fields (run here in quick mode,
    in-process on the CPU-forced test env)."""
    sys.path.insert(0, REPO)
    from bench import measure_pipelined, measure_wire

    wire = measure_wire(quick=True)
    assert wire["valid"] and wire["byte_reduction"] > 3.5
    assert wire["p50_ms_none"] > 1.0 and wire["p50_ms_int8"] > 1.0

    piped = measure_pipelined(quick=True)
    assert piped["valid"]
    assert piped["steps_per_sec_sync"] > 0
    assert piped["steps_per_sec_depth4"] > 0
    assert "note" in piped  # the shared-core caveat must ship with the leg
    # the depth-W window's benefit, demonstrated: with wire latency
    # injected (sleeps burn no CPU, so one core suffices) the window
    # hides the wire behind compute, which the lock-step loop cannot.
    # Loose bound: quick mode times only 6 steps and the image's CPU
    # timing is load-sensitive; the full bench leg (20 steps) publishes
    # the real figure (~1.5x).
    syn_wire = piped["synthetic_wire"]
    assert syn_wire["pipelining_speedup"] > 1.1, syn_wire
    assert "synthetic" in syn_wire["note"]


@pytest.mark.slow
def test_bench_topk8_role_quick():
    """The wire_topk8 leg's contract fields (satellite of the sparse
    error-feedback compression PR): per-mode bytes/step and losses, the
    two byte-reduction ratios against the gates the full leg publishes
    (>=8x vs fp32, >=2.5x vs int8), and loss_parity. The parity gate
    itself only binds the 300-step full leg — 40 quick steps end
    mid-descent — so quick mode must still gate bytes but not parity."""
    sys.path.insert(0, REPO)
    from bench import measure_topk8

    tk = measure_topk8(quick=True)
    assert tk["leg"] == "wire_topk8"
    assert tk["density"] == 0.1
    for mode in ("none", "int8", "topk8"):
        assert tk[f"bytes_per_step_{mode}"] > 0
        assert tk[f"final_loss_{mode}"] > 0
        assert tk[f"steps_per_sec_{mode}"] > 0
    assert tk["bytes_per_step"] == tk["bytes_per_step_topk8"]
    assert tk["byte_reduction_vs_fp32"] >= 8.0
    assert tk["byte_reduction_vs_int8"] >= 2.5
    assert tk["loss_parity"] >= 0.0
    # the byte gates bind even in quick mode: a broken encoder (say, the
    # bitmap path regressing to int32 indices) must fail here, not only
    # in the 15-minute full leg
    assert tk["valid"] is True, tk["invalid_reason"]
    assert "synthetic-wire" in tk["platform"]
    # dispatch watchdog rode along: the leg compiled its jits once and
    # never retraced in steady state (gated into valid above)
    cc = tk["compile_count"]
    assert cc["total"] >= 1
    assert cc["steady_state"] == 0


@pytest.mark.slow
def test_bench_coalesced_compile_count_quick():
    """The multi_client_coalesced leg publishes per-leg compile counts
    from the dispatch watchdog (obs/dispatch_debug.py, forced in-process
    for the timed runs) and gates steady-state recompiles at 0 — the
    pow2-padded group signatures must hold across every occupancy."""
    sys.path.insert(0, REPO)
    from bench import measure_coalesced

    co = measure_coalesced(quick=True)
    assert co["leg"] == "multi_client_coalesced"
    cc = co["compile_count"]
    assert cc["total"] >= 1
    assert cc["steady_state"] == 0
    assert co["valid"] is True, co["invalid_reason"]


@pytest.mark.slow
def test_bench_chaos_soak_role_quick():
    """The chaos_soak leg's contract fields (robustness PR): trains the
    same seeded stream clean and under a seeded drop_resp/dup/http500
    schedule, and must report zero dropped batches, engaged replay
    cache, injected faults, and (exactly-once being deterministic) a
    loss parity that binds even in quick mode."""
    sys.path.insert(0, REPO)
    from bench import measure_chaos_soak

    soak = measure_chaos_soak(quick=True)
    assert soak["leg"] == "chaos_soak"
    assert soak["platform"] == "cpu"
    assert soak["chaos_spec"] and soak["chaos_seed"] is not None
    assert soak["dropped_batches"] == 0
    assert sum(soak["chaos_injected"].values()) > 0
    assert soak["replay_hits"] > 0
    for run in ("clean", "chaos"):
        assert soak[f"final_loss_{run}"] > 0
        assert soak[f"steps_per_sec_{run}"] > 0
    assert soak["loss_parity"] <= 0.05
    assert soak["max_step_loss_diff"] >= 0.0
    assert soak["valid"] is True, soak["invalid_reason"]


@pytest.mark.slow
def test_bench_fleet_soak_role_quick():
    """The fleet_soak leg's contract fields (continuous batching PR):
    one seeded bursty arrival schedule offered to window, continuous,
    and chaos-wrapped-continuous twins. Gates: every scheduled step
    completes, continuous p99 pooled queue-wait beats window, the
    measured runs see zero XLA compiles (warm_fleet shape priming), and
    the chaos twin's loss stays with its clean twin."""
    sys.path.insert(0, REPO)
    from bench import measure_fleet_soak

    fs = measure_fleet_soak(quick=True)
    assert fs["leg"] == "fleet_soak"
    assert fs["clients"] >= 64 and fs["tenants"] >= 2
    expected = fs["clients"] * fs["steps_per_client"]
    for tag in ("window", "continuous", "chaos_twin"):
        rec = fs[tag]
        assert rec["steps_completed"] == expected
        assert rec["dropped_steps"] == 0
        assert rec["compiles_in_run"] == 0
        assert rec["steady_state_recompiles"] == 0
        assert rec["overall"]["queue_wait_p99_ms"] > 0
        assert rec["mean_occupancy"] >= 1.0
        assert len(rec["per_tenant"]) == fs["tenants"]
    assert (fs["queue_wait_p99_ms_continuous"]
            < fs["queue_wait_p99_ms_window"])
    assert fs["chaos_twin"]["replay"]["replay_hits"] > 0
    assert fs["loss_parity"] <= 0.05  # absolute nats (the leg's own gate)
    assert fs["valid"] is True, fs["invalid_reason"]


@pytest.mark.slow
def test_bench_reply_latency_2bp_role_quick():
    """The reply_latency_2bp leg's contract fields (2BP PR): 4
    free-running clients over heterogeneous synthetic wires against a
    coupled vs decoupled server. Gates carried by the leg itself:
    decoupled reply p50 <= 0.7x coupled, lag=0 bit-identity, lag=2
    staleness within the stated nats budget, zero steady-state
    recompiles across both decoupled programs."""
    sys.path.insert(0, REPO)
    from bench import measure_reply_latency_2bp

    rl = measure_reply_latency_2bp(quick=True)
    assert rl["leg"] == "reply_latency_2bp"
    assert rl["clients"] == 4
    assert rl["apply_lag"] == 2
    assert rl["model"]["lm"] is True and rl["model"]["vocab"] >= 1024
    assert len(rl["one_way_latency_ms"]) == rl["clients"]
    assert rl["reply_p50_ms_coupled"] > 0
    assert rl["reply_p50_ms_decoupled"] > 0
    assert rl["reply_p50_ratio"] <= 0.7
    assert rl["reply_p90_ms_coupled"] >= rl["reply_p50_ms_coupled"]
    assert rl["reply_p90_ms_decoupled"] >= rl["reply_p50_ms_decoupled"]
    assert rl["loss_lag0_max_abs_diff"] == 0.0
    assert rl["loss_lag2_staleness_nats"] <= rl["nats_budget"]
    ctr = rl["decoupled_counters"]
    assert ctr["deferred_enqueued"] > 0
    assert ctr["deferred_applied"] + ctr["deferred_apply_depth"] == \
        ctr["deferred_enqueued"]
    assert rl["compile_count"]["steady_state"] == 0
    assert rl["valid"] is True, rl["invalid_reason"]


@pytest.mark.slow
def test_bench_sharded_server_role_quick():
    """The sharded_server leg's contract fields (pjit PR): 8 concurrent
    clients against data=1 vs data=2 coalescing servers at the same
    per-device row ceiling, on the conftest-forced 8-device topology.
    Gates carried by the leg itself: mesh=1 bit-identity, data=2 float
    parity, data=2 strictly-higher throughput at strictly-higher group
    occupancy, zero steady-state recompiles, and the mesh/MFU metadata
    present with MFU honestly None on the CPU backend."""
    sys.path.insert(0, REPO)
    from bench import measure_sharded_server

    sh = measure_sharded_server(quick=True)
    assert sh["leg"] == "sharded_server"
    assert sh["valid"] is True, sh["invalid_reason"]
    assert sh["batch_ceiling_relative"] is True
    assert "ceiling" in sh["note"]  # the honesty caveat ships with the leg
    assert sh["mesh"]["devices"] == 2 and sh["mesh"]["data"] == 2
    assert sh["coalesce_max"]["data2"] == 2 * sh["coalesce_max"]["data1"]
    assert sh["steps_per_sec_data2"] > sh["steps_per_sec_data1"] > 0
    assert sh["mean_occupancy_data2"] > sh["mean_occupancy_data1"]
    assert sh["loss_mesh1_max_abs_diff"] == 0.0
    assert sh["loss_data2_max_abs_diff"] <= sh["parity_tol"]
    assert sh["compile_count"]["steady_state"] == 0
    assert sh["gather_bytes"] > 0
    assert sh["peak_flops_per_device"] is None  # CPU: unknown, never 0
    progs = sh["programs"]
    assert progs and all(p["calls"] >= 1 and p["mfu"] is None
                         for p in progs.values())


def test_validate_leg_gates_impossible_throughput():
    """The round-1/2 failure mode — a steps/sec figure above chip peak —
    must be refused, whether the peak is known (util>1) or not (absolute
    TFLOP/s bound); a dispatch-only timer must be caught by linearity."""
    sys.path.insert(0, REPO)
    from bench import validate_leg

    ok, reason = validate_leg({"util_vs_bf16_peak": 0.10,
                               "model_tflops_per_sec": 20.0,
                               "linearity_2x": 1.9})
    assert ok and reason is None

    # round-2's actual artifact: 60.5x peak
    ok, reason = validate_leg({"util_vs_bf16_peak": 60.53,
                               "model_tflops_per_sec": 11925.0,
                               "linearity_2x": 1.9})
    assert not ok and "peak" in reason

    # unknown peak (CPU): absolute bound
    ok, reason = validate_leg({"util_vs_bf16_peak": None,
                               "model_tflops_per_sec": 500.0,
                               "linearity_2x": 2.0})
    assert not ok and "5 TFLOP/s" in reason

    # dispatch-only timer: doubling the work doesn't double the window
    ok, reason = validate_leg({"util_vs_bf16_peak": 0.5,
                               "model_tflops_per_sec": 1.0,
                               "linearity_2x": 1.02})
    assert not ok and "linearity" in reason


def test_device_role_refuses_a_cpu_it_did_not_ask_for():
    """JAX falls back to the CPU without an error when the TPU runtime
    does not come up; a device role must refuse to measure there unless
    the caller named the CPU itself (JAX_PLATFORMS=cpu, as this suite
    does)."""
    import bench

    assert bench.device_role_refusal("tpu", None) is None
    assert bench.device_role_refusal("tpu", "tpu") is None
    assert bench.device_role_refusal("cpu", "cpu") is None
    for asked in (None, "", "tpu", "tpu,cpu"):
        assert "refusing" in bench.device_role_refusal("cpu", asked)

    # end to end: with JAX_PLATFORMS unset the role either found a chip
    # or exited non-zero without printing a record
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"),
         "--role", "fused", "--quick"],
        capture_output=True, text=True, timeout=600, cwd=REPO, env=env)
    if out.returncode == 0:
        assert json.loads(out.stdout.splitlines()[-1])["platform"] == "tpu"
    else:
        assert "refusing to measure" in out.stderr, out.stderr[-2000:]
        assert not out.stdout.strip()


def test_grow_window_clears_timing_floor():
    """The fused role's timed window must dwarf the fixed per-window
    close-out cost (the 2026-07-31 quick CNN leg timed 0.07 s windows
    and failed its linearity gate at 1.37): grow_window doubles the
    chunk count until a *measured* window clears the floor."""
    import bench

    calls = []

    def fake_window(n):  # 50 ms fixed cost + 20 ms/chunk "compute"
        calls.append(n)
        return 0.05 + 0.02 * n, 0.0

    n = bench.grow_window(fake_window, 2, floor_s=1.0)
    assert n == 64                      # 0.05 + 1.28 s clears the floor
    assert calls == [2, 4, 8, 16, 32, 64]
    # an already-long window is left alone
    assert bench.grow_window(lambda n: (5.0, 0.0), 4, floor_s=1.0) == 4
    # the cap bounds pathological growth
    assert bench.grow_window(lambda n: (0.0, 0.0), 2, floor_s=1.0,
                             cap=16) == 16


def test_bench_d_model_guard(monkeypatch):
    """SLT_BENCH_DMODEL must be a multiple of 128: heads scale with
    width so head_dim stays the 128-lane tile the recorded flash_block
    is resolved for — a non-multiple would silently benchmark a
    different kernel shape than the record describes."""
    sys.path.insert(0, REPO)
    from bench import _bench_d_model
    monkeypatch.delenv("SLT_BENCH_DMODEL", raising=False)
    assert _bench_d_model() == 256
    monkeypatch.setenv("SLT_BENCH_DMODEL", "1024")
    assert _bench_d_model() == 1024
    monkeypatch.setenv("SLT_BENCH_DMODEL", "320")
    with pytest.raises(SystemExit):
        _bench_d_model()


def test_transformer_trunk_kwargs_contract(monkeypatch):
    """The shared trunk builder (bench.transformer_trunk_kwargs) is
    what both the legs and the profiler build from: heads must scale
    with width so head_dim stays the 128-lane tile, and the max_len
    floor must track the seq knob."""
    import numpy as np
    sys.path.insert(0, REPO)
    from bench import transformer_trunk_kwargs
    monkeypatch.delenv("SLT_BENCH_DMODEL", raising=False)
    monkeypatch.delenv("SLT_BENCH_SEQ", raising=False)
    kw = transformer_trunk_kwargs("split", "bfloat16")
    assert kw["d_model"] == 256 and kw["num_heads"] == 2
    assert kw["d_model"] // kw["num_heads"] == 128
    assert kw["max_len"] == 2048
    assert kw["dtype"] == np.dtype("bfloat16")
    monkeypatch.setenv("SLT_BENCH_DMODEL", "1024")
    monkeypatch.setenv("SLT_BENCH_SEQ", "8192")
    kw = transformer_trunk_kwargs("split", "float32")
    assert kw["num_heads"] == 8 and kw["d_model"] // kw["num_heads"] == 128
    assert kw["max_len"] == 8192


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


@pytest.mark.slow
def test_bench_replica_failover_role_quick():
    """The replica_failover side leg (in-process, quick): twin
    3-replica groups, one chaos-killed mid-run — the contract fields
    the orchestrator publishes plus every gate it enforces."""
    sys.path.insert(0, REPO)
    from bench import measure_replica_failover

    rec = measure_replica_failover(quick=True)
    assert rec["valid"], rec["invalid_reason"]
    assert rec["replicas_one_bit_identical"] is True
    expected = rec["clients"] * rec["steps_per_client"]
    for tag in ("clean", "killed"):
        assert rec[tag]["steps_completed"] == expected
        assert rec[tag]["dropped_steps"] == 0
        assert rec[tag]["steady_state_recompiles"] == 0
    assert rec["clean"]["kills"] == 0
    assert rec["killed"]["kills"] == 1
    assert rec["killed"]["replica_handoffs"] == 1
    assert rec["killed"]["handoff_replay_entries"] > 0
    assert rec["killed"]["replica_reroutes"] > 0
    assert len(rec["killed"]["live_replicas"]) == rec["replicas"] - 1
    assert rec["loss_parity"] <= 0.25


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


@pytest.mark.slow
def test_bench_autoscale_diurnal_role_quick():
    """The autoscale_diurnal side leg (in-process, quick): static-peak
    vs elastic twins over one seeded diurnal schedule — the contract
    fields the orchestrator publishes plus every gate it enforces."""
    sys.path.insert(0, REPO)
    from bench import measure_autoscale_diurnal

    rec = measure_autoscale_diurnal(quick=True)
    assert rec["valid"], rec["invalid_reason"]
    expected = rec["clients"] * rec["steps_per_client"]
    for tag in ("static", "elastic"):
        assert rec[tag]["steps_completed"] == expected
        assert rec[tag]["dropped_steps"] == 0
    assert rec["static"]["scale_ups"] == 0
    assert rec["elastic"]["scale_ups"] >= 1
    assert rec["elastic"]["settled_p99_ms"] is not None
    assert rec["elastic"]["settled_p99_ms"] <= rec["slo_ms"]
    assert rec["elastic"]["replica_seconds"] < \
        rec["static"]["replica_seconds"]
    assert rec["replica_seconds_saved"] > 0


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


@pytest.mark.slow
def test_bench_fleet_telemetry_role_quick():
    """bench.py --role fleet_telemetry --quick end to end: the
    telemetry-on twin stays inside the 2% steps/sec budget, the
    critical path pins the synthetic-slow middle stage in >=90% of
    warm windows, the 3-replica burn pair fires against an
    unattainable SLO, and per-replica labeled series render."""
    sys.path.insert(0, REPO)
    from bench import measure_fleet_telemetry
    r = measure_fleet_telemetry(quick=True)

    assert r["leg"] == "fleet_telemetry"
    assert r["stages"] == 3 and r["replicas"] == 3

    ov = r["telemetry_overhead"]
    assert set(ov) == {"steps_per_sec_off", "steps_per_sec_on",
                       "overhead_frac", "budget_frac"}
    assert ov["steps_per_sec_off"] > 0 and ov["steps_per_sec_on"] > 0

    attr = r["attribution"]
    assert attr["slow_party"] == "stage1"
    assert attr["windows_attributed"] > 0
    assert attr["accuracy"] >= attr["accuracy_floor"] == 0.9
    assert attr["bottleneck_histogram"].get("stage1", 0) > 0

    burn = r["slo_burn"]
    assert burn["fired"] is True
    assert burn["windows"] > 0
    assert any(a["state"] == "firing" for a in burn["alerts"])

    assert r["per_replica_labeled_series"] > 0

    # the only tolerated invalidity is steps/sec noise on a loaded
    # box; every deterministic gate above must hold regardless
    if not r["valid"]:
        assert "slower than off" in (r["invalid_reason"] or "")


@pytest.mark.slow
def test_bench_mpmd_compressed_role_quick():
    """bench.py --role mpmd_compressed --quick end to end: dense vs
    topk8 vs clapping over real HTTP loopback hop wires. Both
    compressed modes must cut hop bytes >=10x AND hold end loss inside
    the absolute-nats budget through their own wire; clapping's extras
    must be ledger-free while topk8's carry one; and the packed payload
    shapes must be dispatch-stable (zero steady-state recompiles)."""
    sys.path.insert(0, REPO)
    from bench import measure_mpmd_compressed
    r = measure_mpmd_compressed(quick=True)

    assert r["leg"] == "mpmd_compressed"
    assert r["stages"] == 3 and r["microbatches"] == 4
    for mode in ("dense", "topk8", "clapping"):
        assert r["hop_wire_bytes"][mode] > 0
    for mode in ("topk8", "clapping"):
        assert r["hop_byte_reduction"][mode] >= 10.0
        assert r["loss_parity_nats"][mode] <= r["nats_budget"]
    assert r["clapping_extras_ledger_free"] is True
    assert r["topk8_extras_carry_ledger"] is True
    assert r["steady_state_recompiles"] == 0
    assert r["valid"] is True, r["invalid_reason"]


def test_bench_composed_topology_role_quick():
    """The composed_topology leg's contract fields (composable party
    runtime): a 3-stage chain whose middle stage runs a data=2 pjit
    mesh vs the flat twin at the same per-device rows-per-microbatch
    ceiling, plus a replicated (N=2) x sharded x 3-stage run with a
    mid-run replica kill. Gates carried by the leg itself: mesh=1
    bit-identity, data=2 float parity, a strict throughput win for the
    sharded chain, zero dropped steps with >= 1 handoff across the
    kill, zero steady-state recompiles, and the stage_report mesh
    column reporting the sharded axis (MFU honestly None on CPU)."""
    sys.path.insert(0, REPO)
    from bench import measure_composed_topology

    r = measure_composed_topology(quick=True)
    assert r["leg"] == "composed_topology"
    assert r["valid"] is True, r["invalid_reason"]
    assert r["stages"] == 3
    assert r["batch_ceiling_relative"] is True
    assert "ceiling" in r["note"]  # the honesty caveat ships with the leg
    assert r["mesh"]["devices"] == 2 and r["mesh"]["data"] == 2
    # same 16-row step either way: data=2 admits double-size microbatches
    assert r["microbatches"]["data1"] == 2 * r["microbatches"]["data2"]
    assert r["steps_per_sec_data2"] > r["steps_per_sec_data1"] > 0
    assert r["speedup_data2_vs_data1"] > 1.0
    assert r["loss_mesh1_max_abs_diff"] == 0.0
    assert r["loss_data2_max_abs_diff"] <= r["parity_tol"]
    assert (r["replicated_steps_completed"]
            == r["replicated_steps_expected"])
    assert r["replica_handoffs"] >= 1
    assert r["compile_count"]["steady_state"] == 0
    rep = {row["stage"]: row for row in r["stage_report_data2"]}
    assert rep[1]["mesh"]["data"] == 2 and rep[1]["mfu"] is None
    assert rep[2]["mesh"]["data"] == 1
