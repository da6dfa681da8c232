"""Compressed hop wires on the K-stage chain (PR 18).

Pins, in order: compression OFF leaves the chain bit-for-bit on the
legacy wire (both the untouched passthrough and the dense fp32 wire
emulation); a topk8 chain at moderate density stays within a loose
absolute-nats budget of the dense twin while the per-hop byte
accounting shows up in transport stats, stage gauges and the runner's
stage report; Clapping mode is the SAME arithmetic as topk8 (identical
loss series) differing only in persistence (no wire_ef in extras); a
chaos-corrupted compressed hop reply over a REAL HTTP chain surfaces
as the typed retry path — CRC gate or codec validation, never a
silently wrong gradient — and the replayed retry keeps the run
bit-identical to its clean twin; and the adaptive density controller
is a pure function of its note schedule: same feed → same trajectory,
end to end through two identically-seeded chain runs.
"""

import jax
import numpy as np
import pytest

from split_learning_tpu.models import get_plan
from split_learning_tpu.runtime.pipeline_runner import PipelineRunner
from split_learning_tpu.runtime.stage import StageRuntime
from split_learning_tpu.transport import codec
from split_learning_tpu.transport.chaos import ChaosPolicy
from split_learning_tpu.transport.density import (
    DENSITY_LADDER, DensityController)
from split_learning_tpu.transport.http import HttpTransport, SplitHTTPServer
from split_learning_tpu.transport.local import LocalTransport
from split_learning_tpu.utils import Config

BATCH = 8
SEED = 2


def _cfg(microbatches, batch=BATCH):
    return Config(mode="split", model="split_cnn_chain3",
                  batch_size=batch, num_stages=3,
                  microbatches=microbatches, seed=SEED)


def _chain(microbatches, apply_lag, batch=BATCH, compress=None,
           density=0.25, ef_mode="topk8", density_controller=None,
           wire_ids=False):
    """One 3-stage chain over LocalTransport with optional wire
    compression — the launch path's local-chain construction."""
    cfg = _cfg(microbatches, batch)
    plan = get_plan(model="split_cnn_chain3", mode="split")
    sample = np.zeros((batch, 28, 28, 1), np.float32)
    stages = [StageRuntime(plan, i, cfg, jax.random.PRNGKey(SEED),
                           sample, microbatches=microbatches,
                           apply_lag=apply_lag, ef_mode=ef_mode)
              for i in (1, 2)]
    transports = [
        LocalTransport(s, compress=compress, density=density,
                       ef_mode=ef_mode,
                       density_controller=density_controller,
                       wire_id=(f"hop{i + 1}" if wire_ids else None))
        for i, s in enumerate(stages)]
    runner = PipelineRunner(plan, cfg, jax.random.PRNGKey(SEED), sample,
                            transports, microbatches=microbatches)
    runner.density_controller = density_controller
    return runner, stages, transports


def _close(runner, stages):
    runner.close()
    for s in stages:
        s.close()


def _batch(i, batch=BATCH):
    rs = np.random.RandomState(100 + i)
    return (rs.rand(batch, 28, 28, 1).astype(np.float32),
            rs.randint(0, 10, batch).astype(np.int64))


def _big_batches(n=4, batch=32):
    # batch 32: trajectory comparisons on an oscillating tiny-batch
    # series would measure noise, not the codec (test_mpmd_pipeline's
    # convention)
    rs = np.random.RandomState(0)
    return [(rs.rand(batch, 28, 28, 1).astype(np.float32),
             rs.randint(0, 10, batch).astype(np.int64))
            for _ in range(n)]


def _run(runner, steps, batches):
    return [runner.step(*batches[i % len(batches)], i)
            for i in range(steps)]


# ---------------------------------------------------------------------- #
# compression off: the legacy wire, bit for bit
# ---------------------------------------------------------------------- #

def test_compress_off_is_bitwise_legacy():
    """compress=None (untouched passthrough) and compress="none" (the
    dense fp32 wire emulation — encode → decode, no sparsify) both
    produce the identical loss series: turning the feature off leaves
    the PR-16 chain wire exactly as it was."""
    steps, M = 4, 2
    series = {}
    for mode in (None, "none"):
        runner, stages, _ = _chain(M, 1, compress=mode)
        try:
            series[mode] = _run(runner, steps, [_batch(i)
                                                for i in range(4)])
        finally:
            _close(runner, stages)
    assert series[None] == series["none"]


# ---------------------------------------------------------------------- #
# topk8 parity + the per-hop byte accounting surface
# ---------------------------------------------------------------------- #

def test_topk8_chain_parity_and_accounting():
    """A topk8 chain at density 0.3 converges with the dense twin
    (loose absolute-nats budget)
    and every accounting surface lights up: the transports' raw/wire
    compression counters, each stage's wire_compression_ratio gauge,
    and the runner's per-stage report rows."""
    steps, M = 12, 4
    batches = _big_batches()
    runner_d, stages_d, _ = _chain(M, 1, batch=32, compress=None)
    try:
        dense = _run(runner_d, steps, batches)
    finally:
        _close(runner_d, stages_d)
    runner_c, stages_c, ts = _chain(M, 1, batch=32, compress="topk8",
                                    density=0.3)
    try:
        comp = _run(runner_c, steps, batches)
        gap = abs(float(np.mean(comp[-4:])) - float(np.mean(dense[-4:])))
        assert gap <= 0.6, (gap, comp, dense)
        for t in ts:
            summ = t.stats.summary()
            assert summ["compress_raw_bytes"] > summ["compress_wire_bytes"] > 0
            assert summ["compression_ratio"] > 3.0
        for s in stages_c:
            snap = s.metrics()
            assert snap["gauges"]["wire_compression_ratio"] > 3.0
        rows = runner_c.stage_report()
        for row in rows:
            assert row["compression_ratio"] > 3.0
            assert row["compress_wire_bytes"] > 0
    finally:
        _close(runner_c, stages_c)


@pytest.mark.parametrize("mode", ["topk8", "clapping"])
def test_compressed_hops_are_an_order_of_magnitude_lighter(mode):
    """At density 0.25, the knee the chain is run at, a quarter of the
    values as int8 plus a bit a value for the mask is 4 / (0.25 + 0.125)
    = 10.7 times fewer bytes than float32: every hop's framed bytes,
    request and reply together as the transport counts them, stay 10
    times under what it would have sent raw, in both sparse modes (the
    old mpmd_compressed leg's byte gate)."""
    runner, stages, transports = _chain(4, 1, compress=mode, ef_mode=mode)
    try:
        _run(runner, 3, [_batch(i) for i in range(3)])
        for t in transports:
            raw = t.stats.summary()["compress_raw_bytes"]
            framed = t.stats.bytes_sent + t.stats.bytes_received
            assert 0 < framed * 10 <= raw, (mode, raw, framed)
    finally:
        _close(runner, stages)


def test_clapping_is_topk8_arithmetic_without_the_ledger():
    """Clapping (arXiv:2509.19029 storage-free EF) changes persistence,
    not math: the in-run loss series is BIT-identical to topk8's, but a
    clapping stage's extras sidecar carries no wire_ef entry at all
    (nothing to migrate on a PR-15 handoff) while topk8's does."""
    steps, M = 4, 2
    out = {}
    for mode in ("topk8", "clapping"):
        runner, stages, _ = _chain(M, 1, compress=mode, ef_mode=mode)
        try:
            losses = _run(runner, steps, [_batch(i) for i in range(4)])
            extras = [s.export_runtime_extras(steps) for s in stages]
        finally:
            _close(runner, stages)
        out[mode] = (losses, extras)
    assert out["topk8"][0] == out["clapping"][0]
    assert all("wire_ef" in e for e in out["topk8"][1])
    assert all("wire_ef" not in e for e in out["clapping"][1])


# ---------------------------------------------------------------------- #
# chaos corrupt on a compressed hop: typed refusal, never a wrong grad
# ---------------------------------------------------------------------- #

def test_chaos_corrupt_on_compressed_http_chain_is_exactly_once():
    """Server-side ``corrupt`` faults on a REAL compressed HTTP chain:
    the CRC-sabotaged replies are refused by the client's checksum gate
    (typed TransportError, the retry path), the bounded hop retry
    re-collects the ORIGINAL frame from the replay cache, and the loss
    series is bit-identical to the fault-free twin — at no point does a
    corrupted compressed payload decode into a silently wrong
    gradient."""
    steps, M, density = 4, 2, 0.25

    def http_chain(policy):
        cfg = _cfg(M)
        plan = get_plan(model="split_cnn_chain3", mode="split")
        sample = np.zeros((BATCH, 28, 28, 1), np.float32)
        stages = [StageRuntime(plan, i, cfg, jax.random.PRNGKey(SEED),
                               sample, microbatches=M, apply_lag=1,
                               ef_mode="topk8")
                  for i in (1, 2)]
        servers = [SplitHTTPServer(s, compress="topk8", density=density,
                                   chaos=policy).start()
                   for s in stages]
        ts = [HttpTransport(srv.url, compress="topk8", density=density)
              for srv in servers]
        runner = PipelineRunner(plan, cfg, jax.random.PRNGKey(SEED),
                                sample, ts, microbatches=M)
        return runner, stages, servers

    runner_c, stages_c, servers_c = http_chain(None)
    try:
        clean = _run(runner_c, steps, [_batch(i) for i in range(4)])
    finally:
        _close(runner_c, stages_c)
        for srv in servers_c:
            srv.stop()

    policy = ChaosPolicy("corrupt=0.5", seed=3)
    runner_x, stages_x, servers_x = http_chain(policy)
    try:
        chaotic = _run(runner_x, steps, [_batch(i) for i in range(4)])
        assert chaotic == clean
        assert policy.injected.get("corrupt", 0) > 0
        # the refused frames were re-served from the replay cache as
        # the ORIGINAL bytes — the server never re-applied, the client
        # never re-packed into a drifted EF ledger
        assert sum(s.counters()["replay_body_hits"]
                   for s in stages_x) > 0
        for s in stages_x:
            ctr = s.counters()
            ops = (("hop_fwd", "hop_bwd") if not s.is_last
                   else ("hop_loss",))
            for op in ops:
                assert ctr[op] == steps * M, (s.party, op, ctr)
    finally:
        _close(runner_x, stages_x)
        for srv in servers_x:
            srv.stop()


def test_corrupt_compressed_payload_is_typed_codec_error():
    """A packed topk8 frame that passes transport framing but fails
    codec validation (truncated bitmap, out-of-range index, bad count)
    raises the typed CodecError — the one exception class the HTTP
    client maps to the TransportError retry path — rather than
    decoding into a wrong-shaped or wrong-valued tensor."""
    rs = np.random.RandomState(0)
    packed, _ = codec.topk8_compress(
        rs.randn(64, 64).astype(np.float32), 0.1)
    bad_count = dict(packed, n=-1)
    with pytest.raises(codec.CodecError):
        codec.topk8_decompress(bad_count)
    if "idx" in packed:
        sab = dict(packed, idx=np.array([10 ** 6], np.int32))
    else:
        sab = dict(packed, m=packed["m"][:1])
    with pytest.raises(codec.CodecError):
        codec.topk8_decompress(sab)
    # and through the tree walker the caller actually uses
    with pytest.raises(codec.CodecError):
        codec.decompress_tree({"grads": sab})


# ---------------------------------------------------------------------- #
# the adaptive density controller: deterministic by construction
# ---------------------------------------------------------------------- #

def test_density_controller_validation_and_ladder():
    with pytest.raises(ValueError):
        DensityController(window=0)
    with pytest.raises(ValueError):
        DensityController(ladder=(0.1, 0.2))  # not decreasing
    with pytest.raises(ValueError):
        DensityController(start_rung=99)
    dc = DensityController()
    assert dc.density("hop1") == DENSITY_LADDER[2] == 0.1


def test_density_controller_decision_rule():
    """First window is baseline only; a drift above budget loosens
    every wire one rung; slack tightens exactly the least-compressing
    wire."""
    dc = DensityController(window=2, budget_nats=0.05)
    for wire in ("hop1", "hop2"):
        dc.density(wire)
    # window 1: baseline at mean 1.0
    dc.note_ratio("hop1", 1000, 100)   # 10x
    dc.note_ratio("hop2", 1000, 250)   # 4x — the worst compressor
    dc.note_loss(1.0)
    dc.note_loss(1.0)
    assert dc.densities() == {"hop1": 0.1, "hop2": 0.1}
    # window 2: flat loss => tighten hop2 (lowest achieved ratio)
    dc.note_ratio("hop1", 1000, 100)
    dc.note_ratio("hop2", 1000, 250)
    dc.note_loss(1.0)
    dc.note_loss(1.0)
    assert dc.densities() == {"hop1": 0.1, "hop2": 0.05}
    # window 3: loss blows the budget => every wire loosens one rung
    dc.note_loss(2.0)
    dc.note_loss(2.0)
    assert dc.densities() == {"hop1": 0.2, "hop2": 0.1}
    snap = dc.snapshot()
    assert [r["action"] for r in snap["trajectory"]] == [
        "baseline", "tighten", "loosen"]
    assert snap["windows_closed"] == 3


def test_density_controller_pure_function_of_feed():
    """Identical note schedules → identical snapshots, including the
    full decision trajectory (no clock, no RNG, no arrival order)."""
    def feed(dc):
        for i in range(20):
            dc.note_ratio("hop1", 4096, 256 + 16 * (i % 3))
            dc.note_ratio("hop2", 4096, 512)
            dc.note_loss(2.0 - 0.01 * i + (0.3 if i == 13 else 0.0))
        return dc.snapshot()

    a = feed(DensityController(window=4, budget_nats=0.05))
    b = feed(DensityController(window=4, budget_nats=0.05))
    assert a == b
    assert a["windows_closed"] == 5
    assert len(a["trajectory"]) == 5


def test_density_auto_chain_run_is_deterministic():
    """End to end: two identically-seeded compressed chain runs, each
    with its own fresh controller, land on the identical controller
    snapshot AND the identical loss series — the acceptance criterion
    for ``--compress-density auto``. The runner also surfaces the
    snapshot in trace metadata and the per-wire density in its stage
    report."""
    steps, M = 6, 2

    def auto_run():
        dc = DensityController(window=2)
        runner, stages, _ = _chain(M, 1, compress="topk8",
                                   density_controller=dc, wire_ids=True)
        try:
            losses = _run(runner, steps, [_batch(i) for i in range(4)])
            meta = runner.trace_metadata()
            rows = runner.stage_report()
        finally:
            _close(runner, stages)
        return losses, dc.snapshot(), meta, rows

    losses_a, snap_a, meta_a, rows_a = auto_run()
    losses_b, snap_b, _, _ = auto_run()
    assert losses_a == losses_b
    assert snap_a == snap_b
    assert snap_a["windows_closed"] == steps // 2
    assert sorted(snap_a["densities"]) == ["hop1", "hop2"]
    assert meta_a["density"] == snap_a
    for row, wire in zip(rows_a, ("hop1", "hop2")):
        assert row["density"] == snap_a["densities"][wire]
