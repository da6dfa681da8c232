"""Sharded server runtime (PR 11): the server half pjit-compiled over a
named mesh, with mesh-aware coalesced dispatch.

Pins, in order: a mesh of size 1 normalizes to the legacy single-device
runtime and every path (fused serialized, coalesced groups-of-one, 2BP
lag-0/lag-2) is BIT-identical to ``mesh=None``; ``data=2`` reproduces
the same trajectories to float tolerance (different reduction shapes,
same math); the tensor-parallel layout shards the heavy leaves along
``model`` and still trains; coalesced groups pad to a multiple of the
``data`` axis with zero-weight rows that leave the objective untouched;
``predict`` pads/trims transparently while serialized training rejects
non-divisible batches with a protocol 400; the sanctioned per-shard
gather (slt-lint SLT013) trims to the requested rows and dedups
replicated shards; and the mesh shape + MFU accounting surfaces through
health()/metrics()/trace_metadata(). The suite runs on the forced
8-device CPU host topology from conftest.py, under both the lock and
dispatch watchdog teardown gates."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from split_learning_tpu import obs
from split_learning_tpu.models import get_plan
from split_learning_tpu.parallel.distributed import (SpecLayout,
                                                     server_state_layout)
from split_learning_tpu.parallel.mesh import (DATA_AXIS, MODEL_AXIS,
                                              batch_sharding, host_gather,
                                              make_host_mesh, replicated)
from split_learning_tpu.runtime import ServerRuntime, SplitClientTrainer
from split_learning_tpu.runtime.server import ProtocolError
from split_learning_tpu.transport.local import LocalTransport
from split_learning_tpu.utils import Config

BATCH = 4


def _server(batch=BATCH, **kw):
    cfg = Config(mode="split", batch_size=batch, num_clients=2)
    plan = get_plan(mode="split")
    sample = np.zeros((batch, 28, 28, 1), np.float32)
    return cfg, plan, ServerRuntime(plan, cfg, jax.random.PRNGKey(2),
                                    sample, **kw)


def _batch(seed=0, batch=BATCH):
    rs = np.random.RandomState(seed)
    return (rs.randn(batch, 28, 28, 1).astype(np.float32),
            rs.randint(0, 10, batch).astype(np.int64))


def _series(steps=4, batch=BATCH, **kw):
    cfg, plan, server = _server(batch=batch, **kw)
    client = SplitClientTrainer(plan, cfg, jax.random.PRNGKey(0),
                                LocalTransport(server))
    try:
        return [client.train_step(*_batch(i, batch), i)
                for i in range(steps)], server
    finally:
        server.close()


# ---------------------------------------------------------------------- #
# mesh=1 bit-identity: the degenerate mesh IS the legacy runtime
# ---------------------------------------------------------------------- #

def test_mesh1_is_normalized_and_bit_identical_fused():
    """A size-1 mesh compiles the very same legacy ``jax.jit`` programs
    (the ctor normalizes it to ``mesh=None``), so the loss series is
    IDENTICAL — not merely close."""
    legacy, _ = _series()
    m1, srv = _series(mesh=make_host_mesh(data=1))
    assert srv._mesh is None          # normalized, not special-cased
    assert legacy == m1


def test_mesh1_bit_identical_coalesced_groups_of_one():
    """Window flushes of one route through the mesh-aware group dispatch
    (padding, zero weights, rows-bounded gather); on a size-1 mesh that
    path must still be bit-for-bit the legacy coalesced path."""
    legacy, _ = _series(coalesce_max=4, coalesce_window_ms=5.0)
    m1, _ = _series(coalesce_max=4, coalesce_window_ms=5.0,
                    mesh=make_host_mesh(data=1))
    assert legacy == m1


@pytest.mark.parametrize("lag", [0, 2])
def test_mesh1_bit_identical_decoupled_bwd(lag):
    legacy, _ = _series(decouple_bwd=True, apply_lag=lag)
    m1, _ = _series(decouple_bwd=True, apply_lag=lag,
                    mesh=make_host_mesh(data=1))
    assert legacy == m1


# ---------------------------------------------------------------------- #
# data=2: same math, different reduction shapes -> float tolerance
# ---------------------------------------------------------------------- #

def test_data2_fused_matches_to_float_tolerance():
    legacy, _ = _series()
    d2, srv = _series(mesh=make_host_mesh(data=2))
    assert srv is not None
    np.testing.assert_allclose(d2, legacy, rtol=1e-4, atol=5e-4)


def test_data2_coalesced_and_decoupled_match():
    legacy_c, _ = _series(coalesce_max=4, coalesce_window_ms=5.0)
    d2_c, _ = _series(coalesce_max=4, coalesce_window_ms=5.0,
                      mesh=make_host_mesh(data=2))
    np.testing.assert_allclose(d2_c, legacy_c, rtol=1e-4, atol=5e-4)
    legacy_b, _ = _series(decouple_bwd=True, apply_lag=2)
    d2_b, _ = _series(decouple_bwd=True, apply_lag=2,
                      mesh=make_host_mesh(data=2))
    np.testing.assert_allclose(d2_b, legacy_b, rtol=1e-4, atol=5e-4)


def test_tensor_parallel_mesh_shards_heavy_leaves_and_trains():
    legacy, _ = _series()
    cfg, plan, server = _server(mesh=make_host_mesh(data=2, model=2))
    client = SplitClientTrainer(plan, cfg, jax.random.PRNGKey(0),
                                LocalTransport(server))
    try:
        # divisible weight leaves actually land on the model axis
        specs = [tuple(leaf.sharding.spec)
                 for leaf in jax.tree_util.tree_leaves(server.state.params)]
        assert any(MODEL_AXIS in sp for sp in specs), specs
        tp = [client.train_step(*_batch(i), i) for i in range(4)]
        np.testing.assert_allclose(tp, legacy, rtol=1e-4, atol=5e-4)
    finally:
        server.close()


# ---------------------------------------------------------------------- #
# mesh-aware group sizing: pad to a multiple of the data axis
# ---------------------------------------------------------------------- #

def test_group_pads_to_data_axis_multiple_with_zero_weight_tail():
    """batch=2 on a data=4 mesh: the pow2 bucket (2) is SMALLER than the
    data axis, so the group must round up to 4 rows — and the two
    zero-weight padding rows must leave the loss series at the unsharded
    values (float tolerance)."""
    legacy, _ = _series(batch=2)
    padded, srv = _series(batch=2, coalesce_max=4, coalesce_window_ms=5.0,
                          mesh=make_host_mesh(data=4))
    np.testing.assert_allclose(padded, legacy, rtol=1e-4, atol=5e-4)
    sigs = list(srv._coalesce_shapes)
    assert sigs, "group dispatch never ran"
    for shape, _, _ in sigs:
        assert shape[0] % 4 == 0, sigs


# ---------------------------------------------------------------------- #
# serialized divisibility guard + predict pad/trim
# ---------------------------------------------------------------------- #

def test_serialized_nondivisible_batch_is_a_protocol_400():
    cfg, plan, server = _server(mesh=make_host_mesh(data=2))
    try:
        acts = np.zeros((3, 26, 26, 32), np.float32)  # cut-layer shape
        labels = np.zeros((3,), np.int64)
        with pytest.raises(ProtocolError, match="data") as exc:
            server.split_step(acts, labels, 0)
        assert exc.value.status == 400
    finally:
        server.close()


def test_predict_pads_and_trims_odd_batches():
    cfg, plan, server0 = _server()
    cfg2, plan2, server2 = _server(mesh=make_host_mesh(data=2))
    try:
        acts = np.random.RandomState(7).randn(3, 26, 26, 32).astype(
            np.float32)
        out0 = server0.predict(acts)
        out2 = server2.predict(acts)
        assert out2.shape == out0.shape == (3, 10)
        np.testing.assert_allclose(out2, out0, rtol=1e-5, atol=1e-5)
    finally:
        server0.close()
        server2.close()


@pytest.mark.parametrize("kw", [{}, {"coalesce_max": 4,
                                      "coalesce_window_ms": 5.0}],
                         ids=["serialized", "coalesced"])
def test_data2_hot_loops_are_steady_state_recompile_free(kw):
    """A sharded program whose in/out shardings or committed-ness
    drifted between steps would retrace every step and still train: the
    dispatch watchdog counts none after the second step, serialized and
    coalesced (the old sharded_server leg's compile gate)."""
    from split_learning_tpu.obs import dispatch_debug
    dd = dispatch_debug.tracker()
    g0 = dd.gauges()
    dispatch_debug.force(True)
    try:
        _series(steps=5, mesh=make_host_mesh(data=2), **kw)
    finally:
        dispatch_debug.force(False)
    g1 = dd.gauges()
    assert g1["compile_count"] > g0["compile_count"]  # it was watching
    assert (g1["steady_state_recompiles"]
            - g0["steady_state_recompiles"]) == 0


# ---------------------------------------------------------------------- #
# the sanctioned gather (SLT013) + mesh construction helpers
# ---------------------------------------------------------------------- #

def test_host_gather_trims_dedups_and_passes_through():
    mesh = make_host_mesh(data=2)
    x = jax.device_put(jnp.arange(12.0).reshape(6, 2),
                       batch_sharding(mesh))
    np.testing.assert_array_equal(host_gather(x),
                                  np.arange(12.0).reshape(6, 2))
    # rows bounds the transfer: only the first 3 rows come back
    np.testing.assert_array_equal(host_gather(x, rows=3),
                                  np.arange(6.0).reshape(3, 2))
    # replicated shards dedup — 2 device copies, one logical array
    r = jax.device_put(jnp.arange(4.0).reshape(2, 2), replicated(mesh))
    np.testing.assert_array_equal(host_gather(r),
                                  np.arange(4.0).reshape(2, 2))
    # host arrays pass through (with the same rows contract)
    h = np.arange(10.0).reshape(5, 2)
    np.testing.assert_array_equal(host_gather(h, rows=2), h[:2])
    # scalars fall back to plain materialization
    assert host_gather(jnp.float32(3.5)) == np.float32(3.5)


def test_make_host_mesh_reports_the_remedy_when_short_on_devices():
    with pytest.raises(RuntimeError, match="xla_force_host_platform"):
        make_host_mesh(data=64)


def test_spec_layout_rules():
    layout = server_state_layout(make_host_mesh(data=2, model=2))
    assert isinstance(layout, SpecLayout)
    assert (layout.data, layout.model) == (2, 2)
    # column-parallel: last dim divisible by the model axis
    col = layout.param(jnp.zeros((8, 64))).spec
    assert tuple(col) == (None, MODEL_AXIS)
    # row-parallel: only the second-to-last dim divides
    row = layout.param(jnp.zeros((64, 5))).spec
    assert tuple(row) == (MODEL_AXIS, None)
    # biases / scalars replicate
    assert tuple(layout.param(jnp.zeros((5,))).spec) == ()
    # batch layout shards dim 0 along data
    assert tuple(layout.batch().spec)[0] == DATA_AXIS


# ---------------------------------------------------------------------- #
# observability: health / metrics / trace metadata
# ---------------------------------------------------------------------- #

def test_mesh_surfaces_in_health_metrics_and_trace_metadata():
    cfg, plan, server = _server(mesh=make_host_mesh(data=2))
    client = SplitClientTrainer(plan, cfg, jax.random.PRNGKey(0),
                                LocalTransport(server))
    try:
        client.train_step(*_batch(0), 0)
        mesh_h = server.health()["mesh"]
        assert mesh_h["devices"] == 2 and mesh_h["data"] == 2
        gauges = server.metrics()["gauges"]
        assert gauges["mesh_devices"] == 2.0
        assert gauges["mesh_data"] == 2.0
        # MFU accounting only runs while tracing (zero-overhead-off)
        meta0 = server.trace_metadata()
        assert meta0["programs"] == {}
        obs.enable()
        try:
            client.train_step(*_batch(1), 1)
        finally:
            obs.disable()
        meta = server.trace_metadata()
        assert meta["mesh"]["data"] == 2
        assert meta["gather_bytes"] > 0        # the sanctioned gather ran
        prog = meta["programs"]["split_step"]
        assert prog["calls"] >= 1
        assert prog["model_flops"] > 0
        # CPU backend: peak unknown -> MFU honestly None, never 0
        assert meta["peak_flops_per_device"] is None
        assert prog["mfu"] is None
    finally:
        server.close()


def test_unsharded_server_exports_no_mesh_or_gather_counters():
    cfg, plan, server = _server()
    client = SplitClientTrainer(plan, cfg, jax.random.PRNGKey(0),
                                LocalTransport(server))
    try:
        client.train_step(*_batch(0), 0)
        assert "mesh" not in server.health()
        assert "gather_bytes" not in server.metrics()["counters"]
        meta = server.trace_metadata()
        assert meta["mesh"] == {"devices": 1, "data": 1}
        assert meta["gather_bytes"] == 0
    finally:
        server.close()


def test_federated_mesh_is_rejected():
    cfg = Config(mode="federated", batch_size=BATCH, num_clients=2)
    plan = get_plan(mode="federated")
    sample = np.zeros((BATCH, 28, 28, 1), np.float32)
    with pytest.raises(ValueError, match="federated"):
        ServerRuntime(plan, cfg, jax.random.PRNGKey(2), sample,
                      mesh=make_host_mesh(data=2))


# ---------------------------------------------------------------------- #
# checkpoint round-trip keeps the sharded layout
# ---------------------------------------------------------------------- #

def test_resume_from_reshards_and_continues():
    cfg, plan, server = _server(mesh=make_host_mesh(data=2))
    client = SplitClientTrainer(plan, cfg, jax.random.PRNGKey(0),
                                LocalTransport(server))
    try:
        client.train_step(*_batch(0), 0)
        state = server.export_state()
        # round-trip through host-side state (the checkpoint shape)
        host_state = jax.tree_util.tree_map(np.asarray, state)
        server.resume_from(host_state, step=0)
        for leaf in jax.tree_util.tree_leaves(server.state.params):
            assert DATA_AXIS not in tuple(leaf.sharding.spec or ())
            assert leaf.sharding.mesh.size == 2
        loss = client.train_step(*_batch(1), 1)
        assert np.isfinite(loss)
    finally:
        server.close()
