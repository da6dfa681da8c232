"""Multi-host DCN layer (parallel/distributed.py).

In-process: (a) the single-process no-op contract, (b) the grid-layout
invariant that pipe chains never cross a host boundary, (c) single-process
global_mesh ≡ make_mesh. Out-of-process: a REAL two-process
``jax.distributed`` run (gloo CPU collectives standing in for DCN) driving
one fused DP step whose gradient psum crosses the process boundary —
see test_two_process_dp_step_over_gloo.
"""

import dataclasses
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

from split_learning_tpu.parallel import global_mesh, make_mesh
from split_learning_tpu.parallel.distributed import (
    _grid_rows, init_multi_host)


@dataclasses.dataclass(frozen=True)
class FakeDev:
    id: int
    process_index: int


def _cluster(hosts: int, per_host: int):
    return [FakeDev(id=h * per_host + i, process_index=h)
            for h in range(hosts) for i in range(per_host)]


def test_init_multi_host_single_process_noop(monkeypatch):
    monkeypatch.delenv("SLT_COORDINATOR", raising=False)
    monkeypatch.delenv("SLT_NUM_PROCESSES", raising=False)
    assert init_multi_host() is False
    # explicit 1-process config is also a no-op
    assert init_multi_host("host:1234", num_processes=1, process_id=0) is False


def test_init_multi_host_requires_process_id(monkeypatch):
    monkeypatch.delenv("SLT_PROCESS_ID", raising=False)
    with pytest.raises(ValueError, match="process id"):
        init_multi_host("host:1234", num_processes=2, process_id=None)


def test_grid_rows_pipe_stays_on_host():
    """Every row (one pipeline chain) must live on a single process, so
    ppermute hops ride ICI, never DCN."""
    devs = _cluster(hosts=4, per_host=4)
    rows = _grid_rows(devs, num_stages=2)
    assert len(rows) == 8
    for row in rows:
        assert len({d.process_index for d in row}) == 1
        assert len(row) == 2
    # hosts stack along the data axis in process order
    assert [r[0].process_index for r in rows] == [0, 0, 1, 1, 2, 2, 3, 3]


def test_grid_rows_rejects_cross_host_chain():
    devs = _cluster(hosts=2, per_host=3)
    with pytest.raises(ValueError, match="cross DCN"):
        _grid_rows(devs, num_stages=2)


def test_global_mesh_single_process_equals_make_mesh(devices):
    m1 = global_mesh(num_clients=2, num_stages=2, devices=devices[:4])
    m2 = make_mesh(num_clients=2, num_stages=2, devices=devices[:4])
    assert m1.axis_names == m2.axis_names
    assert (np.asarray(m1.devices) == np.asarray(m2.devices)).all()


def test_global_mesh_runs_a_step(devices):
    """A (2 data x 2 pipe) global_mesh drives a real pipelined step."""
    import jax

    from split_learning_tpu.models import get_plan
    from split_learning_tpu.parallel.pipeline import PipelinedTrainer
    from split_learning_tpu.utils import Config

    mesh = global_mesh(num_clients=2, num_stages=2, devices=devices[:4])
    plan = get_plan(mode="split")
    x = np.zeros((8, 28, 28, 1), np.float32)
    y = np.zeros((8,), np.int64)
    trainer = PipelinedTrainer(
        plan, Config(mode="split", batch_size=8, microbatches=2,
                     num_clients=2),
        jax.random.PRNGKey(0), x, mesh)
    assert np.isfinite(trainer.train_step(x, y))


@pytest.mark.slow
def test_two_process_dp_step_over_gloo():
    """The multi-host path, actually multi-process: two OS processes (2
    virtual CPU devices each) join via jax.distributed through the same
    SLT_* env surface a k8s StatefulSet would set, build the global
    (2 data x 2 pipe) mesh with pipe packed within each "host", and run
    fused DP steps whose gradient psum crosses the process boundary —
    gloo standing in for DCN. Both processes must see the identical,
    decreasing loss."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    worker = os.path.join(repo, "tests", "_mp_worker.py")

    def spawn(extra_env):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("SLT_NUM_PROCESSES", None)
        env.update(extra_env)
        return subprocess.Popen(
            [sys.executable, worker], env=env, cwd=repo,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)

    procs = [spawn({
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        "SLT_COORDINATOR": f"127.0.0.1:{port}",
        "SLT_NUM_PROCESSES": "2",
        "SLT_PROCESS_ID": str(pid),
    }) for pid in range(2)]
    # single-process control: same mesh shape/computation, 4 local devices
    procs.append(spawn(
        {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"}))

    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=420)
            assert p.returncode == 0, f"worker failed:\n{err[-2000:]}"
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()

    results = sorted(line for out in outs for line in out.splitlines()
                     if line.startswith("RESULT"))
    assert len(results) == 3, outs
    series = {r.split("process=", 1)[1].split(" ")[0]:
              np.asarray([float(v) for v in
                          r.split("losses=", 1)[1].split(",")])
              for r in results}
    # replicas must agree EXACTLY: they apply the same psum'd update
    np.testing.assert_array_equal(series["0"], series["1"])
    # the single-process control must match to f32 reassociation noise
    # (gloo's cross-process reduction order differs from single-process
    # XLA by ~1 ULP/step; observed 1e-6 after 8 steps)
    np.testing.assert_allclose(series["0"], series["control"],
                               rtol=0, atol=1e-4)
