"""Where the launcher puts the parties of a chain (launch/run.py
``_stage_placement`` over parallel.mesh.stage_devices), on the 8-device
virtual mesh conftest forces: stage i of an in-process chain takes the
i-th block of devices, with or without a per-stage mesh; a party alone
in its process (``serve``) takes the first block, with or without."""

import argparse
import os
import socket
import subprocess
import sys

import jax

from split_learning_tpu.launch.run import _stage_placement
from split_learning_tpu.parallel.mesh import stage_devices
from split_learning_tpu.transport.http import HttpTransport

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _ids(devices):
    return [d.id for d in devices]


def test_stage_devices_blocks_and_colocation():
    all_ids = _ids(jax.devices())
    assert _ids(stage_devices(2, 4)) == all_ids[2:3]
    assert _ids(stage_devices(2, 4, per_stage=2)) == all_ids[4:6]
    # fewer devices than stages x per_stage: everyone on the first block
    assert _ids(stage_devices(2, 3, per_stage=4)) == all_ids[0:4]
    assert _ids(stage_devices(8, 9)) == all_ids[0:1]


def test_in_process_chain_stage_i_takes_block_i_meshed_or_not():
    all_ids = _ids(jax.devices())
    flat = argparse.Namespace(mesh_data=1, mesh_model=1)
    meshed = argparse.Namespace(mesh_data=2, mesh_model=1)
    for i in (1, 2):
        assert _stage_placement(flat, i, 3) == {"device": jax.devices()[i]}
        placed = _stage_placement(meshed, i, 3)
        assert list(placed) == ["mesh"]
        assert _ids(placed["mesh"].devices.flat) == all_ids[2 * i:2 * i + 2]


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_served_stage_takes_the_first_block_meshed_or_not():
    """`serve --role stage --stage-index 2` is alone in its process: it
    lands on device 0 without a mesh and on devices [0, 1] with
    --mesh-data 2 — the same block by both routes, whatever its index."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    procs = []
    try:
        for extra in ([], ["--mesh-data", "2"]):
            port = _free_port()
            procs.append((port, subprocess.Popen(
                [sys.executable, "-m", "split_learning_tpu.launch.run",
                 "serve", "--role", "stage", "--stage-index", "2",
                 "--model", "split_cnn_chain3", "--microbatches", "2",
                 "--batch-size", "8", "--host", "127.0.0.1",
                 "--port", str(port), "--tracking", "noop", *extra],
                env=env, cwd=REPO, stdout=subprocess.DEVNULL,
                stderr=subprocess.DEVNULL)))
        placed = [HttpTransport(f"http://127.0.0.1:{port}")
                  .wait_ready(timeout=120)["devices"] for port, _ in procs]
    finally:
        for _, proc in procs:
            proc.terminate()
        for _, proc in procs:
            proc.wait(timeout=30)
    assert placed == [[0], [0, 1]]
