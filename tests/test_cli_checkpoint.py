"""CLI checkpoint/resume and eval — the reference persists nothing
(SURVEY.md §5 'Checkpoint / resume'); here save → resume → eval must work
end-to-end through the launcher for every checkpointable topology."""

import json
import os

import pytest

from split_learning_tpu.launch.run import main


def _train(tmp_path, ckdir, *extra):
    return main(["train", "--dataset", "synthetic", "--steps", "4",
                 "--batch-size", "16", "--epochs", "1",
                 "--data-dir", str(tmp_path), "--tracking", "noop",
                 "--checkpoint-dir", str(ckdir), *extra])


@pytest.mark.parametrize("mode,transport", [
    ("split", "fused"),
    ("split", "local"),
    ("u_split", "local"),
    ("federated", "local"),
])
@pytest.mark.slow
def test_checkpoint_resume_eval(tmp_path, capsys, mode, transport):
    ck = tmp_path / "ckpt"
    assert _train(tmp_path, ck, "--mode", mode,
                  "--transport", transport) == 0
    assert (ck / "meta.json").exists()

    # resume continues from the saved step
    assert _train(tmp_path, ck, "--mode", mode, "--transport", transport,
                  "--resume") == 0
    err = capsys.readouterr().err
    assert "resumed at step 4" in err

    # standalone eval reassembles the full composition from the checkpoint
    assert main(["eval", "--checkpoint-dir", str(ck),
                 "--data-dir", str(tmp_path), "--batch-size", "64"]) == 0
    out = capsys.readouterr().out
    line = [l for l in out.splitlines() if l.startswith("{")][-1]
    res = json.loads(line)
    assert res["checkpoint_step"] == 8
    assert 0.0 <= res["accuracy"] <= 1.0
    assert res["examples"] > 0


def test_checkpoint_every_fused(tmp_path, capsys):
    ck = tmp_path / "ck2"
    assert _train(tmp_path, ck, "--mode", "split", "--transport", "fused",
                  "--checkpoint-every", "2") == 0
    from split_learning_tpu.runtime.checkpoint import Checkpointer
    steps = list(Checkpointer(str(ck)).all_steps())
    assert 2 in steps and 4 in steps


def test_train_eval_flag(tmp_path, capsys):
    assert main(["train", "--dataset", "synthetic", "--steps", "3",
                 "--batch-size", "16", "--epochs", "1",
                 "--data-dir", str(tmp_path), "--tracking", "noop",
                 "--transport", "fused", "--eval"]) == 0
    out = capsys.readouterr().out
    assert "[eval] accuracy=" in out


def _start_http_server(cfg_kwargs, ckdir=None, resume=False, every=1):
    import jax
    import numpy as np
    from split_learning_tpu.models import get_plan
    from split_learning_tpu.runtime import ServerRuntime
    from split_learning_tpu.runtime.checkpoint import Checkpointer
    from split_learning_tpu.transport.http import SplitHTTPServer
    from split_learning_tpu.utils import Config

    cfg = Config(**cfg_kwargs)
    plan = get_plan(mode=cfg.mode)
    sample = np.zeros((cfg.batch_size, 28, 28, 1), np.float32)
    runtime = ServerRuntime(plan, cfg, jax.random.PRNGKey(cfg.seed), sample)
    if ckdir is not None:
        ckptr = Checkpointer(str(ckdir))
        latest = ckptr.latest_step()
        if resume and latest is not None:
            runtime.resume_from(
                ckptr.restore({"server": runtime.state})["server"], latest)

        def on_step(step):
            if (step + 1) % every == 0 and ckptr.latest_step() != step + 1:
                ckptr.save(step + 1, {"server": runtime.state})

        runtime.on_step = on_step
    return SplitHTTPServer(runtime).start()


def test_http_resume_guard_rejects_fresh_server(tmp_path, capsys):
    """A resumed client must refuse to train against a server that was not
    resumed (the silent-desync hazard, SURVEY.md §3.4)."""
    ck = tmp_path / "ck_http"
    server = _start_http_server({"mode": "split", "batch_size": 16})
    try:
        assert _train(tmp_path, ck, "--mode", "split", "--transport", "http",
                      "--server-url", server.url) == 0
    finally:
        server.stop()
    # fresh (un-resumed) server: health step == -1 < checkpoint step
    server2 = _start_http_server({"mode": "split", "batch_size": 16})
    try:
        rc = _train(tmp_path, ck, "--mode", "split", "--transport", "http",
                    "--server-url", server2.url, "--resume")
        assert rc == 3
        assert "was not resumed" in capsys.readouterr().err
    finally:
        server2.stop()


@pytest.mark.slow
def test_http_resume_both_halves(tmp_path, capsys):
    """Server checkpoints via on_step; a restarted resumed pair trains on."""
    ck_c = tmp_path / "ck_client"
    ck_s = tmp_path / "ck_server"
    server = _start_http_server({"mode": "split", "batch_size": 16},
                                ckdir=ck_s, every=1)
    try:
        assert _train(tmp_path, ck_c, "--mode", "split", "--transport",
                      "http", "--server-url", server.url) == 0
    finally:
        server.stop()
    # both parties restart and resume; handshake floor accepts the client
    server2 = _start_http_server({"mode": "split", "batch_size": 16},
                                 ckdir=ck_s, resume=True)
    try:
        assert _train(tmp_path, ck_c, "--mode", "split", "--transport",
                      "http", "--server-url", server2.url, "--resume") == 0
        assert "resumed at step 4" in capsys.readouterr().err
    finally:
        server2.stop()


def test_resume_rearms_server_handshake(tmp_path, capsys):
    """After resume the local server refuses steps below the floor —
    exercised implicitly: resumed training starts at the restored step and
    must be accepted."""
    ck = tmp_path / "ck3"
    assert _train(tmp_path, ck, "--mode", "split", "--transport", "local") == 0
    assert _train(tmp_path, ck, "--mode", "split", "--transport", "local",
                  "--resume") == 0
    out = capsys.readouterr().out
    assert out.count("[done]") >= 1


@pytest.mark.slow
def test_checkpoint_resume_eval_transformer(tmp_path, capsys):
    """The long-context family checkpoints/resumes/evals through the same
    machinery (token dataset, fused transport)."""
    ck = tmp_path / "ck_tfm"
    base = ["--mode", "split", "--transport", "fused",
            "--model", "transformer", "--dataset", "tokens"]
    assert _train(tmp_path, ck, *base) == 0
    assert _train(tmp_path, ck, *base, "--resume") == 0
    assert "resumed at step 4" in capsys.readouterr().err
    assert main(["eval", "--checkpoint-dir", str(ck),
                 "--data-dir", str(tmp_path), "--batch-size", "64"]) == 0
    out = capsys.readouterr().out
    res = json.loads([l for l in out.splitlines()
                      if l.startswith("{")][-1])
    assert res["checkpoint_step"] == 8
    assert 0.0 <= res["accuracy"] <= 1.0


@pytest.mark.slow
def test_serve_resume_from_joint_checkpoint(tmp_path, capsys):
    """`serve --resume` on a JOINT checkpoint dir (written by local/fused
    training) must restore the server subtree, leave the joint meta.json
    untouched (periodic saves go to a server_party/ subdir), and yield
    remote-eval metrics identical to local full-composition eval."""
    import subprocess
    import sys as _sys

    ck = tmp_path / "joint"
    assert _train(tmp_path, ck, "--mode", "split",
                  "--transport", "local") == 0
    assert main(["eval", "--checkpoint-dir", str(ck),
                 "--data-dir", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    local = json.loads([l for l in out.splitlines()
                        if l.startswith("{")][-1])

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    port = "18791"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    srv = subprocess.Popen(
        [_sys.executable, "-m", "split_learning_tpu.launch.run", "serve",
         "--mode", "split", "--host", "127.0.0.1", "--port", port,
         "--checkpoint-dir", str(ck), "--resume",
         "--data-dir", str(tmp_path)],
        env=env, cwd=repo, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True)
    try:
        assert main(["eval", "--checkpoint-dir", str(ck),
                     "--data-dir", str(tmp_path),
                     "--server-url", f"http://127.0.0.1:{port}"]) == 0
        out = capsys.readouterr().out
        remote = json.loads([l for l in out.splitlines()
                             if l.startswith("{")][-1])
    finally:
        srv.terminate()
        srv.wait(timeout=30)

    assert remote["accuracy"] == local["accuracy"]
    assert abs(remote["loss"] - local["loss"]) < 1e-3
    meta = json.loads((ck / "meta.json").read_text())
    assert meta["layout"] == "split_local"  # not clobbered to server_only


def test_reconcile_sizes_accepts_explicit_defaults():
    """ADVICE r4: explicit size flags that restate the builder's
    defaults against a default-size checkpoint (and vice versa) must be
    accepted — saved and requested sizes compare as *effective* plans,
    merged over the builder signature's defaults. Only a flag that
    would rebuild a different plan refuses."""
    from split_learning_tpu.launch.run import _reconcile_ckpt_sizes

    # default-size checkpoint (no size_kw persisted) + flags == defaults
    kw, seq, err = _reconcile_ckpt_sizes(
        {}, {"d_model": 64, "num_heads": 4}, None, "eval",
        model="transformer")
    assert err is None and kw == {}

    # sized checkpoint + explicit flags restating the same values
    meta = {"size_kw": {"d_model": 256, "num_heads": 2}}
    kw, seq, err = _reconcile_ckpt_sizes(
        meta, {"d_model": 256, "num_heads": 2}, None, "eval",
        model="transformer")
    assert err is None and kw == {"d_model": 256, "num_heads": 2}

    # a flag subset whose values match the saved ones
    kw, seq, err = _reconcile_ckpt_sizes(
        meta, {"d_model": 256}, None, "eval", model="transformer")
    assert err is None and kw == {"d_model": 256, "num_heads": 2}

    # genuinely different plan still refuses, naming the conflict
    kw, seq, err = _reconcile_ckpt_sizes(
        meta, {"d_model": 128}, None, "eval", model="transformer")
    assert err is not None and "d_model" in err

    # default-size checkpoint + non-default flag refuses too (the
    # saved plan was built at d_model=64)
    kw, seq, err = _reconcile_ckpt_sizes(
        {}, {"d_model": 128}, None, "eval", model="transformer")
    assert err is not None and "d_model" in err
