"""chip_smoke.run_legs at tiny sizes on CPU: the same driver, checks and
CLI entry point the chip run uses — fused (with the flash kernels,
interpreted here), two-party local, and the device chain — so the smoke
itself cannot rot between chip runs. ``chip_smoke.main`` refuses to
start off-chip (tests/test_backend_hermetic.py runs the script), and on
a chip where the kernels would be interpreted (below)."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

_LM = ["--model", "transformer_lm", "--dataset", "lm", "--d-model", "64",
       "--num-heads", "2", "--seq-len", "128", "--batch-size", "4"]


def test_run_legs_tiny_on_cpu():
    legs = [
        chip_smoke.Leg("B", "tiny fused lm, flash",
                       _LM + ["--attn", "flash", "--transport", "fused"],
                       steps=3),
        chip_smoke.Leg("C", "tiny two-party lm", _LM + ["--transport",
                                                        "local"],
                       steps=3, agrees_with="B"),
        chip_smoke.Leg("D", "tiny 3-stage device chain",
                       ["--model", "split_cnn_chain3", "--stages", "3",
                        "--transport", "device", "--schedule", "1f1b",
                        "--microbatches", "2", "--dataset", "synthetic",
                        "--batch-size", "8"], steps=3, chain_stages=3),
        chip_smoke.Leg("E", "needs more devices than any test host has",
                       [], steps=1, min_devices=4096),
    ]
    res = {r["leg"]: r for r in chip_smoke.run_legs(legs)}
    assert sorted(res) == ["B", "C", "D"]          # E skipped, not failed
    for r in res.values():
        assert len(r["losses"]) == 3
        assert r["programs_built_after_first_step"] == 0
        assert r["watchdog"] == {"steady_state_recompiles": 0,
                                 "unexpected_d2h": 0}
    assert res["C"]["max_abs_diff_vs_B"] <= 1e-4   # f32 here, bf16 on chip
    # conftest forces 8 virtual devices: a device per stage, so the
    # four-chip host's distinct-device assertion ran (and passed) here
    assert res["D"]["stage_devices"] == {0: [0], 1: [1], 2: [2]}


def test_a_failing_leg_raises():
    bad = chip_smoke.Leg("X", "model/dataset mismatch: the CLI returns 2",
                         ["--model", "transformer_lm", "--dataset",
                          "synthetic", "--transport", "fused"], steps=1)
    with pytest.raises(RuntimeError, match="leg X: CLI returned 2"):
        chip_smoke.run_legs([bad])


def test_main_refuses_a_tpu_whose_kernels_would_be_interpreted(
        monkeypatch, capsys):
    """The second silent fallback ``main`` refuses: a TPU backend with
    ``SLT_PALLAS_INTERPRET=1`` left in the environment would run every
    Pallas kernel through the interpreter and still report a TPU. It
    returns 1 before any leg and prints no result line."""
    import jax

    class _Tpu:
        platform, device_kind = "tpu", "TPU v5 lite"

    def no_leg(*a, **k):
        raise AssertionError("a leg ran after the refusal")

    monkeypatch.setattr(jax, "devices", lambda *a, **k: [_Tpu()])
    monkeypatch.setenv("SLT_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(chip_smoke, "check_flash_kernel", no_leg)
    monkeypatch.setattr(chip_smoke, "run_legs", no_leg)
    assert chip_smoke.main() == 1
    out, err = capsys.readouterr()
    assert not out.strip(), out
    assert "interpreted" in err
