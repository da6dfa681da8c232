"""The comparison has been shown to fail: a run driven through ``run.main``
with the timed path broken underneath comes out not correct, and the
reference in the precision below bfloat16, put in the program's place, is
refused by the cell's own limits (the ones the chip runs are held to) while
the bfloat16 one is let through.  These run at the rehearsal's sizes on the
CPU; PERF.md has the readings at the cells' own sizes on the chip."""

import io
import json
import os
import sys
from contextlib import redirect_stdout

import pytest

os.environ["JAX_PLATFORMS"] = "cpu"

import check
import run
import traffic
import weights
from paths import fused, party
from reference import common as ref_common

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def drive(monkeypatch, workload, seed=7):
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", workload, "--seed",
                                      str(seed), "--seconds", "0.5", "--trace", "0"])
    out = io.StringIO()
    with redirect_stdout(out):
        assert run.main() == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("workload", ["gpt2m-fused-t1024", "vitl16-party-224"])
def test_a_sound_rehearsal_is_correct(monkeypatch, workload):
    result = drive(monkeypatch, workload)
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    assert set(result["metrics"]) == {"setup_s"}  # no device metric off the chip


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    sound = fused.Driver.step

    def frozen(self, batch):
        state = self.trainer.state
        import jax
        keep = jax.tree_util.tree_map(lambda x: x.copy(), state)
        losses = sound(self, batch)
        self.trainer.state = keep
        return losses

    monkeypatch.setattr(fused.Driver, "step", frozen)
    assert drive(monkeypatch, "gpt2m-fused-t1024")["correct"] is False


def test_a_client_whose_rows_are_left_out_is_not_correct(monkeypatch):
    sound = party.Driver.step

    def short(self, batch):
        # every client is fed client 0's rows: three quarters of the batch is left out
        return sound(self, [batch[0]] * len(batch))

    monkeypatch.setattr(party.Driver, "step", short)
    assert drive(monkeypatch, "vitl16-party-224")["correct"] is False


def test_calibrate_plants_both_faults_and_neither_is_let_through(monkeypatch):
    """``calibrate.py``'s loop at the rehearsal's sizes, as the chip runs it
    at the cell's own (PERF.md, Findings PR 49): the sound program within the
    limits, and under its step a state put back as it was and an update
    applied twice, each read by ``delta_norm_gap`` as about 1."""
    import calibrate
    monkeypatch.setattr(sys, "argv", ["calibrate.py", "--workload", "phi4flash-fused-t8192",
                                      "--seeds", "3", "--fault-seeds", "3",
                                      "--look-seeds", "3", "--look", "layer17/attn/lambda"])
    out = io.StringIO()
    with redirect_stdout(out):
        assert calibrate.main() == 0
    row, summary = (json.loads(line) for line in out.getvalue().strip().splitlines())
    assert row["program"]["correct"] is True
    # the look follows layer 17's four lambda vectors through the three steps
    # on both sides: a vector's gradient is one scalar along its partner
    # (|cosine| 1), and the two sides' first gradients point the same way
    look = row["look"]
    assert sorted(k.rsplit("/", 1)[1] for k in look) == [
        "lambda_k1", "lambda_k2", "lambda_q1", "lambda_q2"]
    for leaf in look.values():
        assert len(leaf["steps"]) == 3 and leaf["program_change"] > 0
        for step in leaf["steps"]:
            assert abs(step["reference_cos_to_partner"]) == pytest.approx(1.0, abs=1e-3)
            assert step["reference_scalar"] * step["reference_cos_to_partner"] > 0
    assert set(calibrate.FAULTS) == {"unchanged", "twice"}
    for fault in calibrate.FAULTS:
        assert row[fault]["correct"] is False
        assert 0.99 < row[fault]["delta_norm_gap"] < 1.1
        # the moments are the sound step's own: the first gradient reads as it did
        assert row[fault]["grad_norm_gap"] == row["program"]["grad_norm_gap"]
    assert summary["summary"]["twice.correct"] == "0 of 1"


def reference_readings(workload, precision, seed=11):
    bench, cell, config = run.load_cell(workload)
    limits = traffic.load(cell["traffic"])["limits"]  # the cell's own, as on the chip
    config, job = run.rehearsal_sizes(config, traffic.load(cell["traffic"]))
    import importlib
    reference = importlib.import_module(f"reference.{config['family']}")
    plan, shapes, parties = run.seeded_model(config, job, weights.seed_key(seed),
                                             traffic.batches(job, config["data"], seed))
    pool = traffic.batches(job, config["data"], seed)
    out = ref_common.train(reference.loss_fn(config, precision), parties,
                           pool[:job["check_steps"]], config["train"]["lr"],
                           job["reference_row_block"])
    return out, limits


@pytest.mark.parametrize("workload", ["gpt2m-fused-t1024", "vitl16-fused-224"])
def test_the_precision_below_bfloat16_is_refused(workload):
    want, limits = reference_readings(workload, "f32")
    bf16, _ = reference_readings(workload, "bf16")
    fp8, _ = reference_readings(workload, "fp8")
    log = lambda *a: None
    assert check.verdict(check.readings(bf16, want), limits, log) is True
    assert check.verdict(check.readings(fp8, want), limits, log) is False
