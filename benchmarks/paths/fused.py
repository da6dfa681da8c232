"""The fused path: ``FusedSplitTrainer``, one program for the whole split
step, stepped as ``launch/run.py``'s default fused loop steps it
(``train_step``, one blocking loss a step)."""

from __future__ import annotations

import jax

from split_learning_tpu.runtime.fused import FusedSplitTrainer

from . import first_moment


class Driver:
    unit = "steps"
    span = "fused.train_step"

    def __init__(self, plan, cfg, key, job: dict, sample) -> None:
        if job["clients"] != 1:
            raise ValueError("the fused path has one data owner")
        self.trainer = FusedSplitTrainer(plan, cfg, key, sample)
        self.reply_seconds, self.wire_bytes = [], []

    def check_gate(self, on: bool) -> None:
        pass

    def step(self, batch) -> list:
        (x, y), = batch
        with jax.profiler.TraceAnnotation(self.span):
            return [self.trainer.train_step(x, y)]

    def warm_up(self, batch) -> None:
        pass

    def sync(self) -> None:
        jax.block_until_ready(self.trainer.state)

    def params(self) -> dict:
        client, server = self.trainer.state.params
        return {"client0": client, "server": server}

    def first_moments(self) -> dict:
        client, server = first_moment(self.trainer.state.opt_state)
        return {"client0": client, "server": server}

    def counters(self) -> dict:
        return {}

    def close(self) -> None:
        pass
