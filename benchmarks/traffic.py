"""The one generator of jobs: a traffic file's parameters and a seed in,
batches out.  A traffic file (``traffic/<name>.json``) says which path runs
the job (``path``), how many clients there are, how many rows (sequences or
images) each client gives a step, and how long a row is.  A later cell is a
new file, never new code.

Every seed gives the same amount of work: ``pool`` distinct steps' worth of
rows, drawn from the seed, which the window cycles through.  The first
``check_steps`` of them are the steps the reference follows.
"""

from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

REQUIRED = ("path", "clients", "rows_per_client", "tokens_per_row", "pool",
            "check_steps", "reference_row_block", "limits")


def load(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        job = json.load(f)
    missing = [k for k in REQUIRED if k not in job]
    if missing:
        raise ValueError(f"traffic/{name}.json lacks {missing}")
    return job


def _rows(data: dict, rng: np.random.Generator, rows: int, tokens: int):
    if data["kind"] == "tokens":
        ids = rng.integers(0, data["vocab"], (rows, tokens + 1), dtype=np.int32)
        return ids[:, :-1], ids[:, 1:]
    if data["kind"] == "images":
        side = data["image_size"]
        x = rng.standard_normal((rows, side, side, data["channels"]), dtype=np.float32)
        return x, rng.integers(0, data["labels"], (rows,), dtype=np.int32)
    raise ValueError(f"unknown data kind {data['kind']!r}")


def batches(job: dict, data: dict, seed: int) -> list:
    """``pool`` steps; each a list over the clients of ``(x, y)`` arrays."""
    rng = np.random.default_rng([int(seed), 0x5EED])
    return [[_rows(data, rng, job["rows_per_client"], job["tokens_per_row"])
             for _ in range(job["clients"])] for _ in range(job["pool"])]


def tokens_per_step(job: dict) -> int:
    return job["clients"] * job["rows_per_client"] * job["tokens_per_row"]
