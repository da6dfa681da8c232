"""Single-source configuration.

The reference scatters configuration across environment variables and
hard-coded constants (SURVEY.md §5 "Config / flag system"): ``LEARNING_MODE``
(``src/model_def.py:59``), S3 credentials (``src/client_part.py:21-23``),
a hard-coded MLflow URI that silently shadows the env var
(``src/server_part.py:19`` vs ``k8s/split-learning.yaml:38-39``), and
hard-coded hyperparameters (lr=0.01 ``src/client_part.py:17``, batch=64
``src/client_part.py:98``, epochs=3 ``src/client_part.py:107``).

Here the whole config surface is one dataclass, constructed from defaults
< env vars < explicit kwargs, so nothing can shadow anything.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Mapping, Optional

_ENV_MAP = {
    # reference-compatible env names
    "mode": "LEARNING_MODE",                  # src/client_part.py:15
    "s3_endpoint": "S3_ENDPOINT_URL",         # src/client_part.py:21
    "s3_access_key": "AWS_ACCESS_KEY_ID",     # src/client_part.py:22
    "s3_secret_key": "AWS_SECRET_ACCESS_KEY", # src/client_part.py:23
    "tracking_uri": "MLFLOW_TRACKING_URI",    # k8s/split-learning.yaml:38-39
    # new surface
    "server_url": "SLT_SERVER_URL",
    "transport": "SLT_TRANSPORT",
    "model": "SLT_MODEL",
    "dataset": "SLT_DATASET",
    "batch_size": "SLT_BATCH_SIZE",
    "epochs": "SLT_EPOCHS",
    "lr": "SLT_LR",
    "momentum": "SLT_MOMENTUM",
    "optimizer": "SLT_OPTIMIZER",
    "weight_decay": "SLT_WEIGHT_DECAY",
    "warmup_steps": "SLT_WARMUP_STEPS",
    "decay_steps": "SLT_DECAY_STEPS",
    "grad_clip_norm": "SLT_GRAD_CLIP_NORM",
    "seed": "SLT_SEED",
    "dtype": "SLT_DTYPE",
    "num_clients": "SLT_NUM_CLIENTS",
    "num_stages": "SLT_NUM_STAGES",
    "microbatches": "SLT_MICROBATCHES",
    "schedule": "SLT_SCHEDULE",
    "remat": "SLT_REMAT",
    "model_parallel": "SLT_MODEL_PARALLEL",
    "seq_parallel": "SLT_SEQ_PARALLEL",
    "attn": "SLT_ATTN",
    "data_dir": "SLT_DATA_DIR",
    "checkpoint_dir": "SLT_CHECKPOINT_DIR",
    "tracking": "SLT_TRACKING",
}


@dataclasses.dataclass(frozen=True)
class Config:
    """Full configuration surface of the framework."""

    # learning mode: "split" | "federated" | "u_split"
    mode: str = "split"
    # model family: "split_cnn" | "resnet18"
    model: str = "split_cnn"
    dataset: str = "mnist"
    # transport: "local" | "http" | "ici"
    transport: str = "local"
    server_url: str = "http://127.0.0.1:8000"

    # hyperparameters (reference defaults: src/client_part.py:17,98,107)
    batch_size: int = 64
    epochs: int = 3
    lr: float = 0.01
    momentum: float = 0.0
    # optimizer family: "sgd" (the reference's, src/client_part.py:17)
    # | "adam" | "adamw" — the LM/transformer families train with adamw
    optimizer: str = "sgd"
    weight_decay: float = 0.0   # adamw decoupled decay; sgd L2 (adam: invalid)
    # learning-rate schedule (runtime/state.py make_lr): linear warmup
    # over warmup_steps, then constant — or cosine decay to 0 by
    # decay_steps (total, including warmup) when decay_steps > 0
    warmup_steps: int = 0
    decay_steps: int = 0
    grad_clip_norm: float = 0.0   # clip grads to this global L2 norm (0 = off)
    seed: int = 0
    dtype: str = "float32"

    # parallelism
    num_clients: int = 1      # data-parallel client replicas (mesh "data" axis)
    num_stages: int = 2       # pipeline stages (mesh "pipe" axis)
    model_parallel: int = 1   # tensor-parallel shards (mesh "model" axis)
    seq_parallel: int = 1     # context-parallel shards (mesh "seq" axis)
    attn: str = "full"        # "full"|"flash"|"auto"|"ring"|"ring_flash"|"ulysses" (transformer)
    microbatches: int = 1     # GPipe microbatches per step
    # MPMD chain injection schedule: "gpipe" (all M in flight) |
    # "1f1b" (warmup min(S, M) then 1-forward-1-backward steady state)
    schedule: str = "gpipe"
    remat: bool = False       # jax.checkpoint stage forwards (FLOPs for HBM)

    # storage / tracking
    data_dir: str = os.path.expanduser("~/.cache/split_learning_tpu")
    checkpoint_dir: Optional[str] = None
    tracking: str = "stdout"  # "stdout" | "jsonl" | "mlflow" | "noop"
    tracking_uri: Optional[str] = None
    s3_endpoint: Optional[str] = None
    s3_access_key: Optional[str] = None
    s3_secret_key: Optional[str] = None
    s3_bucket: str = "mlops-bucket"  # src/client_part.py:24

    def __post_init__(self) -> None:
        self.validate()

    def replace(self, **kw: Any) -> "Config":
        return dataclasses.replace(self, **kw)

    @classmethod
    def from_env(cls, env: Optional[Mapping[str, str]] = None, **overrides: Any) -> "Config":
        """defaults < environment < explicit overrides."""
        env = dict(os.environ if env is None else env)
        kw: dict[str, Any] = {}
        fields = {f.name: f for f in dataclasses.fields(cls)}
        for field_name, env_name in _ENV_MAP.items():
            if env_name in env and env[env_name] != "":
                raw = env[env_name]
                ftype = fields[field_name].type
                if ftype in ("int", int):
                    kw[field_name] = int(raw)
                elif ftype in ("float", float):
                    kw[field_name] = float(raw)
                elif ftype in ("bool", bool):
                    kw[field_name] = raw.strip().lower() in ("1", "true", "yes")
                else:
                    kw[field_name] = raw
        kw.update(overrides)
        return cls(**kw)

    def validate(self) -> None:
        if self.mode not in ("split", "federated", "u_split"):
            # reference raises ValueError on unknown mode (src/model_def.py:70-71)
            raise ValueError(
                f"Unknown learning mode: {self.mode!r} "
                "(expected 'split', 'federated' or 'u_split')"
            )
        if self.batch_size <= 0 or self.epochs <= 0:
            raise ValueError("batch_size and epochs must be positive")
        if self.microbatches <= 0:
            raise ValueError("microbatches must be positive")
        if self.schedule not in ("gpipe", "1f1b"):
            raise ValueError(
                f"Unknown pipeline schedule: {self.schedule!r} "
                "(expected 'gpipe' or '1f1b')")
        if self.batch_size % self.microbatches != 0:
            raise ValueError("batch_size must be divisible by microbatches")
        if self.seq_parallel <= 0:
            raise ValueError("seq_parallel must be positive")
        if self.optimizer not in ("sgd", "adam", "adamw"):
            raise ValueError(
                f"Unknown optimizer: {self.optimizer!r} "
                "(expected 'sgd', 'adam' or 'adamw')")
        if self.weight_decay < 0 or self.warmup_steps < 0 \
                or self.decay_steps < 0 or self.grad_clip_norm < 0:
            raise ValueError("weight_decay / warmup_steps / decay_steps / "
                             "grad_clip_norm must be non-negative")
        if self.weight_decay and self.optimizer == "adam":
            raise ValueError(
                "weight_decay with adam silently L2-couples into the "
                "moments; use optimizer='adamw' (decoupled) instead")
        if self.momentum and self.optimizer != "sgd":
            raise ValueError(
                f"momentum is an SGD hyperparameter; {self.optimizer!r} "
                "has its own moment estimates and would silently ignore "
                "it")
        if self.decay_steps and self.decay_steps <= self.warmup_steps:
            raise ValueError("decay_steps counts total steps incl. "
                             "warmup and must exceed warmup_steps")
        if self.attn not in ("full", "flash", "auto", "ring",
                             "ring_flash", "ulysses"):
            raise ValueError(
                f"Unknown attn impl: {self.attn!r} "
                "(expected 'full', 'flash', 'auto', 'ring', "
                "'ring_flash' or 'ulysses')")
