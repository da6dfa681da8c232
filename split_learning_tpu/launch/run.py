"""CLI entry points — the L3/L0 analog of the reference's process commands.

Reference entry points (SURVEY.md §1): ``python client_part.py``
(``k8s/split-learning.yaml:63``) and ``uvicorn server_part:app``
(``k8s/split-learning.yaml:34``), wired by env vars. Here one CLI:

  python -m split_learning_tpu.launch.run train \
      --mode split --transport fused --dataset synthetic --steps 100
  python -m split_learning_tpu.launch.run serve --mode split --port 8000
  python -m split_learning_tpu.launch.run train --transport http \
      --server-url http://host:8000
  python -m split_learning_tpu.launch.run eval --checkpoint-dir /tmp/ckpt

Config resolution: CLI flags > env vars (LEARNING_MODE etc.) > defaults —
one place, no hard-coded endpoints (the reference's URI-shadowing bug,
``src/server_part.py:19``, is structurally impossible here).

Checkpoint/resume (the reference persists nothing — SURVEY.md §5): with
``--checkpoint-dir`` the joint cross-party state is saved per epoch (and
every ``--checkpoint-every`` steps on the fused/pipeline paths);
``--resume`` restores the latest and re-arms the server's step handshake.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
from typing import Any, Dict, Optional

import numpy as np


@contextlib.contextmanager
def _ckpt_drain(ckptr):
    """Barrier on in-flight async checkpoint saves on EVERY exit path.
    save()/save_once() enqueue background Orbax writes; a mid-epoch
    exception that skips the success-path wait_until_finished() would
    let interpreter teardown tear the newest checkpoint on disk."""
    try:
        yield
    finally:
        if ckptr is not None:
            ckptr.wait_until_finished()


def _add_common(p: argparse.ArgumentParser) -> None:
    p.add_argument("--mode", choices=["split", "federated", "u_split"],
                   default=None)
    p.add_argument("--model", default=None,
                   help="split_cnn | resnet18 | resnet18_4stage | vit | "
                        "transformer | transformer_lm | afmoe | phi4flash | "
                        "joyai_llm_flash | lfm2_moe | nemotron_h | ouro")
    p.add_argument("--dataset", default=None,
                   help="mnist | cifar10 | synthetic | tokens | lm")
    p.add_argument("--batch-size", type=int, default=None)
    p.add_argument("--epochs", type=int, default=None)
    p.add_argument("--lr", type=float, default=None)
    p.add_argument("--optimizer", choices=["sgd", "adam", "adamw"],
                   default=None,
                   help="sgd (the reference's) | adam | adamw "
                        "(runtime/state.py make_tx)")
    p.add_argument("--momentum", type=float, default=None)
    p.add_argument("--weight-decay", dest="weight_decay", type=float,
                   default=None,
                   help="adamw decoupled decay; coupled L2 for sgd")
    p.add_argument("--warmup-steps", dest="warmup_steps", type=int,
                   default=None,
                   help="linear lr warmup over this many steps")
    p.add_argument("--decay-steps", dest="decay_steps", type=int,
                   default=None,
                   help="cosine-decay the lr to 0 by this total step "
                        "count (includes warmup)")
    p.add_argument("--grad-clip-norm", dest="grad_clip_norm", type=float,
                   default=None,
                   help="clip gradients to this global L2 norm (0 = off)")
    p.add_argument("--seed", type=int, default=None)
    p.add_argument("--data-dir", default=None)
    p.add_argument("--tracking", default=None,
                   help="stdout | jsonl | mlflow | noop")
    p.add_argument("--tracking-uri", default=None)
    p.add_argument("--dtype", choices=["float32", "bfloat16"], default=None,
                   help="compute dtype (params stay float32 — mixed "
                        "precision)")
    p.add_argument("--remat", action="store_const", const=True, default=None,
                   help="rematerialize stage forwards in the backward pass "
                        "(jax.checkpoint — trades FLOPs for HBM)")
    p.add_argument("--checkpoint-dir", dest="checkpoint_dir", default=None)
    # size overrides for the transformer/vit families (the fixed
    # reference CNN and ResNet reject them)
    p.add_argument("--d-model", dest="d_model", type=int, default=None)
    p.add_argument("--num-heads", dest="num_heads", type=int, default=None)
    p.add_argument("--client-depth", dest="client_depth", type=int,
                   default=None, help="blocks in the client stage")
    p.add_argument("--server-depth", dest="server_depth", type=int,
                   default=None, help="blocks in the server stage")
    p.add_argument("--seq-len", dest="seq_len", type=int, default=None,
                   help="sequence length of the synthetic token/lm "
                        "datasets (default 64; cached per length)")


def _add_autoscale_args(p: argparse.ArgumentParser) -> None:
    """--autoscale* flags shared by train and serve (runtime/autoscale).
    CLI wins over the SLT_AUTOSCALE* env twins; all default to None so
    the merge in runtime.autoscale.args_config can tell 'unset' from
    an explicit value."""
    p.add_argument("--autoscale", action="store_true",
                   help="elastic autoscaling (runtime/autoscale.py): a "
                        "policy reads the telemetry ring each window and "
                        "adds replicas under pressure / retires them via "
                        "the exactly-once handoff when idle (implies "
                        "--telemetry; env twin SLT_AUTOSCALE=1). Off = "
                        "no policy object, static --replicas, "
                        "bit-identical")
    p.add_argument("--autoscale-min", dest="autoscale_min", type=int,
                   default=None,
                   help="floor on live replicas (default 1; env twin "
                        "SLT_AUTOSCALE_MIN). The group starts at "
                        "max(--replicas, this)")
    p.add_argument("--autoscale-max", dest="autoscale_max", type=int,
                   default=None,
                   help="ceiling on live replicas (default 4; env twin "
                        "SLT_AUTOSCALE_MAX)")
    p.add_argument("--autoscale-cooldown-s", dest="autoscale_cooldown_s",
                   type=float, default=None,
                   help="scale-up cooldown in seconds; scale-down gets "
                        "2x (retiring capacity is the slower reflex). "
                        "Default 5; env twin SLT_AUTOSCALE_COOLDOWN_S")


def _config_from_args(args) -> "Config":
    from split_learning_tpu.utils import Config
    overrides = {}
    for field in ("mode", "model", "dataset", "batch_size", "epochs", "lr",
                  "optimizer", "momentum", "weight_decay", "warmup_steps",
                  "decay_steps", "grad_clip_norm",
                  "seed", "data_dir", "tracking", "tracking_uri",
                  "checkpoint_dir", "dtype", "remat"):
        val = getattr(args, field, None)
        if val is not None:
            overrides[field] = val
    for field in ("transport", "num_clients", "num_stages", "microbatches",
                  "schedule", "server_url", "model_parallel",
                  "seq_parallel", "attn"):
        val = getattr(args, field, None)
        if val is not None:
            overrides[field] = val
    return Config.from_env(**overrides)


# --------------------------------------------------------------------- #
# checkpoint layout bookkeeping: meta.json next to the orbax step dirs
# records how the saved tree maps onto parties, so `eval` can reassemble
# the full composition without reconstructing trainers.

def _size_kw_from_args(args) -> Dict[str, Any]:
    """Model-size overrides present on the command line (train + serve
    share them through _add_common)."""
    return {k: v for k, v in (
        ("d_model", getattr(args, "d_model", None)),
        ("num_heads", getattr(args, "num_heads", None)),
        ("client_depth", getattr(args, "client_depth", None)),
        ("server_depth", getattr(args, "server_depth", None)),
    ) if v is not None}


def _plan_size_kw(model: str, size_kw: Dict[str, Any],
                  seq_len: Optional[int]) -> Dict[str, Any]:
    """Plan-builder kwargs derived from the user-visible size overrides.
    ``max_len`` (the positional-table extent a long ``--seq-len``
    forces) is DERIVED here at every build site and never persisted —
    storing it in checkpoint meta would make the saved ``size_kw``
    compare unequal to the same command line's flags."""
    kw = dict(size_kw)
    if seq_len and seq_len > 2048 \
            and model in ("transformer", "transformer_lm"):
        kw["max_len"] = seq_len
    return kw


def _sig_defaults(builder, *names):
    """Read parameter defaults off a plan builder's own signature —
    the one source that cannot drift from the code (ADVICE r4: both the
    size reconciliation and the vit patch guard hardcoded figures the
    builders already declare)."""
    import inspect
    params = inspect.signature(builder).parameters
    return {k: params[k].default for k in names if k in params}


def _builder_size_defaults(model: str) -> Dict[str, Any]:
    """The size-parameterized plan builders' effective defaults.
    Families without size parameters return ``{}`` (their only valid
    size request is "none")."""
    if model in ("transformer", "transformer_lm"):
        from split_learning_tpu.models.transformer import (
            transformer_plan as builder)
    elif model == "vit":
        from split_learning_tpu.models.vit import vit_plan as builder
    else:
        return {}
    return _sig_defaults(builder, "d_model", "num_heads",
                         "client_depth", "server_depth")


def _reconcile_ckpt_sizes(meta: Dict[str, Any], size_kw: Dict[str, Any],
                          seq_len: Optional[int], what: str,
                          model: str = ""):
    """Adopt-or-refuse against a checkpoint's recorded model sizes.
    Returns ``(size_kw, seq_len, error)``: bare invocations adopt the
    saved sizes/seq_len; conflicting explicit ones return an error
    string BEFORE any meta rewrite or restore can run.

    Saved and requested sizes are compared as *effective* plans — each
    merged over the builder's signature defaults — so an explicit flag
    that merely restates a default (``--d-model 64`` against a
    default-size checkpoint, ADVICE r4) is accepted, and only flags
    that would rebuild a genuinely different plan refuse."""
    saved = meta.get("size_kw", {})
    defaults = _builder_size_defaults(model)
    effective_saved = {**defaults, **saved}
    # unspecified flags inherit the checkpoint's values (a subset of
    # matching flags is a match, not a request for defaults)
    effective_req = {**effective_saved, **size_kw}
    if size_kw and effective_saved != effective_req:
        keys = sorted(set(effective_saved) | set(effective_req))
        conflicts = ", ".join(
            f"{k}: saved {effective_saved.get(k)} != requested "
            f"{effective_req.get(k)}" for k in keys
            if effective_saved.get(k) != effective_req.get(k))
        return size_kw, seq_len, (
            f"checkpoint was written with sizes {saved or '{}'} but "
            f"{what} requested {size_kw} ({conflicts})")
    if saved and not size_kw:
        print(f"[ckpt] {what} with the checkpoint's model sizes "
              f"{saved}", file=sys.stderr)
    # the persisted form is canonical either way: an explicit request
    # that reached here is effectively identical, so rebuilding from
    # `saved` reproduces the checkpoint's plan exactly
    size_kw = dict(saved)
    saved_seq = meta.get("seq_len")
    if saved_seq:
        if seq_len is None:
            seq_len = saved_seq
            print(f"[ckpt] {what} with the checkpoint's --seq-len "
                  f"{seq_len}", file=sys.stderr)
        elif seq_len != saved_seq:
            return size_kw, seq_len, (
                f"checkpoint was trained at --seq-len {saved_seq} but "
                f"{what} requested {seq_len}")
    return size_kw, seq_len, None


def _write_ckpt_meta(directory: str, layout: str, cfg,
                     size_kw: Optional[Dict[str, Any]] = None,
                     seq_len: Optional[int] = None) -> None:
    path = os.path.join(os.path.abspath(os.path.expanduser(directory)),
                        "meta.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    meta = {"layout": layout, "mode": cfg.mode, "model": cfg.model,
            "dataset": cfg.dataset}
    if size_kw:
        # non-default model sizes are part of the checkpoint's identity:
        # eval/generate must rebuild the same plan or restore fails on
        # param shapes
        meta["size_kw"] = size_kw
    if seq_len is not None:
        meta["seq_len"] = seq_len
    with open(path, "w") as f:
        json.dump(meta, f)


def _read_ckpt_meta(directory: str) -> Dict[str, Any]:
    path = os.path.join(os.path.abspath(os.path.expanduser(directory)),
                        "meta.json")
    with open(path) as f:
        return json.load(f)


def _assemble_full_params(layout: str, raw: Dict[str, Any]):
    """Per-stage param sequence for plan.apply from a raw checkpoint tree."""
    if layout in ("fused", "pipeline"):
        return raw["trainer"]["params"]
    if layout == "split_local":
        return [raw["client"]["params"], raw["server"]["params"]]
    if layout == "u_split_local":
        return [raw["client_a"]["params"], raw["server"]["params"],
                raw["client_c"]["params"]]
    if layout == "chain":
        # K-stage MPMD chain: client (stage 0) + stage1..stageK-1
        ks = sorted((k for k in raw if k.startswith("stage")),
                    key=lambda k: int(k[5:]))
        return [raw["client"]["params"]] + [raw[k]["params"] for k in ks]
    if layout == "federated":
        return raw["client"]["params"]
    raise ValueError(
        f"cannot evaluate a {layout!r} checkpoint: the client half alone "
        "does not form the full composition (train with --transport local "
        "or fused to checkpoint the joint state)")


def _server_mesh(args, stage_index: int = 0, num_stages: int = 1):
    """Build the sharded-party mesh from ``--mesh-data``/``--mesh-model``
    (train in-process server or stage parties + serve). 1x1 — the
    default — returns None: the runtime keeps the legacy single-device
    programs byte-for-byte. Stage *i* of an in-process chain takes the
    *i*-th block of ``data*model`` devices when the backend has one per
    stage (parallel.mesh.stage_devices); a lone server takes the first.
    Raises ValueError (the CLI config-error type both callers already
    map to exit 2) when the backend has too few devices, with the
    host-platform remedy in the message."""
    data = int(getattr(args, "mesh_data", 1) or 1)
    model = int(getattr(args, "mesh_model", 1) or 1)
    if data * model <= 1:
        return None
    from split_learning_tpu.parallel.mesh import make_host_mesh
    try:
        return make_host_mesh(data=data, model=model,
                              stage_index=stage_index,
                              num_stages=num_stages)
    except RuntimeError as e:
        raise ValueError(str(e)) from e


def _stage_placement(args, stage_index: int, num_stages: int) -> dict:
    """Where stage ``stage_index`` of the in-process chain lives — the
    one place that is decided, as the StageRuntime kwargs that say it.
    When the backend has a block of devices for every stage, stage *i*
    takes the *i*-th (parallel.mesh.stage_devices): a ``mesh`` over it
    when ``--mesh-data/--mesh-model`` ask for one, else the one
    ``device``. Otherwise every stage shares the first block with the
    hub. A party that is alone in its process (``serve``) is not placed
    here: mesh or not, it takes the backend's first devices."""
    mesh = _server_mesh(args, stage_index, num_stages)
    if mesh is not None:
        return {"mesh": mesh}
    from split_learning_tpu.parallel.mesh import stage_devices
    return {"device": stage_devices(stage_index, num_stages)[0]}


def _density_arg(v: str):
    """argparse type for --compress-density: a float, or the literal
    "auto" (PR 18 adaptive density controller, chain wires only)."""
    s = str(v).strip().lower()
    if s == "auto":
        return "auto"
    try:
        return float(s)
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"--compress-density must be a float or 'auto' (got {v!r})")


def _density_or_default(args) -> float:
    """The plain-float density for paths that cannot run the adaptive
    controller (2-party wires, serve replies): 'auto' warns and falls
    back to the historical default."""
    d = getattr(args, "compress_density", 0.1)
    if d == "auto":
        print("[warn] --compress-density auto drives the chain hop "
              "wires only (mode=split, --stages > 2); this wire uses "
              "the fixed default 0.1", file=sys.stderr)
        return 0.1
    return float(d)


def cmd_train(args) -> int:
    # must run before any JAX backend initializes (DCN multi-host, no-op
    # for single-process runs)
    from split_learning_tpu.parallel.distributed import init_multi_host
    multi_host = init_multi_host(
        coordinator_address=getattr(args, "coordinator", None),
        num_processes=getattr(args, "num_processes", None),
        process_id=getattr(args, "process_id", None))

    import jax

    from split_learning_tpu.data import (
        batches, load_dataset, store_from_config)
    from split_learning_tpu.models import get_plan
    from split_learning_tpu.tracking import make_logger
    from split_learning_tpu.runtime import (
        FederatedClientTrainer, ServerRuntime, SplitClientTrainer,
        USplitClientTrainer)
    from split_learning_tpu.runtime.checkpoint import (
        Checkpointer, read_latest_extras, write_extras)
    from split_learning_tpu.transport import LocalTransport
    from split_learning_tpu.utils import Config

    cfg = _config_from_args(args)
    # dataset/model family pairing: a mismatch surfaces deep in the loss
    # as an opaque shape error, so check it up front like the other
    # flag-combination guards in this command
    token_sets = {"tokens", "lm"}
    if cfg.model == "transformer_lm" and cfg.dataset != "lm":
        print(f"[error] model 'transformer_lm' needs per-token targets: "
              f"--dataset lm (got {cfg.dataset!r})", file=sys.stderr)
        return 2
    if cfg.model == "transformer" and cfg.dataset != "tokens":
        print(f"[error] model 'transformer' (sequence classifier) needs "
              f"--dataset tokens (got {cfg.dataset!r})", file=sys.stderr)
        return 2
    if cfg.model not in ("transformer", "transformer_lm") \
            and cfg.dataset in token_sets:
        print(f"[error] dataset {cfg.dataset!r} is token-shaped; model "
              f"{cfg.model!r} consumes images (mnist | cifar10 | "
              "synthetic)", file=sys.stderr)
        return 2
    size_kw = _size_kw_from_args(args)
    seq_len = args.seq_len
    if seq_len is not None and seq_len <= 0:
        print(f"[error] --seq-len must be positive (got {seq_len})",
              file=sys.stderr)
        return 2
    if seq_len is not None and cfg.dataset not in ("tokens", "lm"):
        print(f"[error] --seq-len applies to the token datasets "
              f"(got --dataset {cfg.dataset!r})", file=sys.stderr)
        return 2
    if cfg.checkpoint_dir and getattr(args, "resume", False):
        # a sized checkpoint's identity lives in its meta: resuming
        # without the flags adopts the saved sizes; resuming WITH
        # different ones is refused before meta gets clobbered
        try:
            existing_meta = _read_ckpt_meta(cfg.checkpoint_dir)
        except (FileNotFoundError, json.JSONDecodeError, OSError):
            existing_meta = None
        if existing_meta is not None:
            size_kw, seq_len, err = _reconcile_ckpt_sizes(
                existing_meta, size_kw, seq_len, "--resume",
                model=cfg.model)
            if err:
                print(f"[error] {err}", file=sys.stderr)
                return 2
    try:
        plan = get_plan(model=cfg.model, mode=cfg.mode, dtype=cfg.dtype,
                        **_plan_size_kw(cfg.model, size_kw, seq_len))
    except (ValueError, TypeError) as e:
        print(f"[error] {e}", file=sys.stderr)
        return 2
    ds = load_dataset(cfg.dataset, cfg.data_dir,
                      store=store_from_config(cfg),
                      allow_synthetic=not args.require_real,
                      download=getattr(args, "download", False),
                      seq_len=seq_len)
    if ds.synthetic:
        print(f"[data] using synthetic {ds.name} "
              f"({len(ds.train)} train examples)", file=sys.stderr)
    if multi_host and jax.process_index() != 0:
        # one metrics stream per job: non-coordinator hosts run the same
        # SPMD program but stay silent (≡ only the server logs to MLflow
        # in the reference, src/server_part.py:55)
        cfg = cfg.replace(tracking="noop")
    logger = make_logger(cfg)
    rng = jax.random.PRNGKey(cfg.seed)
    sample = ds.train.x[:cfg.batch_size]

    ckptr = Checkpointer(cfg.checkpoint_dir) if cfg.checkpoint_dir else None

    max_steps = args.steps
    _budget = {"n": max_steps if max_steps else None, "epoch": 0}

    def data_iter():
        # reshuffle per epoch ≡ DataLoader(shuffle=True); each call is one
        # epoch, so derive the permutation seed from the epoch counter
        epoch_seed = cfg.seed + _budget["epoch"]
        _budget["epoch"] += 1

        def gen():
            for xy in batches(ds.train, cfg.batch_size, seed=epoch_seed,
                              drop_remainder=True):
                if _budget["n"] is not None:
                    if _budget["n"] <= 0:
                        return
                    _budget["n"] -= 1
                yield xy
        return gen()

    # --profile-dir: a jax.profiler session around the run. The
    # program's own spans (obs/trace.py) are host events in that trace,
    # beside the device operations and on their clock, and are recorded
    # for as long as the session runs.
    from split_learning_tpu.utils.profiling import device_trace
    profile_dir = getattr(args, "profile_dir", None)
    trace_ctx = device_trace(profile_dir)

    # --trace: record the spans for the whole run and export them as a
    # Chrome trace (no profiler session needed); off by default, and off
    # a span is an annotation and nothing else
    from split_learning_tpu import obs
    trace_path = getattr(args, "trace", None)
    step_tracer = obs.enable() if trace_path else None

    t0 = time.time()
    n_steps = 0
    final_loss = float("nan")
    full_params = None  # for --eval
    server = None       # the 2-party in-process server, when one exists
    chain_meta = None   # PipelineRunner.trace_metadata() (chain path)
    as_cfg = None       # autoscale config (in-process server arm only)
    autoscaler = None   # the live policy pump, when --autoscale is on
    autoscale_ring = None

    if args.transport != "fused":
        # these knobs only exist on the fused single-program path; say so
        # instead of silently ignoring them (round-1 ADVICE)
        if cfg.model_parallel > 1:
            print(f"[warn] --model-parallel ignored on transport="
                  f"{args.transport!r} (tensor parallelism requires the "
                  f"fused transport)", file=sys.stderr)
        if cfg.seq_parallel > 1:
            print(f"[warn] --seq-parallel ignored on transport="
                  f"{args.transport!r} (context parallelism requires the "
                  f"fused transport)", file=sys.stderr)
        if cfg.attn != "full":
            print(f"[warn] --attn {cfg.attn!r} ignored on transport="
                  f"{args.transport!r} (attention math selection requires "
                  f"the fused transport)", file=sys.stderr)
        if (getattr(args, "scan_steps", 0) or 0) > 1:
            print(f"[warn] --scan-steps ignored on transport="
                  f"{args.transport!r} (only the fused transport scans "
                  f"steps)", file=sys.stderr)
    if (getattr(args, "pipeline_depth", 1) or 1) > 1 \
            and args.transport in ("fused", "pipeline"):
        print(f"[warn] --pipeline-depth ignored on transport="
              f"{args.transport!r} (the in-flight window applies to the "
              "MPMD local/http transports; fused/pipeline exchange "
              "in-XLA and have no wire to overlap)", file=sys.stderr)

    if getattr(args, "decouple_bwd", False) \
            and args.transport in ("fused", "pipeline"):
        print(f"[warn] --decouple-bwd ignored on transport="
              f"{args.transport!r} (2BP splits the server party's "
              "reply from its weight update; the fused/pipeline paths "
              "have no server party)", file=sys.stderr)

    if args.transport == "device" \
            and not (cfg.mode == "split" and cfg.num_stages > 2):
        print("[error] --transport device is the co-located MPMD chain "
              "path: it needs mode=split, a chain plan and --stages > 2 "
              "(the 2-party split has no device-native wire — use "
              "--transport local)", file=sys.stderr)
        return 2
    if cfg.mode == "split" and cfg.num_stages > 2 \
            and args.transport in ("local", "http", "device"):
        # K-stage MPMD chain (PR 14): stage 0 trains here, stages
        # 1..K-1 are StageRuntime parties — in-process behind
        # LocalTransports (or zero-copy DeviceTransports, PR 16), or
        # remote `serve --role stage` processes — driven by the
        # microbatched PipelineRunner (GPipe or 1F1B schedule)
        from split_learning_tpu.runtime.pipeline_runner import (
            PipelineRunner)
        from split_learning_tpu.runtime.stage import StageRuntime
        if plan.num_stages != cfg.num_stages:
            print(f"[error] --stages {cfg.num_stages} does not match "
                  f"model {cfg.model!r} ({plan.num_stages} stages); "
                  "pick a chain plan (e.g. split_cnn_chain3, "
                  "resnet18_4stage)", file=sys.stderr)
            return 2
        M = max(cfg.microbatches, 1)
        lag = getattr(args, "apply_lag", 0) or 0
        # per-stage pjit (ISSUE 20): --mesh-data/--mesh-model shard the
        # IN-PROCESS stage parties. The stage's H2D scatter shards each
        # microbatch's batch dim over 'data', so rows-per-microbatch
        # must divide the axis — the sharded server role's rule, per
        # microbatch. Remote http stages pick their own mesh at serve
        # time.
        chain_mesh_data = int(getattr(args, "mesh_data", 1) or 1)
        chain_mesh_model = int(getattr(args, "mesh_model", 1) or 1)
        if chain_mesh_data * chain_mesh_model > 1 \
                and args.transport == "http":
            print("[warn] --mesh-data/--mesh-model shard in-process "
                  "stage parties; remote http stages take their own "
                  "mesh flags at serve time — ignored here",
                  file=sys.stderr)
        elif chain_mesh_data > 1 and (
                cfg.batch_size % M
                or (cfg.batch_size // M) % chain_mesh_data):
            print(f"[error] --mesh-data {chain_mesh_data} needs the "
                  f"per-microbatch rows (batch_size/microbatches = "
                  f"{cfg.batch_size}/{M}) divisible by the data axis — "
                  "the same rule as the sharded server role",
                  file=sys.stderr)
            return 2
        # replicated stage parties (ISSUE 20): every in-process stage
        # fronts a ReplicaGroup, same router/handoff seam as the server
        # role. Host-reply wires only — a device wire's replay entries
        # are device-resident and die with the replica.
        chain_replicas = getattr(args, "replicas", 1) or 1
        if chain_replicas > 1 and args.transport != "local":
            print("[error] --replicas > 1 on the chain composes "
                  "in-process stage parties behind the group router "
                  "and needs --transport local (http stages are their "
                  "own processes; the device wire's replay entries are "
                  "device-resident and die with the replica)",
                  file=sys.stderr)
            return 2
        if chain_replicas > 1 and cfg.checkpoint_dir:
            # mirror the replicated server role's refusal: the group's
            # checkpoint story is the handoff sidecar, not N interleaved
            # per-stage trees in one directory
            print("[error] --replicas > 1 does not compose with "
                  "--checkpoint-dir yet (per-replica save/resume "
                  "layout is ambiguous); drop one of them",
                  file=sys.stderr)
            return 2
        stage_rts: list = []
        transports: list = []
        # compressed hop wires (PR 18): --compress extends the 2-party
        # codec to every hop of the chain; --compress-density auto binds
        # one adaptive DensityController across all of them. The
        # device wire is exempt — it ships device buffers zero-copy,
        # there are no wire bytes to compress.
        chain_compress = getattr(args, "compress", None)
        if chain_compress and args.transport == "device":
            print("[warn] --compress ignored on --transport device "
                  "(zero-copy device wire; nothing to compress)",
                  file=sys.stderr)
            chain_compress = None
        chain_dc = None
        chain_density = getattr(args, "compress_density", 0.1)
        if chain_density == "auto":
            if chain_compress in ("topk8", "clapping"):
                from split_learning_tpu.transport.density import (
                    DensityController)
                chain_dc = DensityController()
                chain_density = 0.1  # fallback; controller drives wires
            else:
                print("[warn] --compress-density auto needs --compress "
                      "topk8 or clapping; using the fixed default 0.1",
                      file=sys.stderr)
                chain_density = 0.1
        chain_ef_mode = ("clapping" if chain_compress == "clapping"
                         else "topk8")
        if args.transport == "http":
            from split_learning_tpu.transport.http import HttpTransport
            urls = [u.strip() for u in
                    (getattr(args, "stage_urls", None) or "").split(",")
                    if u.strip()]
            if len(urls) != plan.num_stages - 1:
                print(f"[error] chain over http needs --stage-urls with "
                      f"{plan.num_stages - 1} URLs (one per remote "
                      f"stage, chain order; got {len(urls)})",
                      file=sys.stderr)
                return 2
            for i, url in enumerate(urls):
                t = HttpTransport(url,
                                  compress=chain_compress or "none",
                                  density=chain_density,
                                  density_controller=chain_dc,
                                  wire_id=f"hop{i + 1}")
                info = t.wait_ready(timeout=args.wait_server)
                if info.get("role") != "stage" \
                        or info.get("stage_index") != i + 1:
                    print(f"[error] {url} reports "
                          f"role={info.get('role')!r} "
                          f"stage_index={info.get('stage_index')!r}; "
                          f"expected a stage {i + 1} party (start it "
                          f"with serve --role stage --stage-index "
                          f"{i + 1})", file=sys.stderr)
                    return 4
                if info.get("microbatches") != M:
                    print(f"[error] {url} serves microbatches="
                          f"{info.get('microbatches')} but this client "
                          f"runs --microbatches {M}; the 1/M loss "
                          "scaling must agree", file=sys.stderr)
                    return 4
                transports.append(t)
        else:
            from split_learning_tpu.runtime.replica import maybe_replicate
            per_stage = chain_mesh_data * chain_mesh_model
            if 1 < len(jax.devices()) < plan.num_stages * per_stage:
                # _stage_placement's other arm, said once: devices sit
                # idle and the user did not ask for that
                print(f"[pipeline] {len(jax.devices())} devices < "
                      f"{plan.num_stages} stages x {per_stage} per stage: "
                      f"every stage shares the first {per_stage} "
                      "device(s) with the hub", file=sys.stderr)
            for i in range(1, plan.num_stages):
                def _make_stage(_ridx: int = 0, _i: int = i):
                    # same PRNGKey per replica: one stage model, N
                    # servers of it (the server role's convention)
                    return StageRuntime(plan, _i, cfg,
                                        jax.random.PRNGKey(cfg.seed),
                                        sample, microbatches=M,
                                        apply_lag=lag,
                                        ef_mode=chain_ef_mode,
                                        **_stage_placement(
                                            args, _i, plan.num_stages))
                srt = maybe_replicate(_make_stage, chain_replicas)
                stage_rts.append(srt)
                if args.transport == "device":
                    # zero-copy co-located wire: device buffers hand
                    # off straight through, the loss scalar is the one
                    # sanctioned D2H (transport/device.py)
                    from split_learning_tpu.transport.device import (
                        DeviceTransport)
                    transports.append(DeviceTransport(srt))
                else:
                    transports.append(LocalTransport(
                        srt, compress=chain_compress,
                        density=chain_density,
                        density_controller=chain_dc))
        chaos_spec = getattr(args, "chaos", None)
        if chaos_spec:
            from split_learning_tpu.transport.chaos import (
                ChaosPolicy, ChaosTransport)
            chaos_policy = ChaosPolicy(
                chaos_spec, seed=getattr(args, "chaos_seed", 0) or 0)
            # one policy, every hop wire: the seeded draws key on
            # (path, hop_seq) so the schedules stay disjoint per wire
            # direction and microbatch
            transports = [ChaosTransport(t, chaos_policy)
                          for t in transports]
            print(f"[chaos] injecting {chaos_spec!r} "
                  f"(seed {chaos_policy.seed}) on every hop wire",
                  file=sys.stderr)
        runner = PipelineRunner(plan, cfg, rng, sample, transports,
                                microbatches=M, schedule=cfg.schedule)
        runner.density_controller = chain_dc  # None unless density=auto
        if chain_compress:
            print(f"[compress] chain hop wires: {chain_compress} "
                  f"(density "
                  f"{'auto' if chain_dc is not None else chain_density}, "
                  f"ef {chain_ef_mode})", file=sys.stderr)

        # telemetry plane (PR 17): the hub is a party too — give it a
        # windowed ring over its own step/hop registry and (with
        # --telemetry-port) a /telemetry endpoint the FleetCollector
        # scrapes alongside the stage parties'. Off (no SLT_TELEMETRY,
        # no port) = zero overhead, loss series bit-for-bit legacy.
        from split_learning_tpu.obs import telemetry as obs_telemetry
        hub_ring = None
        hub_tel_srv = None
        tel_port = getattr(args, "telemetry_port", None)
        tel_cfg = obs_telemetry.env_config()
        if tel_cfg is None and tel_port is not None:
            tel_cfg = {"interval_s": obs_telemetry.DEFAULT_INTERVAL_S,
                       "capacity": obs_telemetry.DEFAULT_CAPACITY}
        if tel_cfg is not None:
            from split_learning_tpu import obs
            from split_learning_tpu.obs import federate as obs_federate
            from split_learning_tpu.obs.metrics import Registry
            if obs.get_tracer() is None:
                # windows derive their percentiles from the tracer-gated
                # histograms; telemetry on implies tracing on
                obs.enable()
            hub_reg = Registry()
            runner.telemetry_registry = hub_reg
            hub_ring = obs_telemetry.enable(
                hub_reg.snapshot, party="hub",
                interval_s=tel_cfg["interval_s"],
                capacity=tel_cfg["capacity"],
                slo=obs_telemetry.tracker_from_config(tel_cfg))
            if tel_port is not None:
                hub_tel_srv, _ = obs_federate.serve_telemetry(
                    hub_ring, port=int(tel_port))
                print(f"[telemetry] hub /telemetry on port "
                      f"{hub_tel_srv.server_address[1]}", file=sys.stderr)
            hub_ring.start_sampler()

        start_step = 0
        if ckptr is not None:
            _write_ckpt_meta(cfg.checkpoint_dir, "chain", cfg, size_kw,
                             seq_len)
            latest = ckptr.latest_step()
            if args.resume and latest is not None and stage_rts:
                tree = {"client": runner.state}
                for srt in stage_rts:
                    tree[f"stage{srt.stage_index}"] = srt.state
                tree = ckptr.restore(tree)
                runner.state = tree["client"]
                for srt in stage_rts:
                    # per-stage extras sidecar lives under stage<i>/ —
                    # each party's replay cache restores (or clears)
                    # independently
                    d = os.path.join(ckptr.directory,
                                     f"stage{srt.stage_index}")
                    srt.resume_from(
                        tree[f"stage{srt.stage_index}"], latest,
                        extras=read_latest_extras(d, step=latest))
                start_step = latest
                runner.steps_done = latest
                print(f"[ckpt] chain resumed at step {start_step} from "
                      f"{cfg.checkpoint_dir}", file=sys.stderr)
            elif args.resume and latest is not None:
                print("[warn] --resume over http stage parties resumes "
                      "only the client stage; restart the stage "
                      "processes with their own checkpoints",
                      file=sys.stderr)

        def save_chain(step: int) -> None:
            if ckptr is None or not stage_rts:
                return
            tree = {"client": runner.state}
            for srt in stage_rts:
                # export_state flushes each stage's deferred queue
                # first: the joint snapshot never captures a party
                # that is apply_lag updates behind its shipped replies
                tree[f"stage{srt.stage_index}"] = srt.export_state()
            if ckptr.save_once(step, tree):
                for srt in stage_rts:
                    d = os.path.join(ckptr.directory,
                                     f"stage{srt.stage_index}")
                    os.makedirs(d, exist_ok=True)
                    write_extras(d, srt.export_runtime_extras(step))

        step = start_step
        bad_losses = 0
        try:
            with _ckpt_drain(ckptr), trace_ctx:
                for epoch in range(cfg.epochs):
                    for x, y in data_iter():
                        final_loss = runner.step(x, y, step)
                        if not np.isfinite(final_loss):
                            bad_losses += 1
                        logger.log_metric("loss", final_loss, step=step)
                        step += 1
                        if (args.checkpoint_every
                                and (step - start_step)
                                % args.checkpoint_every == 0):
                            save_chain(step)
                    save_chain(step)
        finally:
            chain_meta = runner.trace_metadata()
            if hub_ring is not None:
                hub_ring.advance(force=True)  # close the last window
                if hub_tel_srv is not None:
                    hub_tel_srv.shutdown()
                obs_telemetry.disable()
            runner.close()
            for t in transports:
                close = getattr(t, "close", None)
                if close is not None:
                    close()
            for srt in stage_rts:
                srt.close()
            if ckptr is not None:
                ckptr.wait_until_finished()
        n_steps = step - start_step
        for i, t in enumerate(transports):
            print(f"[transport] hop {i + 1}: {t.stats.summary()}",
                  file=sys.stderr)
        print(f"[pipeline] stage 0 (hub): "
              f"devices={chain_meta.get('hub_devices')}", file=sys.stderr)
        # the stage -> device-ids map as a record, not only as prose
        # (chip_smoke.py reads it from the jsonl tracker)
        logger.log_params({"stage_devices": {
            0: chain_meta.get("hub_devices"),
            **{st["stage"]: st.get("devices")
               for st in chain_meta.get("stages", [])}}})
        for st in chain_meta.get("stages", []):
            bf = st.get("bubble_fraction")
            print(f"[pipeline] stage {st['stage']} "
                  f"[{st.get('schedule', 'gpipe')}]: bubble="
                  f"{bf if bf is None else round(bf, 3)} "
                  f"(ideal {st['bubble_theoretical']:.3f}) "
                  f"reply_p50={st['reply_p50_ms']:.1f}ms "
                  f"devices={st.get('devices')}",
                  file=sys.stderr)
        dc_snap = chain_meta.get("density")
        if dc_snap is not None:
            print(f"[density] adaptive controller: "
                  f"windows={dc_snap['windows_closed']} "
                  f"densities={dc_snap['densities']} "
                  f"(budget {dc_snap['budget_nats']} nats / "
                  f"{dc_snap['window']}-step window)", file=sys.stderr)
        if getattr(args, "gate_dropped_steps", False):
            # fleet_sim's exactly-once gate, on the MPMD chain: every
            # scheduled step produced a finite loss AND every stage
            # party acknowledged the last step — a replica handoff or
            # resharded hop that silently ate a microbatch shows up as
            # a lagging health step
            want = step - 1

            def _stage_step(srt) -> int:
                h = srt.health()
                grp = h.get("replicas")
                if grp is not None and "step_max" in grp:
                    # replicated party: the trained state may sit on
                    # any live replica — gate on the group-wide max
                    return int(grp["step_max"])
                return int(h.get("step", -1))

            lagging = [(srt.stage_index, _stage_step(srt))
                       for srt in stage_rts
                       if _stage_step(srt) != want]
            if bad_losses or lagging:
                print(f"[gate] DROPPED-STEPS GATE FAILED: "
                      f"nonfinite_losses={bad_losses} "
                      f"lagging_stages={lagging} (want step {want})",
                      file=sys.stderr)
                return 1
            handoffs = sum(
                int(srt.counters().get("replica_handoffs", 0))
                for srt in stage_rts if hasattr(srt, "counters"))
            print(f"[gate] ok: {n_steps} steps completed, 0 dropped"
                  + (f" ({handoffs} replica handoff(s))"
                     if handoffs else ""), file=sys.stderr)
        if stage_rts:
            full_params = [runner.state.params] + [
                srt.export_state().params for srt in stage_rts]
    elif args.transport in ("fused", "pipeline"):
        from split_learning_tpu.parallel import global_mesh
        from split_learning_tpu.parallel.mesh import replicated
        if args.transport == "fused":
            from split_learning_tpu.runtime.fused import FusedSplitTrainer
            transformer_family = cfg.model in ("transformer",
                                               "transformer_lm")
            # vit carries the same attention trunk: its sequence axis is
            # the patch-token stream (models/vit.py)
            attention_family = transformer_family or cfg.model == "vit"
            if cfg.seq_parallel > 1 and not attention_family:
                # without this guard the trainer would shard an image dim
                # over 'seq' (or fail on divisibility) — not context
                # parallelism; only the attention families have a seq axis
                print(f"[warn] --seq-parallel ignored: model {cfg.model!r} "
                      "has no sequence axis (transformer/vit only)",
                      file=sys.stderr)
                cfg = cfg.replace(seq_parallel=1)
            if cfg.seq_parallel > 1 and cfg.model == "vit":
                # vit's token count is fixed by the image grid: the ring/
                # Ulysses shard_map needs it divisible by the seq axis.
                # The patch size comes from vit_plan's own signature so
                # this guard cannot drift from the builder (ADVICE r4)
                from split_learning_tpu.models.vit import vit_plan
                patch = _sig_defaults(vit_plan, "patch")["patch"]
                h, w, _ = sample.shape[1:]
                t_tokens = (h // patch) * (w // patch)
                if t_tokens % cfg.seq_parallel:
                    print(f"[warn] --seq-parallel {cfg.seq_parallel} "
                          f"ignored: {t_tokens} patch tokens "
                          f"({h}x{w}, patch {patch}) do not divide "
                          "across it", file=sys.stderr)
                    cfg = cfg.replace(seq_parallel=1)
            mesh = None
            if (cfg.num_clients > 1 or cfg.model_parallel > 1
                    or cfg.seq_parallel > 1 or multi_host):
                mesh = global_mesh(num_clients=cfg.num_clients, num_stages=1,
                                   model_parallel=cfg.model_parallel,
                                   seq_parallel=cfg.seq_parallel)
            if attention_family and cfg.attn in ("ring", "ring_flash",
                                                 "ulysses") and (
                    mesh is None or "seq" not in mesh.axis_names
                    or mesh.shape["seq"] == 1):
                # ring_attention falls back to single-device math when
                # there is no seq axis to rotate over (dense for ring,
                # the flash kernel for ring_flash) — say so instead of
                # silently training without context parallelism
                fallback = ("the single-device flash kernel"
                            if cfg.attn == "ring_flash"
                            else "dense attention")
                print(f"[warn] --attn {cfg.attn!r} runs as {fallback}: "
                      "no 'seq' mesh axis (pass --seq-parallel > 1 to "
                      "shard the sequence)", file=sys.stderr)
            if attention_family and (cfg.seq_parallel > 1
                                     or cfg.attn != "full"):
                # the seq-parallel attention forms need the mesh at plan
                # build time (the shard_map closes over it)
                # same derived kwargs as the first build: dropping the
                # max_len a long --seq-len forces would cap the rebuilt
                # plan at the 2048 default and crash the first forward
                plan_kw = _plan_size_kw(cfg.model, size_kw, seq_len)
                if cfg.model == "vit":
                    from split_learning_tpu.models.vit import vit_plan
                    plan = vit_plan(mode=cfg.mode,
                                    dtype=np.dtype(cfg.dtype),
                                    mesh=mesh, attn=cfg.attn, **plan_kw)
                else:
                    from split_learning_tpu.models.transformer import (
                        transformer_plan)
                    plan = transformer_plan(mode=cfg.mode,
                                            dtype=np.dtype(cfg.dtype),
                                            mesh=mesh, attn=cfg.attn,
                                            lm=cfg.model == "transformer_lm",
                                            **plan_kw)
            elif cfg.attn != "full":
                print(f"[warn] --attn {cfg.attn!r} ignored: model "
                      f"{cfg.model!r} has no attention (transformer/vit "
                      "only)", file=sys.stderr)
            trainer = FusedSplitTrainer(plan, cfg, rng, sample, mesh=mesh)
        else:
            from split_learning_tpu.parallel.pipeline import PipelinedTrainer
            mesh = global_mesh(num_clients=cfg.num_clients,
                               num_stages=plan.num_stages)
            trainer = PipelinedTrainer(plan, cfg, rng, sample, mesh)

        start_step = 0
        if ckptr is not None:
            _write_ckpt_meta(cfg.checkpoint_dir, "fused", cfg, size_kw,
                             seq_len)
            latest = ckptr.latest_step()
            if args.resume and latest is not None:
                tree = ckptr.restore({"trainer": trainer.state})
                state = tree["trainer"]
                if mesh is not None:
                    # the trainer's own sharding tree, NOT plain replication:
                    # under tensor parallelism the jitted step expects
                    # 'model'-sharded weight leaves
                    state = jax.device_put(state, trainer.state_sharding)
                trainer.state = state
                start_step = latest
                print(f"[ckpt] resumed at step {start_step} from "
                      f"{cfg.checkpoint_dir}", file=sys.stderr)

        def save(step: int) -> None:
            if ckptr is not None:
                ckptr.save_once(step, {"trainer": trainer.state})

        scan = getattr(args, "scan_steps", 0) or 0
        can_scan = args.transport == "fused" and scan > 1
        if can_scan and ckptr is not None and args.checkpoint_every:
            # a scan chunk is one opaque device dispatch — saves can only
            # happen at chunk boundaries. Cap the chunk so every
            # --checkpoint-every boundary still produces a save instead of
            # silently coarsening the cadence.
            if scan > args.checkpoint_every:
                print(f"[warn] --scan-steps {scan} capped to "
                      f"--checkpoint-every {args.checkpoint_every} so "
                      f"checkpoint cadence is preserved", file=sys.stderr)
                scan = args.checkpoint_every
                # a cap to 1 means every step checkpoints — scanning buys
                # nothing; fall back to the stepwise path
                can_scan = scan > 1
        if can_scan and jax.devices()[0].platform == "cpu":
            # XLA CPU runs the scan-rolled epoch far slower than eager
            # per-step dispatch (~40x measured); the flag is a TPU idiom
            print("[warn] --scan-steps on CPU is typically much slower "
                  "than stepwise dispatch; intended for TPU", file=sys.stderr)

        step = start_step
        # observable schedules: when an lr schedule is active, log the
        # applied rate alongside the loss (fused path; the schedule
        # itself lives inside the optimizer via make_tx). make_lr
        # returns a plain float when no schedule is configured — that
        # return shape, not a re-statement of its trigger condition,
        # decides whether to log
        from split_learning_tpu.runtime.state import make_lr
        lr_fn = make_lr(cfg)
        if not callable(lr_fn):
            lr_fn = None
        with _ckpt_drain(ckptr), trace_ctx:
            for epoch in range(cfg.epochs):  # step cap enforced by data_iter
                if can_scan:
                    # chunk T batches into one lax.scan dispatch; the
                    # returned loss series keeps per-step logging exact.
                    # The tail (< scan batches) runs stepwise so
                    # train_epoch only ever compiles for one T.
                    buf_x, buf_y = [], []
                    for x, y in data_iter():
                        buf_x.append(x)
                        buf_y.append(y)
                        if len(buf_x) == scan:
                            losses = np.asarray(trainer.train_epoch(
                                np.stack(buf_x), np.stack(buf_y)))
                            buf_x, buf_y = [], []
                            lrs = None
                            if lr_fn is not None:
                                # one vectorized schedule eval per chunk,
                                # not one tiny dispatch per step
                                lrs = np.asarray(lr_fn(
                                    step + np.arange(len(losses))))
                            for i, loss_i in enumerate(losses):
                                final_loss = float(loss_i)
                                logger.log_metric("loss", final_loss,
                                                  step=step)
                                if lrs is not None:
                                    logger.log_metric(
                                        "lr", float(lrs[i]), step=step)
                                step += 1
                            if (args.checkpoint_every
                                    and (step - start_step)
                                    // args.checkpoint_every
                                    != (step - start_step - len(losses))
                                    // args.checkpoint_every):
                                save(step)
                    tail = zip(buf_x, buf_y)
                else:
                    tail = data_iter()
                for x, y in tail:
                    final_loss = trainer.train_step(x, y)
                    logger.log_metric("loss", final_loss, step=step)
                    if lr_fn is not None:
                        logger.log_metric("lr", float(lr_fn(step)), step=step)
                    step += 1
                    if (args.checkpoint_every
                            and (step - start_step) % args.checkpoint_every
                            == 0):
                        save(step)
                save(step)
        n_steps = step - start_step
        full_params = trainer.state.params
    else:
        # MPMD path: a transport to a (possibly remote) server party
        depth = getattr(args, "pipeline_depth", 1) or 1
        if depth > 1 and cfg.mode != "split":
            print(f"[warn] --pipeline-depth ignored in mode {cfg.mode!r} "
                  "(split only)", file=sys.stderr)
            depth = 1
        server: Optional[ServerRuntime] = None
        transport_factory = None
        if args.transport == "http":
            from split_learning_tpu.transport.http import HttpTransport
            density = _density_or_default(args)
            # pool >= depth: a shared session with W > 10 lanes would
            # otherwise serialize on urllib3's default pool of 10
            pool = max(32, depth)
            transport = HttpTransport(cfg.server_url,
                                      compress=args.compress or "none",
                                      density=density, pool_maxsize=pool)
            if depth > 1:  # one connection per in-flight lane
                transport_factory = lambda: HttpTransport(  # noqa: E731
                    cfg.server_url, compress=args.compress or "none",
                    density=density, pool_maxsize=pool)
            # readiness barrier: the reference's client starts blind and
            # silently drops every pre-server batch (SURVEY.md §3.4)
            info = transport.wait_ready(timeout=args.wait_server)
            if info.get("mode") not in (cfg.mode, None):
                print(f"[transport] server is in mode {info.get('mode')!r} "
                      f"but this client wants {cfg.mode!r}", file=sys.stderr)
                return 4
            # default True when absent: servers predating the field are
            # strict by default, and those are exactly the ones to reject
            if depth > 1 and info.get("strict_steps", True):
                # fail fast: with W lanes, arrival order is a thread race
                # and a strict server 409s nondeterministically mid-run
                print(f"[transport] --pipeline-depth {depth} needs the "
                      "server started with serve --allow-out-of-order "
                      "(it reports strict_steps=true)", file=sys.stderr)
                return 5
        else:
            # in-process server: out-of-order arrival is part of the deal
            # for a depth-W window, so strictness follows the depth
            def _make_replica(_idx: int) -> ServerRuntime:
                # every replica from the SAME PRNGKey: the group starts
                # as one model, and FedAvg sync keeps it one
                return ServerRuntime(plan, cfg,
                                     jax.random.PRNGKey(cfg.seed),
                                     sample, strict_steps=depth <= 1,
                                     decouple_bwd=getattr(
                                         args, "decouple_bwd", False),
                                     apply_lag=getattr(
                                         args, "apply_lag", 0) or 0,
                                     mesh=_server_mesh(args))
            from split_learning_tpu.runtime.replica import (
                ReplicaGroup, maybe_replicate)
            # elastic autoscaling (PR 19): CLI over SLT_AUTOSCALE* env;
            # None when off — static --replicas, bit-identical
            from split_learning_tpu.runtime import (
                autoscale as rt_autoscale)
            as_cfg = rt_autoscale.args_config(args)
            _group_kw = dict(
                sync_every=getattr(args, "replica_sync_every", 0) or 0,
                handoff=getattr(args, "handoff", "live") or "live",
                seed=cfg.seed,
                # compressed replica sync rides the same switch as the
                # wire (PR 18); int8/none keep the dense legacy sync
                sync_compress=(args.compress if args.compress in
                               ("topk8", "clapping") else None),
                sync_density=_density_or_default(args))
            if as_cfg is not None:
                # the elastic arm always fronts a ReplicaGroup — even
                # at one starting replica, scale-up needs the router
                n0 = max(getattr(args, "replicas", 1) or 1,
                         as_cfg["min_replicas"])
                server = ReplicaGroup(
                    [_make_replica(i) for i in range(n0)], **_group_kw)
            else:
                server = maybe_replicate(
                    _make_replica, getattr(args, "replicas", 1) or 1,
                    **_group_kw)
            # --compress plumbs here too (wire emulation through the real
            # codec) so compressed-path runs don't need sockets; None
            # keeps the legacy direct path bit-for-bit
            transport = LocalTransport(
                server, compress=args.compress,
                density=_density_or_default(args))
            if as_cfg is not None:
                # autoscale implies telemetry (the policy's signals ARE
                # the ring's windows) and tracing (the ring's
                # percentiles come from the tracer-gated histograms)
                if obs.get_tracer() is None:
                    obs.enable()
                from split_learning_tpu.obs import telemetry as obs_tel
                tcfg = obs_tel.env_config() or {
                    "interval_s": obs_tel.DEFAULT_INTERVAL_S,
                    "capacity": obs_tel.DEFAULT_CAPACITY}
                autoscale_ring = obs_tel.enable(
                    server.metrics, party="server",
                    interval_s=tcfg["interval_s"],
                    capacity=tcfg["capacity"],
                    slo=obs_tel.tracker_from_config(tcfg))
                autoscale_ring.start_sampler()
                autoscaler = rt_autoscale.Autoscaler(
                    server, _make_replica,
                    rt_autoscale.policy_from_config(as_cfg),
                    autoscale_ring, slo_ms=tcfg.get("slo_ms"))
                autoscaler.start(autoscale_ring.interval_s)
                print(f"[autoscale] policy on: "
                      f"min={as_cfg['min_replicas']} "
                      f"max={as_cfg['max_replicas']} "
                      f"cooldown={as_cfg['cooldown_s']}s",
                      file=sys.stderr)
        chaos_spec = getattr(args, "chaos", None)
        if chaos_spec:
            # seeded fault injection wraps whichever wire was built —
            # same spec + same seed = the same faults at the same steps
            # (transport/chaos.py); absent, the wire is untouched
            from split_learning_tpu.transport.chaos import (
                ChaosPolicy, ChaosTransport)
            chaos_policy = ChaosPolicy(
                chaos_spec, seed=getattr(args, "chaos_seed", 0) or 0)
            transport = ChaosTransport(transport, chaos_policy)
            if transport_factory is not None:
                inner_factory = transport_factory
                transport_factory = lambda: ChaosTransport(  # noqa: E731
                    inner_factory(), chaos_policy)
            print(f"[chaos] injecting {chaos_spec!r} "
                  f"(seed {chaos_policy.seed}) on the client wire",
                  file=sys.stderr)
        fail_policy = getattr(args, "failure_policy", None) or "raise"
        breaker = None
        if fail_policy != "raise" and (cfg.mode != "split" or depth > 1):
            print(f"[warn] --failure-policy {fail_policy} applies to the "
                  "serialized split client only; ignored here",
                  file=sys.stderr)
            fail_policy = "raise"
        if fail_policy == "retry":
            # retry clients probe /health instead of hammering a dead
            # server with full payloads (runtime/breaker.py)
            from split_learning_tpu.runtime import CircuitBreaker
            # probe jitter is seeded from the run config (SLT004: the
            # chaos-soak probe schedule must reproduce run to run)
            breaker = CircuitBreaker(transport.health, seed=cfg.seed)
        if cfg.mode == "split":
            if depth > 1:
                from split_learning_tpu.runtime import (
                    PipelinedSplitClientTrainer)
                client = PipelinedSplitClientTrainer(
                    plan, cfg, rng, transport, depth=depth,
                    transport_factory=transport_factory, logger=logger)
            else:
                client = SplitClientTrainer(
                    plan, cfg, rng, transport,
                    failure_policy=fail_policy,
                    max_retries=getattr(args, "max_retries", 3),
                    logger=logger, breaker=breaker)
            layout = "split_local" if server is not None else "client_only"
        elif cfg.mode == "u_split":
            client = USplitClientTrainer(plan, cfg, rng, transport,
                                         logger=logger)
            layout = "u_split_local" if server is not None else "client_only"
        else:
            client = FederatedClientTrainer(plan, cfg, rng, transport,
                                            logger=logger)
            layout = "federated"
        client.ensure_init(sample)

        def party_tree() -> Dict[str, Any]:
            tree: Dict[str, Any] = {}
            if cfg.mode == "u_split":
                tree["client_a"] = client.state_a
                tree["client_c"] = client.state_c
            else:
                tree["client"] = client.state
            if server is not None:
                # export_state, not .state: joint checkpoints must not
                # capture a server half that is apply_lag updates behind
                # the replies the client half already trained on
                tree["server"] = server.export_state()
            return tree

        start_step = 0
        if ckptr is not None:
            _write_ckpt_meta(cfg.checkpoint_dir, layout, cfg, size_kw,
                             seq_len)
            latest = ckptr.latest_step()
            if args.resume and latest is not None:
                tree = ckptr.restore(party_tree())
                if cfg.mode == "u_split":
                    client.state_a = tree["client_a"]
                    client.state_c = tree["client_c"]
                else:
                    client.state = tree["client"]
                if server is not None:
                    # re-arms the step handshake: every client must resume
                    # at or after the restored step (runtime/server.py).
                    # The extras sidecar — replay cache + EF residuals —
                    # restores with it when one was written for this
                    # exact step; otherwise resume_from falls back to
                    # clearing both (stale-lineage rejection)
                    server.resume_from(
                        tree["server"], latest,
                        extras=read_latest_extras(ckptr.directory,
                                                  step=latest))
                start_step = latest
                print(f"[ckpt] resumed at step {start_step} from "
                      f"{cfg.checkpoint_dir}", file=sys.stderr)
                if layout == "client_only":
                    # remote server half: verify it is not behind this
                    # checkpoint (a fresh server + resumed client would
                    # silently desync the composition — the reference
                    # hazard, SURVEY.md §3.4). Servers report their
                    # acknowledged step in /health; serve --checkpoint-dir
                    # --resume restores it.
                    srv_step = transport.health().get("step", -1)
                    if srv_step < start_step - 1:
                        print(f"[ckpt] server is at step {srv_step} but the "
                              f"client checkpoint is at {start_step}: the "
                              "server half was not resumed. Restart it with "
                              "serve --checkpoint-dir ... --resume, or drop "
                              "--resume here to start both halves fresh.",
                              file=sys.stderr)
                        return 3

        def on_epoch_end(epoch: int, next_step: int) -> None:
            if ckptr is not None:
                if ckptr.save_once(next_step, party_tree()) \
                        and server is not None:
                    # the runtime-extras sidecar rides beside every Orbax
                    # save: one small JSON, written tmp+fsync+rename so a
                    # crash can never leave a readable half-file
                    write_extras(ckptr.directory,
                                 server.export_runtime_extras(next_step))

        prefetch = getattr(args, "prefetch", 0) or 0
        if prefetch > 0 and cfg.mode != "split":
            print(f"[warn] --prefetch ignored in mode {cfg.mode!r} "
                  "(split only)", file=sys.stderr)
            prefetch = 0
        train_kwargs: Dict[str, Any] = {}
        if prefetch > 0:
            train_kwargs["prefetch"] = prefetch
        try:
            with trace_ctx:
                records = client.train(data_iter, epochs=cfg.epochs,
                                       start_step=start_step,
                                       on_epoch_end=on_epoch_end,
                                       **train_kwargs)
        finally:
            if autoscaler is not None:
                # stop the pump before anything tears down: a scale
                # event must not race the post-run export/eval reads
                autoscaler.close()
            if autoscale_ring is not None:
                from split_learning_tpu.obs import telemetry as obs_tel
                obs_tel.disable()
            if hasattr(client, "close"):  # pipelined: join lanes + conns
                client.close()
            if ckptr is not None:
                # saves are async — barrier on them even when an epoch
                # raises, or the newest checkpoint on disk can be an
                # in-flight write torn by interpreter teardown
                ckptr.wait_until_finished()
        n_steps = len(records)
        final_loss = records[-1].loss if records else float("nan")
        # pipelined client: its .stats merges every lane's transport —
        # lane 0 alone would undercount round trips/bytes by ~depth
        stats = client.stats if hasattr(client, "stats") else transport.stats
        print(f"[transport] {stats.summary()}", file=sys.stderr)
        if stats.round_trips:
            # the north-star latency series (SURVEY.md §5 metrics)
            logger.log_metric("transport_p50_ms",
                              stats.percentile(50) * 1e3,
                              step=n_steps)

        if cfg.mode == "federated":
            full_params = client.state.params
        elif server is not None:
            # export_state: the eval composition must include any
            # deferred applies still queued (--decouple-bwd)
            if cfg.mode == "u_split":
                full_params = [client.state_a.params,
                               server.export_state().params,
                               client.state_c.params]
            else:
                full_params = [client.state.params,
                               server.export_state().params]

    if profile_dir:
        # the spans recorded while the profiler session ran
        rec = obs.recorder()
        if rec is not None and rec.phase_summary():
            print(f"[profile] {json.dumps(rec.phase_summary())}",
                  file=sys.stderr)
            frac = rec.fraction(obs.spans.TRANSPORT)
            if frac > 0:  # 0.0 = no transport phase (fused/single-program)
                print(f"[profile] transport fraction: {frac:.3f}",
                      file=sys.stderr)
        print(f"[profile] XLA trace written to {profile_dir} "
              "(device operations and the program's spans on one "
              "clock; view in TensorBoard/Perfetto)", file=sys.stderr)
    if step_tracer is not None:
        obs.disable()
        out_path = step_tracer.export_chrome(
            trace_path,
            metadata=server.trace_metadata() if server is not None else None,
            stage_metadata=chain_meta)
        print(f"[trace] {len(step_tracer.spans())} spans -> {out_path} "
              "(Perfetto-loadable; summarize with scripts/trace_report.py)",
              file=sys.stderr)

    dt = time.time() - t0
    if n_steps and dt > 0:
        logger.log_metric("steps_per_sec", n_steps / dt, step=n_steps)
    if ckptr is not None:
        # finally use the artifact root the reference configures but never
        # writes to (SURVEY.md §5 checkpoint gap); no-op off-mlflow.
        # saves are async now — drain them before shipping the directory
        ckptr.wait_until_finished()
        logger.log_artifact(ckptr.directory)

    if args.eval:
        if full_params is None:
            print("[eval] full composition unavailable over a remote "
                  "transport; skipping", file=sys.stderr)
        else:
            from split_learning_tpu.runtime.evaluate import evaluate
            res = evaluate(plan, full_params, ds.test,
                           batch_size=cfg.batch_size)
            logger.log_metric("test_accuracy", res["accuracy"], step=n_steps)
            logger.log_metric("test_loss", res["loss"], step=n_steps)
            print(f"[eval] accuracy={res['accuracy']:.4f} "
                  f"loss={res['loss']:.4f} n={res['predictions']}")

    logger.close()
    print(f"[done] mode={cfg.mode} transport={args.transport} "
          f"steps={n_steps} final_loss={final_loss:.4f} "
          f"({n_steps / dt:.2f} steps/s)")
    return 0


def cmd_serve(args) -> int:
    import jax

    from split_learning_tpu.models import get_plan
    from split_learning_tpu.runtime import ServerRuntime
    from split_learning_tpu.runtime.checkpoint import (
        Checkpointer, read_latest_extras, write_extras)
    from split_learning_tpu.transport.http import SplitHTTPServer

    from split_learning_tpu.data.datasets import _SHAPES

    cfg = _config_from_args(args)
    size_kw = _size_kw_from_args(args)
    seq_len = getattr(args, "seq_len", None)
    if cfg.checkpoint_dir:
        try:
            prior = _read_ckpt_meta(cfg.checkpoint_dir)
        except (FileNotFoundError, json.JSONDecodeError, OSError):
            prior = None
        if prior is not None:
            size_kw, seq_len, err = _reconcile_ckpt_sizes(
                prior, size_kw, seq_len, "serve", model=cfg.model)
            if err:
                print(f"[error] {err}", file=sys.stderr)
                return 2
    try:
        plan = get_plan(model=cfg.model, mode=cfg.mode, dtype=cfg.dtype,
                        **_plan_size_kw(cfg.model, size_kw, seq_len))
    except (ValueError, TypeError) as e:
        print(f"[error] {e}", file=sys.stderr)
        return 2
    if cfg.model in ("transformer", "transformer_lm"):
        # token models init from an integer sequence sample (the image
        # shape below would crash the embed); T follows the reconciled
        # --seq-len / checkpoint meta, falling back to the dataset
        # generators' default
        from split_learning_tpu.data.datasets import _TOKEN_SEQ_LEN
        sample = np.zeros((cfg.batch_size, seq_len or _TOKEN_SEQ_LEN),
                          np.int32)
    else:
        shape = _SHAPES.get(
            "mnist" if cfg.dataset == "synthetic" else cfg.dataset,
            (28, 28, 1))
        sample = np.zeros((cfg.batch_size,) + shape, np.float32)
    role = getattr(args, "role", "server") or "server"
    as_cfg = None  # autoscale config; stays None for stage parties
    if role == "stage":
        # one middle/last party of the K-stage MPMD chain (PR 14): the
        # same HTTP wire, serving the hop ops instead of split_step
        from split_learning_tpu.runtime.stage import StageRuntime
        if getattr(args, "autoscale", False):
            print("[warn] --autoscale applies to the replicated server "
                  "role only; ignored for --role stage", file=sys.stderr)
        if cfg.checkpoint_dir:
            print("[warn] stage parties do not own checkpoints; "
                  "--checkpoint-dir ignored (the chain client saves the "
                  "joint tree over local transports)", file=sys.stderr)
            cfg = cfg.replace(checkpoint_dir=None)
        try:
            runtime = StageRuntime(
                plan, getattr(args, "stage_index", 1) or 1, cfg,
                jax.random.PRNGKey(cfg.seed), sample,
                strict_steps=not args.allow_out_of_order,
                microbatches=max(cfg.microbatches, 1),
                apply_lag=args.apply_lag,
                tenants=args.tenants, quota=args.quota,
                slo_ms=args.slo_ms, mesh=_server_mesh(args),
                ef_mode=("clapping" if args.compress == "clapping"
                         else "topk8"))
        except ValueError as e:  # e.g. stage_index out of range
            print(f"[error] {e}", file=sys.stderr)
            return 2
    else:
        n_replicas = getattr(args, "replicas", 1) or 1
        # elastic autoscaling (PR 19): CLI over SLT_AUTOSCALE* env; None
        # when off — no policy object, static --replicas, bit-identical
        from split_learning_tpu.runtime import autoscale as rt_autoscale
        as_cfg = rt_autoscale.args_config(args)
        if (n_replicas > 1 or as_cfg is not None) and cfg.checkpoint_dir:
            # the group's checkpoint story is the handoff sidecar, not N
            # interleaved Orbax trees in one directory — refuse the
            # ambiguous layout instead of writing it
            print("[error] --replicas > 1 / --autoscale does not compose "
                  "with --checkpoint-dir yet (per-replica save/resume "
                  "layout is ambiguous); drop one of them",
                  file=sys.stderr)
            return 2
        try:
            def _make_replica(_idx: int) -> ServerRuntime:
                # same PRNGKey for every replica: one model, N servers
                return ServerRuntime(
                    plan, cfg, jax.random.PRNGKey(cfg.seed),
                    sample,
                    strict_steps=not args.allow_out_of_order,
                    coalesce_max=args.coalesce_max,
                    coalesce_window_ms=args.coalesce_window_ms,
                    batching=args.batching,
                    tenants=args.tenants,
                    quota=args.quota,
                    slo_ms=args.slo_ms,
                    decouple_bwd=args.decouple_bwd,
                    apply_lag=args.apply_lag,
                    mesh=_server_mesh(args),
                    ef_mode=("clapping" if args.compress == "clapping"
                             else "topk8"))
            from split_learning_tpu.runtime.replica import (
                ReplicaGroup, maybe_replicate)
            sync_compress = (args.compress if args.compress in
                             ("topk8", "clapping") else None)
            sync_density = float(getattr(args, "compress_density",
                                         0.1) or 0.1)
            if as_cfg is not None:
                # the elastic arm always fronts a ReplicaGroup — even at
                # one starting replica, scale-up needs the router seam
                n0 = max(n_replicas, as_cfg["min_replicas"])
                runtime = ReplicaGroup(
                    [_make_replica(i) for i in range(n0)],
                    sync_every=getattr(args, "replica_sync_every", 0) or 0,
                    handoff=getattr(args, "handoff", "live") or "live",
                    seed=cfg.seed, sync_compress=sync_compress,
                    sync_density=sync_density)
            else:
                runtime = maybe_replicate(
                    _make_replica, n_replicas,
                    sync_every=getattr(args, "replica_sync_every", 0) or 0,
                    handoff=getattr(args, "handoff", "live") or "live",
                    seed=cfg.seed,
                    sync_compress=sync_compress,
                    sync_density=sync_density)
        except ValueError as e:  # e.g. --coalesce-max outside split mode
            print(f"[error] {e}", file=sys.stderr)
            return 2

    # the server party owns its half's persistence (the client cannot
    # checkpoint it across HTTP): periodic saves + resume with the step
    # handshake re-armed, so a restarted pair picks up in sync
    ckptr = None
    if cfg.checkpoint_dir:
        # a joint checkpoint dir (written by local/fused training) holds
        # both halves under a different layout: resume the server half
        # from it, but never overwrite its meta or mix server-only step
        # trees into it — periodic saves go to a server_party/ subdir,
        # and on restart the NEWER of (joint root, server_party) wins
        try:
            existing = _read_ckpt_meta(cfg.checkpoint_dir)
        except FileNotFoundError:
            existing = None
        except (json.JSONDecodeError, OSError) as e:
            print(f"[ckpt] meta.json unreadable ({e}); treating "
                  f"{cfg.checkpoint_dir} as a server-only dir",
                  file=sys.stderr)
            existing = None
        joint = existing is not None and existing.get(
            "layout", "server_only") != "server_only"
        if existing is not None:
            for key, got in (("mode", cfg.mode), ("model", cfg.model)):
                want = existing.get(key)
                if want is not None and want != got:
                    print(f"[ckpt] checkpoint dir was written with "
                          f"{key}={want!r} but serve was started with "
                          f"{key}={got!r}; refusing to resume a "
                          "mismatched server half", file=sys.stderr)
                    return 2
        if joint:
            save_dir = os.path.join(cfg.checkpoint_dir, "server_party")
            ckptr = Checkpointer(save_dir)
            _write_ckpt_meta(save_dir, "server_only", cfg, size_kw,
                             seq_len)
            print(f"[ckpt] joint-layout dir: periodic server saves go to "
                  f"{save_dir}", file=sys.stderr)
        else:
            ckptr = Checkpointer(cfg.checkpoint_dir)
            _write_ckpt_meta(cfg.checkpoint_dir, "server_only", cfg,
                             size_kw, seq_len)
        latest = ckptr.latest_step()
        if args.resume and joint:
            # a prior serve on this joint dir may have saved newer
            # server-only state under server_party/ — prefer it; else
            # restore the server's share of the joint tree
            root = Checkpointer(cfg.checkpoint_dir)
            try:
                root_latest = root.latest_step()
                if root_latest is not None and (latest is None
                                                or root_latest > latest):
                    layout = (existing or {}).get("layout")
                    if layout in ("fused", "pipeline"):
                        # single-program layouts store one whole-plan
                        # tree: take the server's share of the params
                        # and re-init the optimizer for them (exact for
                        # the reference's plain constant-lr SGD;
                        # stateful optimizers restart their moments on
                        # this handoff — the joint opt_state spans all
                        # parties and cannot be attributed per stage
                        # generically)
                        import jax.numpy as jnp
                        from split_learning_tpu.runtime.state import (
                            make_state)
                        if cfg.warmup_steps or cfg.decay_steps \
                                or cfg.momentum \
                                or cfg.optimizer != "sgd":
                            print("[ckpt] note: optimizer state "
                                  "(moments / lr-schedule position) "
                                  "restarts on a fused-layout handoff; "
                                  "params and the step handshake are "
                                  "exact", file=sys.stderr)
                        raw = root.restore_raw(root_latest)
                        raw_params = raw["trainer"]["params"]
                        # federated servers own the full composition;
                        # split/u_split own one stage
                        sp = (tuple(raw_params) if cfg.mode == "federated"
                              else raw_params[runtime.server_stage])
                        st = make_state(sp, runtime._tx)._replace(
                            step=jnp.asarray(root_latest, jnp.int32))
                        del raw, raw_params, sp  # the joint tree is ~3x
                        # the served stage; don't pin it for the whole
                        # server lifetime
                        runtime.resume_from(st, root_latest)
                    else:
                        try:
                            tree = root.restore_partial(
                                {"server": runtime.state}, root_latest)
                        except KeyError:
                            # client_only / remote-server federated
                            # trees carry no server half to resume
                            print(f"[error] checkpoint layout "
                                  f"{layout or 'split_local'!r} under "
                                  f"{cfg.checkpoint_dir} has no server "
                                  "subtree to resume (it was written by "
                                  "a client whose server was remote)",
                                  file=sys.stderr)
                            return 2
                        runtime.resume_from(
                            tree["server"], root_latest,
                            extras=read_latest_extras(cfg.checkpoint_dir,
                                                      step=root_latest))
                    print(f"[ckpt] server resumed at step {root_latest} "
                          f"from joint {cfg.checkpoint_dir} "
                          f"(layout {layout or 'split_local'})",
                          file=sys.stderr)
                    latest = None  # handled; skip the server_party branch
            finally:
                root.close()
        if args.resume and latest is not None:
            tree = ckptr.restore({"server": runtime.state})
            # sidecar restore: replay cache + EF residuals come back iff
            # an extras file was written for exactly this step (anything
            # stale is rejected and resume_from clears instead)
            runtime.resume_from(
                tree["server"], latest,
                extras=read_latest_extras(ckptr.directory, step=latest))
            print(f"[ckpt] server resumed at step {latest} from "
                  f"{ckptr.directory}", file=sys.stderr)

        every = max(args.checkpoint_every, 1)

        def on_step(step: int) -> None:
            # save_once: no barriering latest_step() here — this hook runs
            # under the runtime lock, so a barrier would stall every client
            # on the previous in-flight write. export_state() (not
            # .state) flushes any deferred applies first (--decouple-bwd:
            # the live state may be up to apply_lag updates behind); the
            # flush only dispatches async jitted calls, so it is safe
            # under the lock this hook already holds.
            if (step + 1) % every == 0:
                if ckptr.save_once(step + 1,
                                   {"server": runtime.export_state()}):
                    # one small JSON beside the (async) Orbax save: the
                    # replay cache + EF residuals a restart needs to keep
                    # duplicate delivery exactly-once. tmp+fsync+rename,
                    # so no crash point leaves a readable half-file.
                    write_extras(ckptr.directory,
                                 runtime.export_runtime_extras(step + 1))

        runtime.on_step = on_step

    trace_path = getattr(args, "trace", None)
    step_tracer = None
    if trace_path:
        from split_learning_tpu import obs
        step_tracer = obs.enable()
        print(f"[serve] tracing on: /metrics histograms live; Chrome "
              f"trace -> {trace_path} on shutdown", file=sys.stderr)

    chaos_policy = None
    if getattr(args, "chaos", None):
        from split_learning_tpu.transport.chaos import ChaosPolicy
        chaos_policy = ChaosPolicy(
            args.chaos, seed=getattr(args, "chaos_seed", 0) or 0)
        print(f"[chaos] injecting {args.chaos!r} "
              f"(seed {chaos_policy.seed}) server-side", file=sys.stderr)

    # telemetry plane (PR 17): --telemetry (or SLT_TELEMETRY) hangs a
    # windowed ring off this party's metrics() and serves it on
    # GET /telemetry; CLI flags win over the env knobs. Telemetry
    # implies tracing (the windows' percentiles come from the
    # tracer-gated histograms). Off = the legacy routes, bit-for-bit.
    from split_learning_tpu.obs import telemetry as obs_telemetry
    telemetry_ring = None
    tel_cfg = obs_telemetry.env_config()
    if tel_cfg is None and (getattr(args, "telemetry", False)
                            or as_cfg is not None):
        # --autoscale implies telemetry: the policy's signals ARE the
        # ring's windows
        tel_cfg = {"interval_s": obs_telemetry.DEFAULT_INTERVAL_S,
                   "capacity": obs_telemetry.DEFAULT_CAPACITY}
    if tel_cfg is not None:
        if getattr(args, "telemetry_interval_s", None):
            tel_cfg["interval_s"] = float(args.telemetry_interval_s)
        if getattr(args, "telemetry_slo_ms", None):
            tel_cfg["slo_ms"] = float(args.telemetry_slo_ms)
        if step_tracer is None:
            from split_learning_tpu import obs
            if obs.get_tracer() is None:
                obs.enable()
        party = (f"stage{getattr(args, 'stage_index', 1) or 1}"
                 if role == "stage" else "server")
        telemetry_ring = obs_telemetry.enable(
            runtime.metrics, party=party,
            interval_s=tel_cfg["interval_s"],
            capacity=tel_cfg["capacity"],
            slo=obs_telemetry.tracker_from_config(
                tel_cfg, tenants=getattr(args, "tenants", 1) or 1))
        telemetry_ring.start_sampler()
        print(f"[telemetry] windowed ring on: GET /telemetry "
              f"(interval {tel_cfg['interval_s']}s, "
              f"capacity {tel_cfg['capacity']})", file=sys.stderr)

    autoscaler = None
    if as_cfg is not None:
        # policy + pump over the live group; scale-up spawns via the
        # same factory the group was built from, scale-down drives the
        # exactly-once handoff (runtime/autoscale.py)
        from split_learning_tpu.runtime.autoscale import (
            Autoscaler, policy_from_config)
        autoscaler = Autoscaler(
            runtime, _make_replica, policy_from_config(as_cfg),
            telemetry_ring,
            coalesce_max=getattr(args, "coalesce_max", 1) or 1,
            slo_ms=(tel_cfg.get("slo_ms")
                    or (getattr(args, "slo_ms", 0) or None)))
        autoscaler.start(telemetry_ring.interval_s)
        print(f"[autoscale] policy on: min={as_cfg['min_replicas']} "
              f"max={as_cfg['max_replicas']} "
              f"cooldown={as_cfg['cooldown_s']}s", file=sys.stderr)

    server = SplitHTTPServer(runtime, host=args.host, port=args.port,
                             compress=args.compress or "none",
                             density=args.compress_density,
                             chaos=chaos_policy,
                             telemetry=telemetry_ring).start()
    print(f"[serve] mode={cfg.mode} role={role} listening on {server.url}")
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        print("[serve] shutting down")
        server.stop()
    finally:
        if autoscaler is not None:
            # stop the pump first: a scale event must not race the
            # group teardown below
            autoscaler.close()
        if telemetry_ring is not None:
            telemetry_ring.advance(force=True)
            obs_telemetry.disable()
        runtime.close()  # flush + join the coalescer, if one is running
        if step_tracer is not None:
            from split_learning_tpu import obs
            obs.disable()
            step_tracer.export_chrome(
                trace_path,
                metadata=(runtime.trace_metadata()
                          if hasattr(runtime, "trace_metadata") else None))
            print(f"[trace] Chrome trace written to {trace_path}",
                  file=sys.stderr)
        if ckptr is not None:
            # saves are async — make the in-flight checkpoint durable
            # before the process exits, or a resume comes back behind the
            # clients' own checkpoints (step-handshake mismatch)
            ckptr.close()
    return 0


def _resolve_checkpoint(args, cfg, cmd: str, require_model: str = None):
    """Shared eval/generate preamble: meta-aware mode/model/dataset
    resolution (``args.X or meta[X] or cfg.X``), plan build, latest-or-
    ``--step`` pick, raw restore, full-composition assembly. Returns
    ``(None, rc)`` on user error, else ``((meta, mode, model, dataset,
    plan, step, params, seq_len), None)`` — the trailing ``seq_len`` is
    the checkpoint-reconciled sequence extent the caller's dataset load
    must use."""
    from split_learning_tpu.models import get_plan
    from split_learning_tpu.runtime.checkpoint import Checkpointer

    ckdir = cfg.checkpoint_dir
    if not ckdir:
        print(f"{cmd} requires --checkpoint-dir", file=sys.stderr)
        return None, 2
    meta = _read_ckpt_meta(ckdir)
    mode = args.mode or meta.get("mode", cfg.mode)
    model = args.model or meta.get("model", cfg.model)
    dataset = args.dataset or meta.get("dataset", cfg.dataset)
    if require_model and model != require_model:
        print(f"[error] {cmd} needs a {require_model!r} checkpoint "
              f"(got {model!r})", file=sys.stderr)
        return None, 2
    # the checkpoint's recorded sizes AND seq_len are authoritative —
    # explicit flags must match or be absent, never silently overridden
    # (the returned seq_len is what the caller's dataset load must use)
    size_kw, seq_len, err = _reconcile_ckpt_sizes(
        meta, _size_kw_from_args(args), getattr(args, "seq_len", None),
        cmd, model=model)
    if err:
        print(f"[error] {err}", file=sys.stderr)
        return None, 2
    plan = get_plan(model=model, mode=mode, dtype=cfg.dtype,
                    **_plan_size_kw(model, size_kw, seq_len))
    ckptr = Checkpointer(ckdir)
    step = args.step if args.step is not None else ckptr.latest_step()
    params = _assemble_full_params(meta["layout"], ckptr.restore_raw(step))
    return (meta, mode, model, dataset, plan, step, params, seq_len), None


def cmd_eval(args) -> int:
    from split_learning_tpu.data import load_dataset
    from split_learning_tpu.runtime.evaluate import evaluate

    cfg = _config_from_args(args)
    resolved, rc = _resolve_checkpoint(args, cfg, "eval")
    if resolved is None:
        return rc
    meta, mode, model, dataset, plan, step, params, seq_len = resolved
    from split_learning_tpu.data import store_from_config as _sfc
    # seq_len comes reconciled from _resolve_checkpoint: the
    # checkpoint's recorded T, already checked against any explicit flag
    ds = load_dataset(dataset, cfg.data_dir, store=_sfc(cfg),
                      seq_len=seq_len if dataset in ("tokens", "lm")
                      else None)
    record = {"checkpoint_step": step, "dataset": dataset}
    if getattr(args, "server_url", None):
        # split-party inference: client stages local, server compute
        # behind /predict (the serving peer's weights, not the
        # checkpoint's server half)
        from split_learning_tpu.runtime.evaluate import evaluate_remote
        from split_learning_tpu.transport.http import HttpTransport
        transport = HttpTransport(args.server_url)
        try:
            transport.wait_ready(timeout=60.0)
            client_params = [params[i] for i in plan.stages_of("client")]
            res = evaluate_remote(plan, client_params, transport, ds.test,
                                  batch_size=cfg.batch_size)
        finally:
            transport.close()
        record["remote_server"] = args.server_url
    else:
        res = evaluate(plan, params, ds.test, batch_size=cfg.batch_size)
    record.update({
        "accuracy": round(res["accuracy"], 4),
        "loss": round(res["loss"], 4),
        "perplexity": (None if res["perplexity"] is None
                       else round(res["perplexity"], 4)),
        "examples": res["examples"],
        "predictions": res["predictions"],
    })
    print(json.dumps(record))
    return 0


def cmd_generate(args) -> int:
    """Decode from a causal-LM checkpoint: KV-cache local decode by
    default, O(T²) re-forward with --no-kv-cache, split-party remote
    decode (client stages local, server compute behind /predict) with
    --server-url."""
    import jax

    from split_learning_tpu.runtime.generate import (
        generate_remote, greedy_generate, sample_generate)

    cfg = _config_from_args(args)

    # cheap flag validation before the (expensive) checkpoint restore;
    # every rejection is an [error] + rc 2, like the rest of the CLI.
    # No falsy-zero coercion: --temperature 0 / --top-p 0 are errors
    # with the library's own explanations, never a silent rewrite.
    sampled = (args.temperature is not None or args.top_p is not None
               or args.top_k > 0)
    temperature = 1.0 if args.temperature is None else args.temperature
    top_p = 1.0 if args.top_p is None else args.top_p
    if sampled and not temperature > 0.0:
        print(f"[error] --temperature must be > 0 (got {temperature}); "
              "omit all sampling flags for deterministic greedy decode",
              file=sys.stderr)
        return 2
    if sampled and not 0.0 < top_p <= 1.0:
        print(f"[error] --top-p must be in (0, 1] (got {top_p})",
              file=sys.stderr)
        return 2
    if args.top_k < 0:
        print(f"[error] --top-k must be >= 0 (got {args.top_k})",
              file=sys.stderr)
        return 2
    tokens = None
    if args.prompt:
        try:
            tokens = [int(tok) for tok in args.prompt.split(",")]
        except ValueError:
            print(f"[error] --prompt must be comma-separated token ids "
                  f"(got {args.prompt!r})", file=sys.stderr)
            return 2
        if any(tok < 0 for tok in tokens):
            print(f"[error] --prompt token ids must be >= 0 "
                  f"(got {args.prompt!r})", file=sys.stderr)
            return 2

    resolved, rc = _resolve_checkpoint(args, cfg, "generate",
                                       require_model="transformer_lm")
    if resolved is None:
        return rc
    meta, mode, model, dataset, plan, step, params, seq_len = resolved

    if tokens is not None:
        prompt = np.asarray([tokens], np.int32)
        # the embedding gather CLAMPS out-of-range ids (JAX semantics),
        # which would silently decode from the wrong tokens — bound
        # them against the checkpoint's own token-embed table, found by
        # its flax param path (nn.Embed stores its [vocab, D] table
        # under the unique leaf name "embedding"; the [max_len, D]
        # positional table is a raw "pos" param and can't shadow it).
        # No match = unknown layout, skip the check
        vocab = None
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                params[0])[0]:
            if any("embedding" in str(k) for k in path) \
                    and getattr(leaf, "ndim", 0) == 2:
                vocab = leaf.shape[0]
                break
        if vocab is not None:
            bad = [tok for tok in tokens if tok >= vocab]
            if bad:
                print(f"[error] --prompt ids {bad} are outside the "
                      f"checkpoint's vocabulary ({vocab})", file=sys.stderr)
                return 2
    else:
        # no prompt: seed from the dataset's test split, like eval
        from split_learning_tpu.data import load_dataset, store_from_config
        ds = load_dataset(dataset, cfg.data_dir,
                          store=store_from_config(cfg),
                          seq_len=seq_len if dataset in ("tokens", "lm")
                          else None)
        prompt = np.asarray(ds.test.x[:1, :args.prompt_len], np.int32)

    record = {"checkpoint_step": step, "prompt_len": int(prompt.shape[1]),
              "n_new": args.n_new,
              "decode": "sampled" if sampled else "greedy"}
    if args.server_url:
        from split_learning_tpu.transport.http import HttpTransport
        transport = HttpTransport(args.server_url)
        try:
            transport.wait_ready(timeout=60.0)
            client_params = [params[i] for i in plan.stages_of("client")]
            kw = {}
            if sampled:
                kw = dict(rng=jax.random.PRNGKey(cfg.seed),
                          temperature=temperature,
                          top_k=args.top_k, top_p=top_p)
            out = generate_remote(plan, client_params, transport, prompt,
                                  args.n_new, **kw)
        finally:
            transport.close()
        record["remote_server"] = args.server_url
    elif sampled:
        out = sample_generate(plan, params, prompt, args.n_new,
                              jax.random.PRNGKey(cfg.seed),
                              temperature=temperature,
                              top_k=args.top_k, top_p=top_p,
                              kv_cache=not args.no_kv_cache)
    else:
        out = greedy_generate(plan, params, prompt, args.n_new,
                              kv_cache=not args.no_kv_cache)
    out = np.asarray(out)
    record["prompt"] = out[:, :prompt.shape[1]].tolist()
    record["tokens"] = out[:, prompt.shape[1]:].tolist()
    print(json.dumps(record))
    return 0


def _run_with_flight(args) -> int:
    """Dispatch one subcommand under the flight recorder's process-level
    dump triggers (obs/flight.py): ``--flight PATH`` arms the recorder
    and dumps the journal on normal exit (trigger #4); SIGTERM and a
    fatal exception dump it on the way down (trigger #2). With neither
    the flag nor ``SLT_FLIGHT`` set this is a plain ``args.fn(args)`` —
    the recorder stays ``None`` and nothing here allocates."""
    from split_learning_tpu.obs import flight as obs_flight
    party = "server" if args.cmd == "serve" else "client"
    flight_path = getattr(args, "flight", None)
    if flight_path:
        # the CLI flag is both switch and dump path; it wins over any
        # recorder SLT_FLIGHT already armed
        obs_flight.enable(party=party, dump_path=flight_path)
    else:
        obs_flight.maybe_enable_from_env(party=party)
    if obs_flight.enabled():
        import signal

        def _on_sigterm(signum, frame):
            obs_flight.fatal("sigterm", f"signal {signum}")
            signal.signal(signal.SIGTERM, signal.SIG_DFL)
            os.kill(os.getpid(), signal.SIGTERM)

        try:
            signal.signal(signal.SIGTERM, _on_sigterm)
        except ValueError:
            pass  # not the main thread (embedded use): no signal hook
    try:
        rc = args.fn(args)
    except Exception as exc:
        # fatal-exception dump: journal what led up to the crash, then
        # let the exception propagate untouched
        obs_flight.fatal(type(exc).__name__, str(exc))
        raise
    fl = obs_flight.get_recorder()
    if fl is not None and fl.dump_path:
        out = fl.dump_json(fl.dump_path, reason="exit")
        print(f"[flight] {len(fl.events())} events -> {out} "
              "(merge with scripts/postmortem.py)", file=sys.stderr)
    return rc


def main(argv: Optional[list] = None) -> int:
    from split_learning_tpu.utils import configure_compile_cache
    configure_compile_cache()  # before the first compile of the process
    ap = argparse.ArgumentParser(prog="split_learning_tpu")
    sub = ap.add_subparsers(dest="cmd", required=True)

    pt = sub.add_parser("train", help="run a training client (or full sim)")
    _add_common(pt)
    pt.add_argument("--transport",
                    choices=["local", "http", "device", "fused",
                             "pipeline"],
                    default="fused")
    pt.add_argument("--schedule", choices=["gpipe", "1f1b"], default=None,
                    help="MPMD chain injection schedule (PR 16): gpipe "
                         "streams all --microbatches out up front; 1f1b "
                         "warms up min(stages, microbatches) then runs "
                         "strict 1-forward-1-backward — same loss bit "
                         "for bit, bounded in-flight depth")
    pt.add_argument("--server-url", dest="server_url", default=None)
    pt.add_argument("--wait-server", dest="wait_server", type=float,
                    default=60.0,
                    help="seconds to wait for the server /health barrier "
                         "(http transport)")
    pt.add_argument("--steps", type=int, default=0,
                    help="stop after N steps (0 = full epochs)")
    pt.add_argument("--profile-dir", dest="profile_dir", default=None,
                    help="write a jax.profiler XLA trace here and report "
                         "per-phase (compute vs transport) wall-clock")
    pt.add_argument("--trace", default=None, metavar="PATH",
                    help="per-step span tracing (obs/): write a Chrome-"
                         "trace JSON here on exit (Perfetto-loadable; "
                         "summarize with scripts/trace_report.py). Off = "
                         "zero overhead")
    pt.add_argument("--telemetry-port", dest="telemetry_port", type=int,
                    default=None,
                    help="MPMD chain only: serve the hub's windowed "
                         "telemetry ring on this port's GET /telemetry "
                         "(0 = ephemeral), so obs/federate.py's "
                         "FleetCollector can scrape hub + stages as one "
                         "fleet; also turns telemetry on for this run "
                         "(SLT_TELEMETRY=1 does too, without the port)")
    pt.add_argument("--flight", default=None, metavar="PATH",
                    help="flight recorder (obs/flight.py): journal causal "
                         "runtime events into a bounded ring and dump "
                         "them here as JSON on exit / SIGTERM / fatal "
                         "exception / watchdog trip (merge with "
                         "scripts/postmortem.py). Off = zero overhead")
    pt.add_argument("--scan-steps", dest="scan_steps", type=int, default=0,
                    help="fused transport: batch N steps per device "
                         "dispatch via lax.scan (per-step losses still "
                         "logged; big dispatch-bound speedup)")
    pt.add_argument("--num-clients", dest="num_clients", type=int,
                    default=None)
    pt.add_argument("--model-parallel", dest="model_parallel", type=int,
                    default=None,
                    help="tensor-parallel shards (mesh 'model' axis; "
                         "fused transport)")
    pt.add_argument("--seq-parallel", dest="seq_parallel", type=int,
                    default=None,
                    help="context-parallel shards (mesh 'seq' axis; fused "
                         "transport, transformer family — ring/Ulysses "
                         "attention over ICI)")
    pt.add_argument("--mesh-data", dest="mesh_data", type=int, default=1,
                    help="sharded in-process server (local transport): "
                         "'data' axis size — batch dims and coalesced "
                         "groups shard across it. 1 = legacy single-"
                         "device server, bit-for-bit")
    pt.add_argument("--mesh-model", dest="mesh_model", type=int, default=1,
                    help="sharded in-process server: 'model' axis size — "
                         "heavy weight matrices shard across it "
                         "(parallel/distributed.py SpecLayout rule)")
    pt.add_argument("--attn",
                    choices=["full", "flash", "auto", "ring", "ring_flash",
                             "ulysses"],
                    default=None,
                    help="transformer attention math (flash = Pallas "
                         "blockwise kernels; ring/ulysses shard the "
                         "sequence and need --seq-parallel > 1)")
    pt.add_argument("--coordinator", default=None,
                    help="host:port of process 0 for multi-host DCN runs "
                         "(or SLT_COORDINATOR; on k8s, a headless Service)")
    pt.add_argument("--num-processes", dest="num_processes", type=int,
                    default=None, help="total hosts in the multi-host job")
    pt.add_argument("--process-id", dest="process_id", type=int, default=None,
                    help="this host's index (k8s: the pod ordinal)")
    pt.add_argument("--microbatches", type=int, default=None)
    pt.add_argument("--stages", dest="num_stages", type=int, default=None,
                    help="pipeline stages. On --transport local/http with "
                         "mode=split and a chain plan (split_cnn_chain3, "
                         "resnet18_4stage), > 2 selects the K-stage MPMD "
                         "chain: stage 0 trains here, every other stage "
                         "is a StageRuntime party and --microbatches "
                         "GPipe-fills the hop wires (PR 14)")
    pt.add_argument("--stage-urls", dest="stage_urls", default=None,
                    metavar="URL[,URL...]",
                    help="chain over http: comma-separated stage party "
                         "URLs in chain order (stage 1 first), one per "
                         "remote stage — each a `serve --role stage` "
                         "process")
    pt.add_argument("--require-real", action="store_true",
                    help="fail if real dataset files are absent instead of "
                         "falling back to synthetic data")
    pt.add_argument("--download", action="store_true",
                    help="on a raw-file miss, download the canonical "
                         "distribution into --data-dir (sha256-verified; "
                         "default stays hermetic/offline)")
    pt.add_argument("--compress",
                    choices=["none", "int8", "topk8", "clapping"],
                    default=None,
                    help="wire compression of the cut-layer tensors "
                         "(http/local transports) and, in a chain run "
                         "(--stages > 2), of every hop wire: int8 = "
                         "dense 4x quantization; topk8 = top-k "
                         "sparsification + int8 with error feedback "
                         "(~17x at the default density); clapping = "
                         "topk8 selection with storage-free error "
                         "feedback — nothing persisted or migrated "
                         "(README 'Pipeline compression')")
    pt.add_argument("--compress-density", dest="compress_density",
                    type=_density_arg, default=0.1,
                    help="topk8/clapping: fraction of elements shipped "
                         "per step (default 0.1), or 'auto' — the "
                         "deterministic adaptive density controller "
                         "(chain runs only): tightens per-wire density "
                         "while end-loss stays inside a rolling parity "
                         "budget, loosens every wire when it drifts")
    pt.add_argument("--pipeline-depth", dest="pipeline_depth", type=int,
                    default=1,
                    help="split mode, local/http transports: keep up to N "
                         "cut-layer exchanges in flight (bounded-staleness "
                         "async SGD; an http server needs "
                         "--allow-out-of-order when N > 1)")
    pt.add_argument("--prefetch", dest="prefetch", type=int, default=0,
                    help="split mode: stage the next N batches on device "
                         "while the current step is in flight (background "
                         "H2D transfer; 0 = off, 2 is a good start)")
    pt.add_argument("--decouple-bwd", dest="decouple_bwd",
                    action="store_true",
                    help="split mode, local transport: 2BP reply-first "
                         "server — return the cut-layer gradient from a "
                         "forward+grad-of-acts dispatch immediately and "
                         "defer the weight update off the reply critical "
                         "path (see README 'Decoupled backward (2BP)'); "
                         "off = the fused legacy step, bit-identical")
    pt.add_argument("--apply-lag", dest="apply_lag", type=int, default=0,
                    help="with --decouple-bwd: let up to N weight "
                         "updates queue before the reply path drains "
                         "them — step t's forward may then use weights "
                         "from step t-k, k <= N (bounded staleness). "
                         "0 (default) = every update lands before the "
                         "next step is admitted: the legacy loss "
                         "trajectory, bit-for-bit")
    pt.add_argument("--chaos", default=None, metavar="SPEC",
                    help="deterministic fault injection on the client "
                         "wire: comma list of kind[=rate][:ms], kinds "
                         "drop_req | drop_resp | dup | delay | corrupt | "
                         "http500 (e.g. 'drop_resp=0.1,dup=0.05'); seeded "
                         "by --chaos-seed, off by default (untouched "
                         "wire) — see README 'Fault tolerance'")
    pt.add_argument("--chaos-seed", dest="chaos_seed", type=int, default=0,
                    help="seed for the --chaos schedule (same spec + "
                         "seed = the same faults at the same steps)")
    pt.add_argument("--replicas", dest="replicas", type=int, default=1,
                    help="local transport only: run N same-init server "
                         "replicas behind the sticky failover router "
                         "(runtime/replica.py); 1 = no router, the plain "
                         "in-process server, bit-identical")
    pt.add_argument("--replica-sync-every", dest="replica_sync_every",
                    type=int, default=0,
                    help="FedAvg the replicas' server tops every K group "
                         "steps (0 = never; with one client only its own "
                         "replica trains, so sync propagates the updates)")
    pt.add_argument("--gate-dropped-steps", dest="gate_dropped_steps",
                    action="store_true",
                    help="chain runs (--stages > 2): exit 1 unless every "
                         "scheduled step completed with a finite loss "
                         "and every stage party's health step reached "
                         "the last step — fleet_sim's exactly-once gate "
                         "on the MPMD chain (composed-topology CI smoke)")
    pt.add_argument("--handoff", dest="handoff",
                    choices=["live", "checkpoint"], default="live",
                    help="how a dead replica's step state reaches its "
                         "successors: live (in-memory extras payload) or "
                         "checkpoint (round-trip through the durable "
                         "sidecar on disk)")
    _add_autoscale_args(pt)
    pt.add_argument("--failure-policy", dest="failure_policy",
                    choices=["raise", "retry", "skip"], default=None,
                    help="what a split client does when the wire fails: "
                         "raise (default), retry (bounded, with a "
                         "circuit breaker probing /health while the "
                         "server is down), or skip (reference behavior: "
                         "drop the batch, counted)")
    pt.add_argument("--max-retries", dest="max_retries", type=int,
                    default=3,
                    help="retry budget per step with "
                         "--failure-policy retry (default 3)")
    pt.add_argument("--resume", action="store_true",
                    help="restore the latest checkpoint before training")
    pt.add_argument("--checkpoint-every", type=int, default=0,
                    help="also checkpoint every N steps "
                         "(fused/pipeline transports)")
    pt.add_argument("--eval", action="store_true",
                    help="report test-split accuracy after training")
    pt.set_defaults(fn=cmd_train)

    ps = sub.add_parser("serve", help="serve the server party over HTTP")
    _add_common(ps)
    ps.add_argument("--host", default="0.0.0.0")
    ps.add_argument("--port", type=int, default=8000)
    ps.add_argument("--role", choices=["server", "stage"], default="server",
                    help="party kind: 'server' owns the tail of a 1-cut "
                         "split; 'stage' owns one interior/tail stage of a "
                         "K-stage MPMD chain (PR 14) and speaks the hop "
                         "protocol (/hop_forward, /hop_backward, /hop_loss)")
    ps.add_argument("--stage-index", dest="stage_index", type=int, default=1,
                    help="--role stage: which SplitPlan stage this party "
                         "owns (1..K-1; stage 0 is always the data-owning "
                         "client)")
    ps.add_argument("--microbatches", type=int, default=None,
                    help="--role stage: GPipe microbatches per step the "
                         "chain driver will send; must agree across all "
                         "stage parties and the trainer (health-checked)")
    ps.add_argument("--resume", action="store_true",
                    help="restore the latest server checkpoint on startup")
    ps.add_argument("--checkpoint-every", type=int, default=100,
                    help="checkpoint the server half every N acknowledged "
                         "steps (with --checkpoint-dir)")
    ps.add_argument("--allow-out-of-order", dest="allow_out_of_order",
                    action="store_true",
                    help="accept out-of-order client steps (required by "
                         "pipelined clients, --pipeline-depth > 1; disables "
                         "the replay-refusing strict step handshake)")
    ps.add_argument("--coalesce-max", dest="coalesce_max", type=int,
                    default=1,
                    help="split mode: batch up to N concurrent split-step "
                         "requests into one server dispatch (group-mean "
                         "SGD update — see README 'Request coalescing' "
                         "for the semantics trade-off); 1 = serialized")
    ps.add_argument("--coalesce-window-ms", dest="coalesce_window_ms",
                    type=float, default=2.0,
                    help="how long a coalescing group waits for peers "
                         "after its first request before flushing partial "
                         "(only with --coalesce-max > 1). A group is then "
                         "cut back to the requests that fill a smaller row "
                         "bucket exactly where the step times the server "
                         "has measured say that answers its clients "
                         "sooner (/health counts flush_shed)")
    ps.add_argument("--batching", choices=["window", "continuous"],
                    default="window",
                    help="coalescer flush policy (with --coalesce-max > "
                         "1): 'window' waits out --coalesce-window-ms "
                         "for peers; 'continuous' dispatches whatever is "
                         "admitted the moment the previous group is in "
                         "flight, earliest-SLO-deadline first (see "
                         "README 'Continuous batching & admission "
                         "control')")
    ps.add_argument("--tenants", type=int, default=1,
                    help="admission control: number of tenants; clients "
                         "map to tenants by client_id %% tenants")
    ps.add_argument("--quota", type=float, default=None,
                    help="admission control: per-tenant quota in "
                         "steps/sec (token bucket; burst = one second "
                         "of quota). Over-quota requests get HTTP 429 "
                         "+ Retry-After instead of queueing; unset = "
                         "unlimited")
    ps.add_argument("--slo-ms", dest="slo_ms", type=float, default=None,
                    help="admission control: per-tenant latency SLO; "
                         "admitted requests are stamped now+slo-ms and "
                         "the continuous batcher picks groups earliest-"
                         "deadline-first")
    ps.add_argument("--decouple-bwd", dest="decouple_bwd",
                    action="store_true",
                    help="split mode: 2BP reply-first step — reply with "
                         "the cut-layer gradient from a forward+grad-of-"
                         "acts dispatch immediately, defer the weight "
                         "update off the reply critical path (README "
                         "'Decoupled backward (2BP)'); checkpoints, "
                         "predict and shutdown flush the queue first")
    ps.add_argument("--apply-lag", dest="apply_lag", type=int, default=0,
                    help="with --decouple-bwd: bounded staleness — up "
                         "to N deferred weight updates may queue, so a "
                         "step's forward can use weights at most N "
                         "updates old; 0 (default) applies each update "
                         "before the next step is admitted (the legacy "
                         "trajectory, bit-for-bit)")
    ps.add_argument("--mesh-data", dest="mesh_data", type=int, default=1,
                    help="sharded server (pjit): 'data' axis size — "
                         "batch dims shard across it and coalesced "
                         "groups round to a multiple of it (zero-weight "
                         "padding). 1 = legacy single-device server, "
                         "bit-for-bit (README 'Sharded server (pjit)')")
    ps.add_argument("--mesh-model", dest="mesh_model", type=int, default=1,
                    help="sharded server (pjit): 'model' axis size — "
                         "heavy weight matrices (and their optimizer "
                         "mirrors) shard across it via the SpecLayout "
                         "column-then-row rule")
    ps.add_argument("--compress",
                    choices=["none", "int8", "topk8", "clapping"],
                    default=None,
                    help="default wire compression for replies to clients "
                         "that do not pick one themselves (a request's own "
                         "compress key always wins); clapping also "
                         "switches this party's reply-side error "
                         "feedback to the storage-free ledger (no EF "
                         "state in checkpoints or failover handoffs)")
    ps.add_argument("--compress-density", dest="compress_density",
                    type=float, default=0.1,
                    help="topk8 only: default reply density (default 0.1)")
    ps.add_argument("--chaos", default=None, metavar="SPEC",
                    help="deterministic server-side fault injection on "
                         "step requests: same grammar as train --chaos; "
                         "http500/drop_req fire before the update is "
                         "applied, drop_resp/corrupt after (the "
                         "lost-response case the replay cache recovers)")
    ps.add_argument("--chaos-seed", dest="chaos_seed", type=int, default=0,
                    help="seed for the --chaos schedule")
    ps.add_argument("--replicas", dest="replicas", type=int, default=1,
                    help="serve N same-init server replicas behind the "
                         "sticky failover router on one HTTP port "
                         "(runtime/replica.py); 1 = the plain runtime, "
                         "no router on the step path. Does not compose "
                         "with --checkpoint-dir yet")
    ps.add_argument("--replica-sync-every", dest="replica_sync_every",
                    type=int, default=0,
                    help="FedAvg the replicas' server tops every K group "
                         "steps (0 = never)")
    ps.add_argument("--handoff", dest="handoff",
                    choices=["live", "checkpoint"], default="live",
                    help="failover handoff path: live (in-memory extras "
                         "payload) or checkpoint (durable sidecar "
                         "round-trip)")
    _add_autoscale_args(ps)
    ps.add_argument("--trace", default=None, metavar="PATH",
                    help="per-step span tracing (obs/): serve live "
                         "queue-wait/dispatch histograms on GET /metrics "
                         "and write a Chrome trace here on shutdown. "
                         "Off = zero overhead (/metrics stays up but "
                         "histograms stay empty)")
    ps.add_argument("--flight", default=None, metavar="PATH",
                    help="flight recorder (obs/flight.py): journal causal "
                         "server events; dump JSON here on shutdown / "
                         "SIGTERM / watchdog trip, or fetch the live ring "
                         "via GET /debug/flight. Off = zero overhead")
    ps.add_argument("--telemetry", action="store_true",
                    help="telemetry plane (obs/telemetry.py): windowed "
                         "rates/percentiles ring served on GET /telemetry "
                         "(implies tracing; SLT_TELEMETRY=1 is the env "
                         "twin). Off = the legacy routes, zero overhead")
    ps.add_argument("--telemetry-interval-s", dest="telemetry_interval_s",
                    type=float, default=None,
                    help="telemetry window width in seconds (default "
                         "1.0; env twin SLT_TELEMETRY_INTERVAL_S)")
    ps.add_argument("--telemetry-slo-ms", dest="telemetry_slo_ms",
                    type=float, default=None,
                    help="per-tenant latency SLO for the burn-rate "
                         "tracker (enables slt_slo_burn_rate_* gauges "
                         "and fl_slo_alert flight events; env twin "
                         "SLT_TELEMETRY_SLO_MS)")
    ps.set_defaults(fn=cmd_serve)

    pe = sub.add_parser("eval", help="evaluate a checkpoint on the test split")
    _add_common(pe)
    pe.add_argument("--step", type=int, default=None,
                    help="checkpoint step (default: latest)")
    pe.add_argument("--server-url", dest="server_url", default=None,
                    help="split-party inference: run only the client-"
                         "owned stages locally and the server-owned "
                         "compute behind this serving server's /predict")
    pe.set_defaults(fn=cmd_eval)

    pg = sub.add_parser("generate",
                        help="decode from a causal-LM checkpoint "
                             "(KV-cache local, or split-party remote)")
    _add_common(pg)
    pg.add_argument("--step", type=int, default=None,
                    help="checkpoint step (default: latest)")
    pg.add_argument("--prompt", default=None,
                    help="comma-separated token ids (default: first "
                         "test-split example)")
    pg.add_argument("--prompt-len", dest="prompt_len", type=int, default=16,
                    help="tokens taken from the test split when no "
                         "--prompt is given")
    pg.add_argument("--n-new", dest="n_new", type=int, default=32,
                    help="tokens to generate")
    pg.add_argument("--temperature", type=float, default=None,
                    help="sample at this temperature (omit = greedy)")
    pg.add_argument("--top-k", dest="top_k", type=int, default=0)
    pg.add_argument("--top-p", dest="top_p", type=float, default=None)
    pg.add_argument("--no-kv-cache", dest="no_kv_cache",
                    action="store_true",
                    help="use the O(T^2) re-forward reference decode")
    pg.add_argument("--server-url", dest="server_url", default=None,
                    help="split-party decode: client stages local, "
                         "server compute behind this server's /predict")
    pg.set_defaults(fn=cmd_generate)

    args = ap.parse_args(argv)
    return _run_with_flight(args)


if __name__ == "__main__":
    sys.exit(main())
