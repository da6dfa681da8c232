"""What a cell's fused step keeps on the device, by XLA's own reckoning.

    JAX_PLATFORMS=cpu python scripts/fused_step_memory.py --workload <cell>

Compiles the cell's fused step (``runtime/fused.py``'s ``step_fn``: loss,
gradient, optimizer update, the step's counters, the state donated) at the
cell's real sizes
for a *described* TPU v5e, with no chip attached, and prints the compiled
program's memory analysis as one JSON line: arguments, outputs, aliased,
temporaries and their peak (arguments + outputs - aliased + temporaries),
in bytes and GB.  Nothing runs, so it says what fits, never how fast.  It
counts this one program, not what else a process keeps on the device
(PERF.md section 4: two loaded executables once cost the reference its
room).  It is the check of the fit rules of ``trinity-mini-fused-t8192``
(13.0 GB or under), ``phi4flash-fused-t8192``,
``joyai-flash-fused-t8192``, ``lfm2-moe-fused-t8192``,
``nemotronh-moe-fused-t8192`` and ``ouro-loop-fused-t8192`` (14.5).
``--remat 0`` compiles the step without the family's ``remat``, to see what
the values it recomputes cost when kept; ``--remat-mlp-passes N`` sets in how
many of a looped model's passes the MLPs' ``gate`` and ``up`` are made again
(``models/ouro.py``: the fit rule of ``ouro-loop-fused-t8192`` walks it).
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True,
                        help="a cell of BENCHMARK.json on the fused path")
    parser.add_argument("--remat", type=int, choices=(0, 1), default=None,
                        help="override the configuration's plan.kwargs.remat")
    parser.add_argument("--remat-mlp-passes", type=int, default=None,
                        help="override plan.kwargs.remat_mlp_passes")
    args = parser.parse_args()

    import run
    _, cell, config = run.load_cell(args.workload)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import traffic
    import weights
    from split_learning_tpu.core.losses import plan_loss_with_counters
    from split_learning_tpu.models.factory import get_plan
    from split_learning_tpu.ops import common
    from split_learning_tpu.runtime.state import (
        apply_grads, make_state, make_tx)

    # the kernels as the chip compiles them, not their interpreter
    common._default_backend_platform = lambda: "tpu"
    # T 8192 takes the one-pass flash backward on the chip, after a
    # preflight compile that cannot run here: name the form instead (the
    # module by its path: the package's attribute of that name is the op)
    importlib.import_module(
        "split_learning_tpu.ops.flash_attention").ONEPASS = True
    jax.config.update("jax_enable_compilation_cache", False)

    job = traffic.load(cell["traffic"])
    spec = config["plan"]
    kw = dict(spec["kwargs"])
    if args.remat is not None:
        kw["remat"] = bool(args.remat)
    if args.remat_mlp_passes is not None:
        kw["remat_mlp_passes"] = args.remat_mlp_passes
    plan = get_plan(spec["model"], spec["mode"], jnp.dtype(spec["dtype"]), **kw)
    tx = make_tx(run.program_config(config, job))

    rows = job["clients"] * job["rows_per_client"]
    tokens = jax.ShapeDtypeStruct((rows, job["tokens_per_row"]), jnp.int32)
    params = tuple(weights.stage_shapes(plan, tokens))
    state = jax.eval_shape(lambda p: make_state(p, tx), params)

    def step(state, x, y):
        (loss, counters), grads = jax.value_and_grad(
            lambda p: plan_loss_with_counters(plan, p, x, y),
            has_aux=True)(state.params)
        return apply_grads(tx, state, grads), loss, counters

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip), tree)
    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        on_chip(state), on_chip(tokens), on_chip(tokens)).compile()
    m = compiled.memory_analysis()
    sizes = {"arguments": m.argument_size_in_bytes,
             "outputs": m.output_size_in_bytes,
             "aliased": m.alias_size_in_bytes,
             "temporaries": m.temp_size_in_bytes,
             "code": m.generated_code_size_in_bytes}
    sizes["peak"] = (sizes["arguments"] + sizes["outputs"] - sizes["aliased"]
                     + sizes["temporaries"])
    text = compiled.as_text()
    print(json.dumps({
        "workload": args.workload, "remat": kw.get("remat"),
        **{k: kw[k] for k in ("remat_mlp_passes",) if k in kw},
        "device": topo.devices[0].device_kind,
        "bytes": sizes,
        "gb": {k: round(v / 1e9, 3) for k, v in sizes.items()},
        "tpu_custom_calls": text.count("tpu_custom_call"),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
