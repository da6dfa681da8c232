"""Counters that leave the jitted step (core/stage.with_counters,
core/losses.plan_loss_with_counters, runtime/fused.py): what the routed
layer sows equals its own functions called on its captured input, the
host reads it only while recording, and a program that does not ask for
it is the program without it. CPU, small sizes."""

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import Var
from jax.interpreters import partial_eval as pe

from split_learning_tpu import obs
from split_learning_tpu.data.datasets import Split
from split_learning_tpu.core.losses import (
    cross_entropy, plan_loss, plan_loss_with_counters)
from split_learning_tpu.core.stage import with_counters
from split_learning_tpu.models import afmoe, get_plan
from split_learning_tpu.obs import spans
from split_learning_tpu.parallel import make_mesh
from split_learning_tpu.runtime import (
    ServerRuntime, SplitClientTrainer, evaluate)
from split_learning_tpu.runtime.fused import FusedSplitTrainer
from split_learning_tpu.runtime.state import apply_grads, make_tx
from split_learning_tpu.transport import LocalTransport
from split_learning_tpu.utils import Config

B, T, VOCAB = 2, 16, 300
# the benchmark rehearsals' sizes: four routed layers behind a dense one
# (and in joyai the prediction module's block: five), 2 of 8 experts held,
# so the ladder has two rungs: 32 rows, twice the even share, and all 64
# (twice 32 is the worst case itself: no middle rung at a quarter held)
ROUTED = dict(vocab=VOCAB, d_model=64, dense_width=192, expert_width=32,
              experts_total=8, experts_held=2, expert_offset=2,
              experts_per_token=2, dense_layers=1, client_depth=1)
FAMILIES = {
    "afmoe": dict(ROUTED, num_heads=4, num_kv_heads=2, head_dim=16,
                  route_scale=2.826, window=8),
    "joyai_llm_flash": dict(
        ROUTED, num_heads=4, q_lora_rank=48, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        route_scale=2.5, layers=5, mtp_layers=1),
}
LAYERS = {
    "afmoe": [f"trunk_head/layer{i}/experts" for i in (1, 2, 3, 4)],
    "joyai_llm_flash": [f"trunk_head/layer{i}/experts" for i in (1, 2, 3, 4)]
    + ["trunk_head/mtp/block/experts"],
}
# one routed layer behind the dense one: the runtime's cases compile it
SMALL = dict(FAMILIES["afmoe"],
             layer_types=["sliding_attention", "full_attention"])
SMALL_LAYERS = ["trunk_head/layer1/experts"]
PLAIN = {
    "transformer_lm": (dict(d_model=64, num_heads=4, max_len=T, vocab=97),
                       np.zeros((B, T), np.int32), np.zeros((B, T), np.int32)),
    "vit": (dict(d_model=64, num_heads=4, patch=16, num_classes=10,
                 max_tokens=4),
            np.zeros((B, 32, 32, 3), np.float32), np.zeros((B,), np.int32)),
}


def batch(seed=0):
    ids = np.random.RandomState(seed).randint(
        0, VOCAB, (B, T + 1)).astype(np.int32)
    return ids[:, :-1], ids[:, 1:]


def seeded(plan, x, seed=1):
    """``plan.init``'s weights with the selection bias and the norms'
    scales moved off their constants, so that the bias moves the top-k."""
    params = plan.init(jax.random.PRNGKey(seed), x)
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 100), len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        leaf + 0.02 * jax.random.normal(k, leaf.shape, leaf.dtype)
        for leaf, k in zip(leaves, keys)])


def config(model, **over):
    return Config(mode="split", model=model, optimizer="adamw", lr=1e-3,
                  batch_size=B, **over)


def routed_directly(plan, params, x, y, kw):
    """{layer: (sizes, rows, rungs)} from ``route``, ``held_pairs``,
    ``pair_rungs`` and ``rung_of`` called on each routed layer's captured
    input (the float32 norm before it), through the final stage's
    objective where it has one."""
    norms = ("norm_pre_mlp", "norm_mlp")     # by family
    watch = dict(capture_intermediates=lambda m, _: m.name in norms,
                 mutable=["intermediates"])
    seen = {}

    def routed(weights, captured, path):
        for name, sub in captured.items():
            if "experts" in weights.get(name, {}):
                m32 = next(sub[n] for n in norms if n in sub)["__call__"][0]
                p = weights[name]["experts"]
                chosen, _ = afmoe.route(
                    m32.reshape(-1, m32.shape[-1]), p["router"],
                    p["expert_bias"], kw["experts_per_token"],
                    kw["route_scale"])
                sizes = afmoe.held_pairs(chosen, kw["expert_offset"],
                                         kw["experts_held"])[2]
                rungs = afmoe.pair_rungs(chosen.size, kw["experts_held"],
                                         kw["experts_total"])
                rows = rungs[int(afmoe.rung_of(sizes.sum(), rungs))]
                seen[f"{path}/{name}/experts"] = (sizes, rows, rungs)
            elif isinstance(sub, dict):
                routed(weights.get(name, {}), sub, f"{path}/{name}")

    h = jnp.asarray(x)
    for stage, p in zip(plan.stages, params):
        if stage.objective is not None:
            _, state = stage.objective(p, h, jnp.asarray(y), **watch)
        else:
            h, state = stage.apply(p, h, **watch)
        routed(p["params"], state["intermediates"], stage.name)
    return seen


@pytest.mark.parametrize("model", sorted(FAMILIES))
def test_sown_counters_equal_the_layers_own_functions(model):
    kw = FAMILIES[model]
    plan = get_plan(model, "split", jnp.float32, **kw)
    x, y = batch()
    params = seeded(plan, x)
    loss, counters = jax.jit(
        lambda p: plan_loss_with_counters(plan, p, x, y))(params)
    assert float(loss) == pytest.approx(float(plan_loss(plan, params, x, y)),
                                        rel=1e-6)
    want = routed_directly(plan, params, x, y, kw)
    assert sorted(counters) == sorted(want) == LAYERS[model]
    for layer, (sizes, rows, rungs) in want.items():
        got = counters[layer]
        assert sorted(got) == [spans.MOE_LADDER, spans.MOE_PAIRS,
                               spans.MOE_ROWS]
        np.testing.assert_array_equal(got[spans.MOE_PAIRS], sizes)
        assert got[spans.MOE_PAIRS].dtype == jnp.int32
        assert int(got[spans.MOE_ROWS]) == rows
        assert tuple(got[spans.MOE_LADDER].tolist()) == rungs == (32, 64)
    # the seeded bias is no even routing: some layer holds another count
    assert len({int(c[spans.MOE_PAIRS].sum()) for c in counters.values()}) > 1


@pytest.mark.parametrize("remat,favoured,held,rows,ladder", [
    (True, (0.0, 0.0), None, 32, (32, 64, 128)),   # about an eighth: the low rung
    (True, (9.0, -9.0), 64, 64, (32, 64, 128)),    # one pair a token: the middle
    (True, (9.0, 9.0), 128, 128, (32, 64, 128)),   # every pair held: the top
    (False, (0.0, 0.0), None, 128, (128,)),        # kept rows: the top rung alone
    (False, (9.0, -9.0), 64, 128, (128,)),
], ids=["low-rung", "middle-rung", "overflow", "no-remat", "no-remat-middle"])
def test_the_rung_reported_is_the_rung_run(remat, favoured, held, rows, ladder):
    """A bias that sends every token to one of the two held experts, or to
    both, overflows the lower rung, or the middle one, and the layer says
    so: ``rows`` is the smallest rung that holds the pairs."""
    layer = afmoe.RoutedExperts(width=8, experts_total=16, experts_held=2,
                                expert_offset=3, per_token=2,
                                route_scale=1.0, remat=remat)
    m = jax.random.normal(jax.random.PRNGKey(0), (64, 16))
    params = layer.init(jax.random.PRNGKey(1), m)
    assert sorted(params) == ["params"]          # init sows nothing
    bias = jnp.zeros(16).at[3:5].set(jnp.asarray(favoured))
    params = {"params": {**params["params"], "expert_bias": bias}}
    plain = layer.apply(params, m)
    out, sown = layer.apply(params, m, mutable=[spans.STEP_COUNTERS])
    np.testing.assert_array_equal(out, plain)
    got = sown[spans.STEP_COUNTERS]
    assert tuple(got[spans.MOE_LADDER].tolist()) == ladder
    assert int(got[spans.MOE_ROWS]) == rows
    pairs = int(got[spans.MOE_PAIRS].sum())
    assert max([r for r in ladder if r < rows], default=-1) < pairs <= rows
    assert held in (None, pairs)


def _device_gets(monkeypatch):
    calls = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda tree: (calls.append(tree), real(tree))[1])
    return calls


def test_the_host_reads_the_counters_only_while_recording(monkeypatch):
    plan = get_plan("afmoe", "split", jnp.float32, **SMALL)
    x, y = batch()
    trainer = FusedSplitTrainer(plan, config("afmoe"),
                                jax.random.PRNGKey(0), x)
    gets = _device_gets(monkeypatch)
    made = []
    real_span = obs.span
    monkeypatch.setattr("split_learning_tpu.runtime.fused.obs_trace.span",
                        lambda name, **a: (made.append(name),
                                           real_span(name, **a))[1])
    # off: the step's extra outputs are never fetched, no span is opened
    assert not obs.recording()
    assert np.isfinite(trainer.train_step(x, y))
    assert gets == [] and spans.COUNTERS_READ not in made
    tr = obs.enable()
    try:
        before = trainer.params
        want = jax.device_get(plan_loss_with_counters(
            plan, before, x, y)[1])
        del gets[:]
        for _ in range(2):
            trainer.train_step(x, y)
        trainer.train_step_async(x, y).block_until_ready()
    finally:
        obs.disable()
    assert len(gets) == 2                      # one a blocking step
    recs = tr.spans()
    reads = [r for r in recs if r["name"] == spans.COUNTERS_READ]
    roots = [r for r in recs if r["name"] == spans.STEP_TOTAL]
    assert [r["parent_id"] for r in reads] == [r["span_id"]
                                              for r in roots[:2]]
    kids = [k["name"] for k in sorted(
        (r for r in recs if r["parent_id"] == roots[0]["span_id"]),
        key=lambda r: r["start_ns"])]
    assert kids == [spans.H2D, spans.DISPATCH, spans.LOSS_WAIT,
                    spans.COUNTERS_READ]
    first = reads[0]["attrs"]
    assert first["layers"] == SMALL_LAYERS
    assert sorted(first) == ["ladder", "layers", "pairs", "rows"]
    for i, layer in enumerate(first["layers"]):
        assert first["pairs"][i] == want[layer][spans.MOE_PAIRS].tolist()
        assert first["rows"][i] == int(want[layer][spans.MOE_ROWS])
        assert first["ladder"][i] == [32, 64]
    # the operator's view: the record rides the Chrome export's args
    events = json.loads(json.dumps(tr.chrome_events()))
    args = [e["args"] for e in events if e["name"] == spans.COUNTERS_READ]
    assert len(args) == 2 and args[0]["pairs"] == first["pairs"]


@pytest.mark.parametrize("favoured,pairs,rows", [(-9.0, 0, 16), (9.0, 32, 32)],
                         ids=["low-rung", "middle-rung"])
def test_counters_read_names_the_middle_rung(favoured, pairs, rows):
    """One of eight experts held: 64 pairs, a ladder of 16, 32 and all 64.
    A bias that sends every token's first pair to the held expert fills 32
    rows, and the fused step's ``counters_read`` says the middle rung ran;
    one that keeps every token off it, the lowest."""
    kw = dict(SMALL, experts_held=1)
    plan = get_plan("afmoe", "split", jnp.float32, **kw)
    x, y = batch()
    trainer = FusedSplitTrainer(plan, config("afmoe"),
                                jax.random.PRNGKey(0), x)
    held = kw["expert_offset"]
    trainer.state = trainer.state._replace(params=jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf.at[held].set(favoured)
        if "expert_bias" in jax.tree_util.keystr(path) else leaf,
        trainer.state.params))
    tr = obs.enable()
    try:
        assert np.isfinite(trainer.train_step(x, y))
    finally:
        obs.disable()
    read, = [r["attrs"] for r in tr.spans() if r["name"] == spans.COUNTERS_READ]
    assert read["layers"] == SMALL_LAYERS
    assert read["ladder"] == [[16, 32, 64]] and read["rows"] == [rows]
    assert read["pairs"] == [[pairs]]


@pytest.mark.parametrize("ladder,ran,by_rung,first,key", [
    ([64], [64, 64, 64], [3], [], "2"),
    ([32, 64], [32, 64, 32], [2, 1], [1], "1/1"),
    ([16, 32, 64], [16, 32, 64], [1, 1, 1], [1, 2], "1/1/0"),
    ([16, 32, 64], [16, 64, 64], [1, 0, 2], [1, 1], "1/0/1"),
], ids=["one-rung", "two", "three", "skips-the-middle"])
def test_routed_window_tabulates_a_ladder_of_any_length(ladder, ran, by_rung, first, key):
    """``scripts/routed_window.py:reduce`` over three recorded steps of two
    layers, the second always on its lowest rung: samples by rung, the step
    each higher rung was first reached, and the step time keyed by how many
    layers sat on each rung."""
    spec = importlib.util.spec_from_file_location("routed_window", os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts", "routed_window.py"))
    routed_window = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(routed_window)
    records = []
    for i, rows in enumerate(ran):
        records += [
            {"name": spans.STEP_TOTAL, "span_id": i, "duration": 0.1},
            {"name": spans.COUNTERS_READ, "parent_id": i, "duration": 0.001,
             "attrs": {"layers": ["a", "b"], "pairs": [[rows - 1, 1], [2, 2]],
                       "rows": [rows, ladder[0]], "ladder": [ladder, ladder]}}]
    summary, table = routed_window.reduce(records, 0, 4.0)
    assert summary["layers"]["a"]["steps_by_rung"] == by_rung
    assert summary["layers"]["a"]["first_step_on_rung"] == first
    assert summary["layers"]["b"]["steps_by_rung"] == [3] + [0] * (len(ladder) - 1)
    assert summary["samples_by_rung"] == [n + m for n, m in zip(
        by_rung, summary["layers"]["b"]["steps_by_rung"])]
    assert key in summary["step_ms_by_layers_on_each_rung"]
    assert [r["rung"][0] for r in table] == [ladder.index(r) for r in ran]


@pytest.mark.parametrize("over,reads", [
    (dict(remat=True), 1), (dict(microbatches=2), 0)],
    ids=["config-remat", "microbatches"])
def test_counters_under_a_checkpointed_plan_and_under_microbatches(over, reads):
    """``Config.remat`` checkpoints every stage and the counters still
    come out; a microbatch's counters stay inside the scan."""
    plan = get_plan("afmoe", "split", jnp.float32, **SMALL)
    x, y = batch()
    trainer = FusedSplitTrainer(plan, config("afmoe", **over),
                                jax.random.PRNGKey(0), x)
    tr = obs.enable()
    try:
        loss = trainer.train_step(x, y)
        losses = trainer.train_epoch(np.stack([x, x]), np.stack([y, y]))
    finally:
        obs.disable()
    assert np.isfinite(loss) and np.isfinite(np.asarray(losses)).all()
    got = [r for r in tr.spans() if r["name"] == spans.COUNTERS_READ]
    assert len(got) == reads
    if reads:
        assert got[0]["attrs"]["layers"] == SMALL_LAYERS


def test_counters_on_a_data_mesh_are_the_global_counts(devices):
    """The batch sharded over two clients: ``pairs`` is the sum over the
    shards, the rung the one the whole step ran."""
    plan = get_plan("afmoe", "split", jnp.float32, **SMALL)
    x, y = batch()
    read = []
    for mesh in (None, make_mesh(num_clients=2, num_stages=1,
                                 devices=devices[:2])):
        trainer = FusedSplitTrainer(plan, config("afmoe"),
                                    jax.random.PRNGKey(0), x, mesh=mesh)
        tr = obs.enable()
        try:
            trainer.train_step(x, y)
        finally:
            obs.disable()
        read.append([r["attrs"] for r in tr.spans()
                     if r["name"] == spans.COUNTERS_READ])
    assert read[0] == read[1] and len(read[0]) == 1
    assert sum(read[0][0]["pairs"][0]) <= read[0][0]["rows"][0]


def _old_step(plan, tx):
    """The fused step as it stood before the counters."""
    def step_fn(state, x, y):
        loss, grads = jax.value_and_grad(
            lambda p, x, y: plan_loss(plan, p, x, y, cross_entropy))(
                state.params, x, y)
        return apply_grads(tx, state, grads), loss
    return step_fn


def _texts(model, kw, x, y):
    """(the old step's jaxpr, the trainer's own step's jaxpr)."""
    plan = get_plan(model, "split", jnp.float32, **kw)
    cfg = config(model)
    trainer = FusedSplitTrainer(plan, cfg, jax.random.PRNGKey(0), x)
    state = jax.eval_shape(lambda s: s, trainer.state)
    old = jax.make_jaxpr(_old_step(plan, make_tx(cfg)))(state, x, y)
    new = jax.make_jaxpr(trainer._step.__wrapped__)(state, x, y)
    return trainer, old, new


@pytest.mark.parametrize("model", sorted(PLAIN))
def test_a_plan_that_sows_nothing_has_the_step_it_had(model):
    kw, x, y = PLAIN[model]
    trainer, old, new = _texts(model, kw, x, y)
    assert str(new) == str(old)
    _, loss, counters = trainer._step(trainer.state, x, y)
    assert counters == {} and np.isfinite(float(loss))


@pytest.mark.parametrize("model", sorted(FAMILIES))
def test_a_routed_plans_step_differs_by_its_extra_outputs_alone(model):
    """Take the counters' outputs off the new step and what is left is
    the old step, to the letter: the counters change no arithmetic."""
    x, y = batch()
    _, old, new = _texts(model, FAMILIES[model], x, y)
    extra = len(new.jaxpr.outvars) - len(old.jaxpr.outvars)
    assert extra == 3 * len(LAYERS[model])
    keep = [True] * len(old.jaxpr.outvars)

    def live(jaxpr, outputs):
        """``jaxpr`` without what only the outputs left out need."""
        cut, _ = pe.dce_jaxpr(jaxpr, outputs)
        used = {v for e in cut.eqns for v in e.invars
                if isinstance(v, Var)} | set(cut.outvars)
        return str(cut.replace(
            constvars=[v for v in cut.constvars if v in used]))

    assert live(new.jaxpr, keep + [False] * extra) == live(old.jaxpr, keep)
    assert str(new) != str(old)


def test_no_other_path_makes_the_collection_mutable(monkeypatch):
    """The two-party step, ``plan.apply`` and evaluation never reach the
    sowing code, so their programs are the ones they were."""
    reached = []
    real = afmoe.RoutedExperts._count
    monkeypatch.setattr(afmoe.RoutedExperts, "_count",
                        lambda self, *a: (reached.append(1),
                                          real(self, *a))[1])
    plan = get_plan("afmoe", "split", jnp.float32, **SMALL)
    x, y = batch()
    cfg = config("afmoe")
    server = ServerRuntime(plan, cfg, jax.random.PRNGKey(0), x)
    client = SplitClientTrainer(plan, cfg, jax.random.PRNGKey(0),
                                LocalTransport(server))
    assert np.isfinite(client.train_step(x, y, 0))
    params = plan.init(jax.random.PRNGKey(0), x)
    plan.apply(params, x)
    evaluate(plan, params, Split(x, y))
    assert not reached
    # a hand-written stage has nothing to sow and is never asked
    bare = dataclasses.replace(plan.stages[0], sows=False)
    _, counters = with_counters(bare, lambda p, h: h, params[0], x)
    assert counters == {}
    plan_loss_with_counters(plan, params, x, y)
    assert len(reached) == 1
