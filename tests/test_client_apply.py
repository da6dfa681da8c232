"""The split clients' optimizer step is one jitted program
(runtime/state.py:jit_apply_grads).

Three contracts: the jitted apply gives the state the eager
``apply_grads`` gives from the same gradients, traced once a trainer and
not run once a step; it donates the optimizer state and the gradients
but never the parameters, which the bottom sync, the pipelined window
and callers hold across steps; and a client builds no program after its
third step.
"""

import jax
import numpy as np
import pytest

from split_learning_tpu.models import get_plan
from split_learning_tpu.runtime import (
    PipelinedSplitClientTrainer, ServerRuntime, SplitClientTrainer,
    USplitClientTrainer)
from split_learning_tpu.runtime import state as state_mod
from split_learning_tpu.runtime.multi_client import MultiClientSplitRunner
from split_learning_tpu.transport import LocalTransport
from split_learning_tpu.utils import Config

SEED = 3
BATCH = 8
STEPS = 4
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _batches(n, seed=11):
    rs = np.random.RandomState(seed)
    return [(rs.randn(BATCH, 28, 28, 1).astype(np.float32),
             rs.randint(0, 10, (BATCH,)).astype(np.int64)) for _ in range(n)]


def _copy(tree):
    return jax.tree_util.tree_map(np.array, tree)


def _readable(tree) -> bool:
    return not any(leaf.is_deleted()
                   for leaf in jax.tree_util.tree_leaves(tree)
                   if isinstance(leaf, jax.Array))


def _assert_trees_close(got, want, **tol):
    got, want = (jax.tree_util.tree_leaves(t) for t in (got, want))
    assert len(got) == len(want)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **tol)


class _Recorded:
    """Stands in for a trainer's ``_apply_grads``: keeps, per state
    structure, the first state and every gradient tree as host copies
    (the call donates the gradients), then calls the real one."""

    def __init__(self, inner):
        self.inner = inner
        self.first, self.grads = {}, {}

    def __call__(self, state, grads):
        key = jax.tree_util.tree_structure(grads)
        self.first.setdefault(key, _copy(state))
        self.grads.setdefault(key, []).append(_copy(grads))
        return self.inner(state, grads)


def _run_stepwise(cfg, batches, mode, trainer, states_of):
    cfg = cfg.replace(mode=mode)
    plan = get_plan(mode=mode)
    server = ServerRuntime(plan, cfg, jax.random.PRNGKey(SEED), batches[0][0])
    client = trainer(plan, cfg, jax.random.PRNGKey(SEED),
                     LocalTransport(server))
    rec = client._apply_grads = _Recorded(client._apply_grads)
    for i, (x, y) in enumerate(batches):
        client.train_step(x, y, i)
    server.close()
    return rec, states_of(client)


def _run_split(cfg, batches):
    return _run_stepwise(cfg, batches, "split", SplitClientTrainer,
                         lambda c: [c.state])


def _run_u_split(cfg, batches):
    return _run_stepwise(cfg, batches, "u_split", USplitClientTrainer,
                         lambda c: [c.state_a, c.state_c])


def _run_pipelined(cfg, batches):
    plan = get_plan(mode="split")
    server = ServerRuntime(plan, cfg, jax.random.PRNGKey(SEED), batches[0][0],
                           strict_steps=False)
    client = PipelinedSplitClientTrainer(
        plan, cfg, jax.random.PRNGKey(SEED), LocalTransport(server), depth=2)
    rec = client._apply_grads = _Recorded(client._apply_grads)
    client.train(lambda: iter(batches), epochs=1)
    client.close()
    server.close()
    return rec, [client.state]


_CLIENTS = {"split": _run_split, "u_split": _run_u_split,
            "pipelined": _run_pipelined}


@pytest.mark.parametrize("clip", [0.0, 0.5], ids=["noclip", "clip"])
@pytest.mark.parametrize("optimizer", ["sgd", "adam", "adamw"])
@pytest.mark.parametrize("client", sorted(_CLIENTS))
def test_jitted_apply_equals_eager_and_traces_once(client, optimizer, clip,
                                                   monkeypatch):
    cfg = Config(mode="split", batch_size=BATCH, lr=0.01, optimizer=optimizer,
                 grad_clip_norm=clip,
                 weight_decay=0.01 if optimizer == "adamw" else 0.0)
    eager = state_mod.apply_grads
    entered = []

    def counted(tx, state, grads):
        entered.append(1)
        return eager(tx, state, grads)

    monkeypatch.setattr(state_mod, "apply_grads", counted)
    rec, states = _CLIENTS[client](cfg, _batches(STEPS))

    # entered at the trace, once a state structure (the U-shaped client
    # owns two stages), whatever the number of steps
    assert len(entered) == len(states)
    assert len(rec.grads) == len(states)
    tx = state_mod.make_tx(cfg)
    for got in states:
        key = jax.tree_util.tree_structure(got.params)
        assert len(rec.grads[key]) == STEPS
        want = rec.first[key]
        assert int(want.step) == 0
        for grads in rec.grads[key]:
            want = eager(tx, want, grads)
        assert int(got.step) == STEPS == int(want.step)
        _assert_trees_close(got.params, want.params, rtol=1e-5, atol=1e-6)
        _assert_trees_close(got.opt_state, want.opt_state,
                            rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------- #
# aliasing: what the apply donates, and who still holds the parameters
# ---------------------------------------------------------------------- #

def _runner(n_clients, **kw):
    cfg = Config(mode="split", batch_size=BATCH, num_clients=n_clients,
                 optimizer="adam", lr=0.01)
    plan = get_plan(mode="split")
    sample = np.zeros((BATCH, 28, 28, 1), np.float32)
    server = ServerRuntime(plan, cfg, jax.random.PRNGKey(0), sample)
    runner = MultiClientSplitRunner(
        plan, cfg, jax.random.PRNGKey(0),
        transport_factory=lambda i: LocalTransport(server),
        num_clients=n_clients, **kw)
    return server, runner


def test_apply_donates_opt_state_and_grads_not_params():
    cfg = Config(mode="split", batch_size=BATCH, optimizer="adam", lr=0.01)
    plan = get_plan(mode="split")
    batches = _batches(2)
    server = ServerRuntime(plan, cfg, jax.random.PRNGKey(SEED), batches[0][0])
    client = SplitClientTrainer(plan, cfg, jax.random.PRNGKey(SEED),
                                LocalTransport(server))
    client.train_step(*batches[0], 0)
    held = client.state
    params_before = _copy(held.params)
    seen = []
    inner = client._apply_grads
    client._apply_grads = lambda s, g: (seen.append(g), inner(s, g))[1]
    client.train_step(*batches[1], 1)
    server.close()
    # a caller that kept client.state.params across the step reads them
    assert _readable(held.params)
    _assert_trees_close(held.params, params_before, rtol=0, atol=0)
    assert int(held.step) == 1 and int(client.state.step) == 2
    # the moments and the gradients went into the new state's buffers
    moments = [leaf for leaf in jax.tree_util.tree_leaves(held.opt_state)
               if leaf.ndim]
    assert moments and all(leaf.is_deleted() for leaf in moments)
    assert all(leaf.is_deleted()
               for leaf in jax.tree_util.tree_leaves(seen[0]))


@pytest.mark.parametrize("compress", [None, "topk8"], ids=["dense", "topk8"])
def test_shared_mean_params_survive_every_clients_next_step(compress):
    """``sync_bottoms`` hands ONE mean tree to every client (and, with
    topk8, keeps it as the next round's reference): each client's next
    apply must leave it readable for the others."""
    _, runner = _runner(2, sync_bottoms_every=2, sync_compress=compress,
                        sync_density=0.1)
    for r in range(2):
        runner.train_round(_batches(2, seed=r))
    shared = runner.clients[0].state.params
    assert runner.clients[1].state.params is shared
    if compress == "topk8":
        assert runner._sync_ref is shared
    mean = _copy(shared)
    runner.train_round(_batches(2, seed=2))   # no sync: every client steps
    assert _readable(shared)
    _assert_trees_close(shared, mean, rtol=0, atol=0)
    for c in runner.clients:
        assert c.state.params is not shared
        assert _readable(c.state.params)
    # the second sync reads the reference and every client's parameters
    runner.train_round(_batches(2, seed=3))
    a, b = (c.state.params for c in runner.clients)
    _assert_trees_close(a, b, rtol=0, atol=0)
    if compress == "topk8":
        assert runner.sync_wire_bytes > 0


def test_pipelined_window_reads_params_then_after_state_moved_on():
    cfg = Config(mode="split", batch_size=BATCH, optimizer="adam", lr=0.01)
    plan = get_plan(mode="split")
    batches = _batches(6)
    server = ServerRuntime(plan, cfg, jax.random.PRNGKey(SEED), batches[0][0],
                           strict_steps=False)
    client = PipelinedSplitClientTrainer(
        plan, cfg, jax.random.PRNGKey(SEED), LocalTransport(server), depth=2)
    stale = []
    bwd = client._bwd

    def checked_bwd(params_then, x, g):
        assert _readable(params_then)
        stale.append(params_then is not client.state.params)
        return bwd(params_then, x, g)

    client._bwd = checked_bwd
    records = client.train(lambda: iter(batches), epochs=1)
    client.close()
    server.close()
    assert len(records) == len(batches) == len(stale)
    # depth 2: in the steady window the backward runs under parameters
    # one apply behind the state
    assert sum(stale) >= len(batches) - 2
    assert int(client.state.step) == len(batches)


# ---------------------------------------------------------------------- #
# no program is built after a client's third step
# ---------------------------------------------------------------------- #

@pytest.mark.parametrize("n_clients", [1, 4])
def test_no_program_built_after_third_step(n_clients):
    """The listener ``chip_smoke.py`` and the benchmark count with: a
    weak-type or sharding mismatch between ``make_state``'s first state
    and the apply's own output would show as a later compile."""
    built = []

    def on_event(name, secs, **kw):
        if name == COMPILE_EVENT:
            built.append(secs)

    server, runner = _runner(n_clients, concurrent=n_clients > 1)
    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        for r in range(3):
            runner.train_round(_batches(n_clients, seed=r))
        jax.block_until_ready([c.state for c in runner.clients])
        assert built, "the listener saw no compile at all"
        after_third = len(built)
        for r in range(3, 7):
            runner.train_round(_batches(n_clients, seed=r))
        jax.block_until_ready([c.state for c in runner.clients])
        assert len(built) == after_third
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
        runner.close()
        server.close()
