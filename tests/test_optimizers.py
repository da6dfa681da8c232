"""Optimizer factory (runtime/state.py make_tx / make_lr).

The reference trains everything with SGD(lr=0.01)
(``src/client_part.py:17``, ``src/server_part.py:15``); that stays the
default, bit-for-bit. The transformer/causal-LM families added beyond
the reference's scope get the standard recipe — adam/adamw with
decoupled weight decay and warmup/cosine schedules — through the same
single construction site every trainer shares.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from split_learning_tpu.runtime.state import (
    apply_grads, make_lr, make_state, make_tx, sgd)
from split_learning_tpu.utils import Config


def toy_tree():
    return {"w": jnp.arange(6.0).reshape(2, 3), "b": jnp.ones((3,))}


def test_default_config_is_reference_sgd_exactly():
    """make_tx(Config()) must reproduce the reference optimizer's update
    bit-for-bit — the parity guarantees rest on it."""
    params = toy_tree()
    grads = jax.tree_util.tree_map(lambda x: 0.1 * x + 0.5, params)
    want = apply_grads(sgd(0.01), make_state(params, sgd(0.01)), grads)
    got = apply_grads(make_tx(Config()), make_state(params, make_tx(Config())),
                      grads)
    for a, b in zip(jax.tree_util.tree_leaves(want.params),
                    jax.tree_util.tree_leaves(got.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_make_lr_warmup_then_constant():
    cfg = Config(warmup_steps=10)
    lr = make_lr(cfg)
    assert float(lr(0)) == 0.0
    assert np.isclose(float(lr(5)), cfg.lr / 2)
    assert np.isclose(float(lr(10)), cfg.lr)
    assert np.isclose(float(lr(1000)), cfg.lr)


def test_make_lr_warmup_cosine():
    cfg = Config(warmup_steps=10, decay_steps=110)
    lr = make_lr(cfg)
    assert float(lr(0)) == 0.0
    assert np.isclose(float(lr(10)), cfg.lr)
    mid = float(lr(60))  # halfway through the cosine leg
    assert np.isclose(mid, cfg.lr / 2, rtol=1e-3)
    assert float(lr(110)) <= 1e-9
    # constant default stays a plain float (no schedule state)
    assert make_lr(Config()) == Config().lr


def test_adamw_decoupled_decay_moves_params_without_gradient():
    cfg = Config(optimizer="adamw", weight_decay=0.1, lr=0.1)
    tx = make_tx(cfg)
    params = toy_tree()
    state = make_state(params, tx)
    zero = jax.tree_util.tree_map(jnp.zeros_like, params)
    new = apply_grads(tx, state, zero)
    # decoupled decay shrinks weights even at zero gradient
    assert float(jnp.abs(new.params["w"]).sum()) \
        < float(jnp.abs(params["w"]).sum())


def test_sgd_weight_decay_is_coupled_l2():
    cfg = Config(optimizer="sgd", weight_decay=0.5, lr=0.1)
    tx = make_tx(cfg)
    params = toy_tree()
    state = make_state(params, tx)
    zero = jax.tree_util.tree_map(jnp.zeros_like, params)
    new = apply_grads(tx, state, zero)
    # update = -lr * wd * w
    np.testing.assert_allclose(np.asarray(new.params["w"]),
                               np.asarray(params["w"]) * (1 - 0.1 * 0.5),
                               rtol=1e-6)


def test_config_rejects_bad_optimizer_combos():
    with pytest.raises(ValueError, match="Unknown optimizer"):
        Config(optimizer="lamb")
    with pytest.raises(ValueError, match="adamw"):
        Config(optimizer="adam", weight_decay=0.1)
    with pytest.raises(ValueError, match="decay_steps"):
        Config(warmup_steps=100, decay_steps=50)
    with pytest.raises(ValueError, match="non-negative"):
        Config(weight_decay=-1.0)


def test_optimizer_env_parsing():
    cfg = Config.from_env(env={"SLT_OPTIMIZER": "adamw",
                               "SLT_WEIGHT_DECAY": "0.05",
                               "SLT_WARMUP_STEPS": "7",
                               "SLT_DECAY_STEPS": "70"})
    assert cfg.optimizer == "adamw"
    assert cfg.weight_decay == 0.05
    assert cfg.warmup_steps == 7
    assert cfg.decay_steps == 70


@pytest.mark.slow
def test_fused_trainer_adamw_learns_and_differs_from_sgd():
    """The fused trainer accepts the new optimizers end-to-end: adamw
    with warmup reduces the loss and takes a different trajectory from
    the reference SGD default."""
    from split_learning_tpu.models import get_plan
    from split_learning_tpu.runtime.fused import FusedSplitTrainer

    rs = np.random.RandomState(3)
    # one batch repeated: the trajectory must descend on data it has
    # seen, which keeps the assertion sharp at toy scale
    xb = rs.randn(16, 28, 28, 1).astype(np.float32)
    yb = rs.randint(0, 10, (16,)).astype(np.int64)

    def run(cfg):
        plan = get_plan(mode="split")
        tr = FusedSplitTrainer(plan, cfg, jax.random.PRNGKey(0), xb)
        return [tr.train_step(xb, yb) for _ in range(10)]

    adamw = run(Config(optimizer="adamw", lr=1e-3, weight_decay=0.01,
                       warmup_steps=2, batch_size=16))
    sgd_l = run(Config(batch_size=16))
    assert np.mean(adamw[-3:]) < adamw[0]
    assert not np.allclose(adamw, sgd_l)


def test_momentum_rejected_off_sgd_and_env_parses():
    with pytest.raises(ValueError, match="momentum"):
        Config(optimizer="adamw", momentum=0.9)
    assert Config.from_env(env={"SLT_MOMENTUM": "0.9"}).momentum == 0.9


def test_grad_clip_global_norm():
    cfg = Config(grad_clip_norm=1.0, lr=1.0)
    tx = make_tx(cfg)
    params = toy_tree()
    state = make_state(params, tx)
    big = jax.tree_util.tree_map(lambda x: 100.0 * jnp.ones_like(x), params)
    new = apply_grads(tx, state, big)
    delta = jax.tree_util.tree_map(lambda a, b: np.asarray(a) - np.asarray(b),
                                   new.params, params)
    norm = np.sqrt(sum(float((d ** 2).sum())
                       for d in jax.tree_util.tree_leaves(delta)))
    # update = -lr * clipped grad, so its norm is exactly the clip
    assert np.isclose(norm, 1.0, rtol=1e-5)
    with pytest.raises(ValueError, match="non-negative"):
        Config(grad_clip_norm=-0.5)


@pytest.mark.slow
def test_schedule_position_survives_fused_resume(tmp_path):
    """Warmup/cosine schedules ride optax's step count inside opt_state:
    a checkpoint/resume at step k must continue the schedule from k, not
    restart warmup — the resumed trajectory equals the uninterrupted one
    step for step."""
    import numpy as np

    from split_learning_tpu.models import get_plan
    from split_learning_tpu.runtime.checkpoint import Checkpointer
    from split_learning_tpu.runtime.fused import FusedSplitTrainer

    rs = np.random.RandomState(9)
    xs = rs.randn(8, 16, 28, 28, 1).astype(np.float32)
    ys = rs.randint(0, 10, (8, 16)).astype(np.int64)
    cfg = Config(optimizer="adamw", lr=5e-3, warmup_steps=3,
                 decay_steps=8, batch_size=16)

    def trainer():
        return FusedSplitTrainer(get_plan(mode="split"), cfg,
                                 jax.random.PRNGKey(0), xs[0])

    # uninterrupted reference
    ref = trainer()
    ref_losses = [ref.train_step(x, y) for x, y in zip(xs, ys)]

    # train 4 steps, checkpoint, resume in a FRESH trainer, finish
    a = trainer()
    for x, y in zip(xs[:4], ys[:4]):
        a.train_step(x, y)
    ck = Checkpointer(str(tmp_path / "ck"))
    ck.save(4, {"trainer": a.state})
    ck.close()

    b = trainer()
    ck2 = Checkpointer(str(tmp_path / "ck"))
    b.state = ck2.restore({"trainer": b.state})["trainer"]
    ck2.close()
    resumed = [b.train_step(x, y) for x, y in zip(xs[4:], ys[4:])]
    np.testing.assert_allclose(resumed, ref_losses[4:], rtol=1e-6,
                               atol=1e-7)
