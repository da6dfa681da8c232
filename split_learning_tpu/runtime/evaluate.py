"""Test-split evaluation — accuracy + mean loss of the full composition.

The reference downloads and caches the MNIST *test* split
(``src/client_part.py:66-78``) but never evaluates on it: the only
acceptance signal is the eyeballed MLflow loss curve (SURVEY.md §4).
Here evaluation is a first-class op over any SplitPlan's full composition,
usable on params from the fused trainer, an assembled MPMD pair, or a
restored checkpoint.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from split_learning_tpu.core.losses import (
    cross_entropy, final_loss, refuse_objective)
from split_learning_tpu.core.stage import SplitPlan
from split_learning_tpu.data.datasets import Split, batches


def _accumulate_metrics(split: Split, batch_size: int,
                        score_batch) -> Dict[str, float]:
    """The one home of the metric accounting rules: ``score_batch(x, y)
    -> (loss, correct)`` per batch; predictions count label *elements*
    (B for classifiers, B*T for the causal LM), ``examples`` counts
    rows, perplexity is exp(mean CE) nulled on overflow (inf/nan are
    not JSON tokens)."""
    total = rows = correct_sum = 0
    loss_sum = 0.0
    # fixed order, keep the partial tail batch: every example counts once
    for x, y in batches(split, batch_size, shuffle=False):
        loss, correct = score_batch(x, y)
        n = int(np.prod(np.shape(y)))
        total += n
        rows += len(y)
        correct_sum += int(correct)
        loss_sum += float(loss) * n
    if total == 0:
        return {"accuracy": float("nan"), "loss": float("nan"),
                "perplexity": float("nan"), "examples": 0, "predictions": 0}
    mean_loss = loss_sum / total
    with np.errstate(over="ignore"):
        ppl = float(np.exp(mean_loss))
    return {"accuracy": correct_sum / total, "loss": mean_loss,
            "perplexity": ppl if np.isfinite(ppl) else None,
            "examples": rows, "predictions": total}


def evaluate(plan: SplitPlan, params: Sequence[Any], split: Split,
             batch_size: int = 512) -> Dict[str, float]:
    """Accuracy and mean CE loss of ``plan.apply(params, .)`` on a split.

    ``params`` is the per-stage parameter sequence (tuple or list — a raw
    orbax restore yields lists, which ``plan.apply`` accepts as-is).
    """
    params = jax.tree_util.tree_map(jnp.asarray, list(params))
    last = plan.num_stages - 1

    @jax.jit
    def fwd(params, x, y):
        feats = plan.apply_range(params, x, 0, last)
        logits = plan.stages[last].apply(params[last], feats)
        loss = final_loss(plan.stages[last], params[last], feats, y,
                          logits=logits)
        correct = jnp.sum(jnp.argmax(logits, axis=-1) == y)
        return loss, correct

    return _accumulate_metrics(
        split, batch_size,
        lambda x, y: fwd(params, jnp.asarray(x), jnp.asarray(y)))


def split_client_stages(plan: SplitPlan, client_params: Sequence[Any]):
    """Partition the client-owned stages (and their params) around the
    server stage: ``(pre_stages, pre_params, post_stages, post_params)``
    — the ownership protocol shared by split-party evaluation and
    decoding. Raises on a params/ownership mismatch or a plan without a
    server stage."""
    client_idx = plan.stages_of("client")
    if len(client_params) != len(client_idx):
        raise ValueError(
            f"expected params for {len(client_idx)} client-owned stages, "
            f"got {len(client_params)}")
    server_idx = plan.stages_of("server")
    if not server_idx:
        raise ValueError("plan has no server-owned stage to call remotely")
    first_server = min(server_idx)
    client_params = jax.tree_util.tree_map(jnp.asarray, list(client_params))
    pre_stages = [plan.stages[i] for i in client_idx if i < first_server]
    post_stages = [plan.stages[i] for i in client_idx if i > first_server]
    return (pre_stages, client_params[:len(pre_stages)],
            post_stages, client_params[len(pre_stages):])


def evaluate_remote(plan: SplitPlan, client_params: Sequence[Any],
                    transport: Any, split: Split,
                    batch_size: int = 512) -> Dict[str, float]:
    """Split-party inference: the client holds ONLY its own stages and
    the server-owned compute happens behind ``transport.predict``.

    ``client_params`` is the parameter sequence for the client-owned
    stages in plan order (one stage for the classic split, two for the
    U-shape). Labels never leave the client either way; metrics match
    :func:`evaluate` of the full composition to float tolerance
    (tests/test_split_inference.py)."""
    pre_stages, pre_params, post_stages, post_params = \
        split_client_stages(plan, client_params)

    @jax.jit
    def pre(params, x):
        for st, p in zip(pre_stages, params):
            x = st.apply(p, x)
        return x

    if not post_stages:
        refuse_objective(plan, "split-party evaluation (the server's "
                               "predict returns logits)")

    @jax.jit
    def post_and_score(params, feats, y):
        for st, p in zip(post_stages[:-1], params):
            feats = st.apply(p, feats)
        if post_stages:
            logits = post_stages[-1].apply(params[-1], feats)
            loss = final_loss(post_stages[-1], params[-1], feats, y,
                              logits=logits)
        else:
            logits, loss = feats, cross_entropy(feats, y)
        correct = jnp.sum(jnp.argmax(logits, axis=-1) == y)
        return loss, correct

    def score_batch(x, y):
        acts = pre(pre_params, jnp.asarray(x))
        out = transport.predict(np.asarray(acts))
        return post_and_score(post_params, jnp.asarray(out),
                              jnp.asarray(y))

    return _accumulate_metrics(split, batch_size, score_batch)
