"""Evaluation metrics reported by the paper: AUC, accuracy, F1 (§4.1)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.obs import compiles as compiles_mod


def auc(y: jnp.ndarray, score: jnp.ndarray) -> jnp.ndarray:
    """Area under ROC via the Mann-Whitney rank statistic (ties averaged).

    One sort of ``(score, y)`` on the score carries the labels along; the
    rest is elementwise passes and prefix scans, with no gather, scatter
    or binary search.  Equal neighbours in the sorted scores form a tie
    group; ``-0.0`` and ``+0.0`` compare equal, so they share a group.  A
    position's group starts at the running max of the group-start indices
    and ends at the reversed running min of the group-end indices; its
    1-based average rank is ``(start + end) / 2 + 1``.  The positions are
    int32 and exact; the ranks (exact in float32 below 2**23 rows) are
    summed over the positives in float32, as the statistic always was.
    A single-class ``y`` gives 0: the pair count is guarded to at least 1.
    """
    counter = compiles_mod.installed()
    if counter is not None:
        counter.auc_traces.inc()
    n = score.shape[0]
    # Order within a tie leaves the rank sum as it is; a stable sort would
    # carry an iota as a third operand on the TPU.
    s, ys = jax.lax.sort((score, y.astype(jnp.float32)), num_keys=1,
                         is_stable=False)
    pos = jax.lax.iota(jnp.int32, n)
    new = jnp.concatenate([jnp.ones((1,), bool), s[1:] != s[:-1]])
    last = jnp.concatenate([new[1:], jnp.ones((1,), bool)])
    start = jax.lax.cummax(jnp.where(new, pos, 0))
    end = jax.lax.cummin(jnp.where(last, pos, n - 1), reverse=True)
    ranks = 0.5 * (start + end).astype(jnp.float32) + 1.0
    n_pos = jnp.sum(ys)
    n_neg = n - n_pos
    sum_pos_ranks = jnp.sum(ranks * ys)
    return (sum_pos_ranks - n_pos * (n_pos + 1.0) / 2.0) / jnp.maximum(n_pos * n_neg, 1.0)


def accuracy(y: jnp.ndarray, prob: jnp.ndarray, threshold: float = 0.5) -> jnp.ndarray:
    pred = (prob >= threshold).astype(jnp.float32)
    return jnp.mean(pred == y.astype(jnp.float32))


def f1_score(y: jnp.ndarray, prob: jnp.ndarray, threshold: float = 0.5) -> jnp.ndarray:
    y = y.astype(jnp.float32)
    pred = (prob >= threshold).astype(jnp.float32)
    tp = jnp.sum(pred * y)
    fp = jnp.sum(pred * (1.0 - y))
    fn = jnp.sum((1.0 - pred) * y)
    return 2.0 * tp / jnp.maximum(2.0 * tp + fp + fn, 1.0)


def classification_report(y: jnp.ndarray, margin: jnp.ndarray) -> dict:
    """All three paper metrics from raw margins."""
    prob = 1.0 / (1.0 + jnp.exp(-margin))
    return {
        "auc": float(auc(y, margin)),
        "acc": float(accuracy(y, prob)),
        "f1": float(f1_score(y, prob)),
    }


def multiclass_report(y: jnp.ndarray, margin: jnp.ndarray) -> dict:
    """Accuracy + macro-F1 from (n, K) margins (argmax decision rule).

    AUC is a binary ranking statistic — it has no single canonical K-class
    form, so the multiclass report drops it rather than invent one.
    """
    k = margin.shape[-1]
    y = y.astype(jnp.int32)
    pred = jnp.argmax(margin, axis=-1).astype(jnp.int32)
    f1s = []
    for c in range(k):
        yc = (y == c).astype(jnp.float32)
        pc = (pred == c).astype(jnp.float32)
        tp = jnp.sum(pc * yc)
        fp = jnp.sum(pc * (1.0 - yc))
        fn = jnp.sum((1.0 - pc) * yc)
        f1s.append(2.0 * tp / jnp.maximum(2.0 * tp + fp + fn, 1.0))
    return {
        "acc": float(jnp.mean((pred == y).astype(jnp.float32))),
        "macro_f1": float(jnp.mean(jnp.stack(f1s))),
    }
