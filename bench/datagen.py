"""Seeded credit-scoring tables at the shapes of the paper's datasets.

The Kaggle and UCI tables are not available offline, so each configuration's
``dataset`` block gives the published shape (rows, features, positive rate,
the paper's 7:3 train/test split) and the generator below fills it: heavy
tailed monetary columns with a missing-value sentinel, bounded utilisation
ratios and counts, and a sparse logit with pairwise interactions that sets
the labels.  The same seed gives the same table.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np


class Table(NamedTuple):
    x_train: np.ndarray   # (n_train, d) float32
    y_train: np.ndarray   # (n_train,) float32 in {0, 1}
    x_test: np.ndarray
    y_test: np.ndarray


def credit_table(spec: dict, seed: int) -> Table:
    """The table a configuration's ``dataset`` block describes."""
    rng = np.random.default_rng(seed)
    n, d = int(spec["rows"]), int(spec["features"])
    n_heavy = d // 3
    n_ratio = d // 3
    n_count = d - n_heavy - n_ratio

    heavy = rng.lognormal(mean=0.0, sigma=1.2, size=(n, n_heavy))
    ratio = rng.beta(2.0, 5.0, size=(n, n_ratio))
    count = rng.poisson(lam=3.0, size=(n, n_count)).astype(np.float64)
    x = np.concatenate([heavy, ratio, count], axis=1)

    miss = rng.random((n, n_heavy)) < float(spec["missing_share"])
    x[:, :n_heavy][miss] = float(spec["missing_sentinel"])

    z = (x - x.mean(axis=0)) / (x.std(axis=0) + 1e-9)
    w = rng.normal(size=d) * (rng.random(d) < 0.7)
    logit = z @ w * 0.8
    for _ in range(int(spec["interaction_pairs"])):
        i, j = rng.integers(0, d, size=2)
        logit += 0.5 * z[:, i] * z[:, j]
    k = rng.integers(0, d)
    logit += 0.6 * np.abs(z[:, k]) - 0.5
    logit += rng.normal(scale=0.8, size=n)
    thresh = np.sort(logit)[int((1.0 - float(spec["positive_rate"])) * n)]
    y = (logit > thresh).astype(np.float32)
    x = x.astype(np.float32)

    perm = rng.permutation(n)
    k_train = int(float(spec["train_share"]) * n)
    tr, te = perm[:k_train], perm[k_train:]
    return Table(x[tr], y[tr], x[te], y[te])
