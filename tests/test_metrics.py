"""``metrics.auc``: one sort and prefix scans against float64 references.

Every case is checked against a float64 reference (brute-force pairs for
small n, ``rankdata``-style average ranks for large n) and against a frozen
copy of the earlier form, which ranked ties by two ``searchsorted`` calls
into the argsorted scores.
"""

import re
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import metrics

TOL = 1e-6


def searchsorted_auc(y, score):
    """The earlier ``metrics.auc``, kept as the old oracle."""
    y = y.astype(jnp.float32)
    n = y.shape[0]
    order = jnp.argsort(score)
    sorted_scores = score[order]
    ranks_lo = jnp.searchsorted(sorted_scores, score, side="left").astype(jnp.float32)
    ranks_hi = jnp.searchsorted(sorted_scores, score, side="right").astype(jnp.float32)
    ranks = 0.5 * (ranks_lo + ranks_hi + 1.0)
    n_pos = jnp.sum(y)
    n_neg = n - n_pos
    sum_pos_ranks = jnp.sum(ranks * y)
    return (sum_pos_ranks - n_pos * (n_pos + 1.0) / 2.0) / jnp.maximum(n_pos * n_neg, 1.0)


def pairs_auc(y, score):
    """Brute-force Mann-Whitney over every (positive, negative) pair."""
    y, s = np.asarray(y, np.float64), np.asarray(score, np.float64)
    pos, neg = s[y == 1], s[y == 0]
    wins = (pos[:, None] > neg[None, :]).sum() + 0.5 * (
        pos[:, None] == neg[None, :]).sum()
    return wins / max(len(pos) * len(neg), 1)


def rank_auc(y, score):
    """Average ranks of tied scores (as ``scipy.stats.rankdata``), float64."""
    y, s = np.asarray(y, np.float64), np.asarray(score, np.float64)
    _, inv, counts = np.unique(s, return_inverse=True, return_counts=True)
    ends = np.cumsum(counts)
    ranks = (ends - (counts - 1) / 2.0)[inv]
    n_pos = y.sum()
    n_neg = len(y) - n_pos
    return (np.sum(ranks * y) - n_pos * (n_pos + 1) / 2) / max(n_pos * n_neg, 1)


def _case(name):
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    if name == "continuous_200":
        y = rng.integers(0, 2, 200)
        s = rng.normal(size=200) + 0.5 * y
    elif name.startswith("ties_"):
        n, levels = {"ties_5": (2000, 5), "ties_40": (21000, 40)}[name]
        y = (rng.random(n) < 0.22).astype(int)
        s = rng.integers(0, levels, n) * 0.1 - 1.0 + 0.2 * y * rng.random(n)
        s = np.round(s, 1)  # early boosting margins: few distinct values
    elif name == "all_equal":
        y = rng.integers(0, 2, 500)
        s = np.full(500, 0.25)
    elif name == "signed_zeros":
        y = rng.integers(0, 2, 300)
        s = np.where(rng.random(300) < 0.5, -0.0, 0.0)
        s = np.where(rng.random(300) < 0.3, rng.normal(size=300), s)
    elif name == "one_class":
        y = np.ones(400, int)
        s = rng.normal(size=400)
    elif name == "gmsc_105k":
        y = (rng.random(105_000) < 0.067).astype(int)
        s = rng.normal(size=105_000) + 0.8 * y
    return (jnp.asarray(y, jnp.float32), jnp.asarray(s, jnp.float32))


@pytest.mark.parametrize("name", ["continuous_200", "ties_5", "ties_40",
                                  "all_equal", "signed_zeros", "one_class",
                                  "gmsc_105k"])
def test_auc_matches_float64_reference_and_searchsorted_form(name):
    y, s = _case(name)
    got = float(jax.jit(metrics.auc)(y, s))
    old = float(jax.jit(searchsorted_auc)(y, s))
    small = y.shape[0] <= 2000
    ref = pairs_auc(y, s) if small and name != "one_class" else rank_auc(y, s)
    assert np.isfinite(got)
    assert abs(got - ref) <= TOL
    assert abs(old - ref) <= TOL
    assert abs(got - old) <= TOL
    if name == "all_equal":
        assert got == pytest.approx(0.5, abs=TOL)
    if name == "one_class":
        assert got == old == 0.0


def test_auc_lowers_with_no_gather_scatter_or_while():
    n = 105_000
    a = jax.ShapeDtypeStruct((n,), jnp.float32)
    text = jax.jit(metrics.auc).lower(a, a).as_text()
    for op in ("gather", "scatter", "while"):
        assert not re.search(rf"\b{op}\b", text), op
    assert len(re.findall(r"\bsort\b", text)) >= 1
