"""Sibling-subtraction histogram pipeline (DESIGN.md §6).

The contract lattice, bottom up:

* histogram ALGEBRA — ``parent == left + right`` additively, and
  ``derive_sibling`` interleaves (left, parent − left) in routing order;
* PROVIDERS — every child provider (generic ``as_child_fn`` adaptation,
  fused Pallas child kernel) agrees with the direct left-child histogram;
* TREES — subtraction-vs-direct parity across the registry backends
  (predictions within float-reassociation tolerance; on this fixed data the
  trees come out structurally identical);
* TRAINING — both engines run the pipeline end-to-end and stay equivalent
  to each other; the leaf fast path is bit-identical to the formulation it
  replaced.

The federated side of the lattice (bit-identity vs centralized with
subtraction on both sides, exact byte reconciliation at half width, the
>= 1.7x measured phase cut) lives in federation/selftest.py, invoked by
tests/test_federation.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import boosting, forest, tree
from repro.core.histogram import (
    as_child_fn,
    compute_histogram,
    compute_histogram_onehot,
    derive_sibling,
    leaf_stats,
)
from repro.core.types import FedGBFConfig, TreeConfig


def _case(seed, n, d, B, frontier):
    rng = np.random.default_rng(seed)
    return (
        jnp.asarray(rng.integers(0, B, (n, d)), jnp.int32),
        jnp.asarray(rng.normal(size=n), jnp.float32),
        jnp.asarray(rng.random(n) + 0.05, jnp.float32),
        jnp.asarray(rng.integers(0, 2, n), jnp.float32),
        jnp.asarray(rng.integers(0, frontier, n), jnp.int32),
    )


# ---------------------------------------------------------------------------
# Histogram algebra
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("parents", [1, 2, 4, 8])
@pytest.mark.parametrize("seed", [0, 1])
def test_parent_equals_left_plus_right(parents, seed):
    """Additivity: the parent histogram is exactly the sum of its children's
    (the identity the whole pipeline rests on)."""
    n, d, B = 600, 5, 16
    binned, g, h, w, assign = _case(seed, n, d, B, 2 * parents)
    parent = compute_histogram(binned, g, h, w, assign // 2, parents, B)
    left = as_child_fn(compute_histogram)(binned, g, h, w, assign, parents, B)
    right_w = w * (assign % 2).astype(w.dtype)
    right = compute_histogram(binned, g, h, right_w, assign // 2, parents, B)
    np.testing.assert_allclose(
        np.asarray(left + right), np.asarray(parent), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("parents", [1, 2, 4])
def test_derive_sibling_matches_direct_frontier(parents):
    """derive_sibling(parent, left) == the direct full-frontier histogram,
    up to float reassociation, with children interleaved in routing order."""
    n, d, B = 700, 6, 8
    binned, g, h, w, assign = _case(3, n, d, B, 2 * parents)
    parent = compute_histogram(binned, g, h, w, assign // 2, parents, B)
    left = as_child_fn(compute_histogram)(binned, g, h, w, assign, parents, B)
    derived = derive_sibling(parent, left)
    direct = compute_histogram(binned, g, h, w, assign, 2 * parents, B)
    assert derived.shape == direct.shape
    np.testing.assert_allclose(
        np.asarray(derived), np.asarray(direct), rtol=1e-4, atol=1e-5
    )
    # even nodes ARE the left histograms, bit-for-bit (only right is derived)
    np.testing.assert_array_equal(
        np.asarray(derived[0::2]), np.asarray(left)
    )


def test_child_providers_agree():
    """Generic adaptation of every formulation + the fused Pallas child
    kernel compute the same left-child histogram."""
    from repro.kernels.histogram.ops import compute_histogram_pallas_child

    n, d, B, parents = 700, 9, 16, 4
    binned, g, h, w, assign = _case(5, n, d, B, 2 * parents)
    ref = as_child_fn(compute_histogram)(binned, g, h, w, assign, parents, B)
    oh = as_child_fn(compute_histogram_onehot)(
        binned, g, h, w, assign, parents, B
    )
    pal = compute_histogram_pallas_child(
        binned, g, h, w, assign, parents, B
    )
    np.testing.assert_allclose(np.asarray(oh), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Leaf-statistics fast path
# ---------------------------------------------------------------------------
def test_leaf_stats_bit_identical_to_pseudo_feature_histogram():
    """The direct three-channel segment_sum replaces the old (n, 1)-zeros
    pseudo-feature compute_histogram call bit-for-bit (same segment ids,
    same stacked operand, same reduction)."""
    n, leaves = 900, 8
    rng = np.random.default_rng(7)
    g = jnp.asarray(rng.normal(size=n), jnp.float32)
    h = jnp.asarray(rng.random(n), jnp.float32)
    w = jnp.asarray(rng.integers(0, 2, n), jnp.float32)
    assign = jnp.asarray(rng.integers(0, leaves, n), jnp.int32)
    old = compute_histogram(
        jnp.zeros((n, 1), jnp.int32), g, h, w, assign, leaves, 1
    )[:, 0, 0, :]
    np.testing.assert_array_equal(
        np.asarray(leaf_stats(g, h, w, assign, leaves)), np.asarray(old)
    )


# ---------------------------------------------------------------------------
# Tree / training parity across registry backends
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["local", "local-pallas"])
@pytest.mark.parametrize("seed", [0, 2])
def test_subtraction_vs_direct_tree_parity(backend, seed):
    """Trees built with the subtraction pipeline agree with the direct
    reference oracle on every registry backend: identical routing behaviour
    within float-reassociation tolerance (structural equality asserted too —
    on CPU with fixed data the argmax never lands on a reassociation-size
    tie for these seeds)."""
    from repro.core.backend import get_backend

    n, d, B = 800, 7, 16
    rng = np.random.default_rng(seed)
    binned = jnp.asarray(rng.integers(0, B, (n, d)), jnp.int32)
    g = jnp.asarray(rng.normal(size=n), jnp.float32)
    h = jnp.asarray(rng.random(n) + 0.1, jnp.float32)
    w = jnp.asarray(rng.integers(0, 2, n), jnp.float32)
    fm = jnp.ones(d, bool)
    bk = get_backend(backend)

    cfg_d = TreeConfig(max_depth=3, num_bins=B, hist_subtraction=False)
    cfg_s = TreeConfig(max_depth=3, num_bins=B, hist_subtraction=True)
    t_d, a_d = tree.build_tree(binned, g, h, w, fm, cfg_d, backend=bk)
    t_s, a_s = tree.build_tree(binned, g, h, w, fm, cfg_s, backend=bk)

    np.testing.assert_array_equal(np.asarray(t_d.feature), np.asarray(t_s.feature))
    np.testing.assert_array_equal(
        np.asarray(t_d.threshold), np.asarray(t_s.threshold)
    )
    np.testing.assert_array_equal(np.asarray(a_d), np.asarray(a_s))
    np.testing.assert_allclose(
        np.asarray(t_d.leaf_weight), np.asarray(t_s.leaf_weight),
        rtol=1e-5, atol=1e-6,
    )


def test_subtraction_forest_and_engines_end_to_end():
    """Full training with hist_subtraction on: scan and loop engines stay
    metric-equivalent to each other, and the end metrics track the direct
    pipeline within the §5/§6 tolerance class."""
    rng = np.random.default_rng(11)
    n, d = 1200, 6
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = (x[:, 0] - 0.5 * x[:, 1] + rng.normal(0, 0.5, n) > 0).astype(np.float32)
    x, y = jnp.asarray(x), jnp.asarray(y)
    base = FedGBFConfig(
        rounds=3, n_trees_max=3, n_trees_min=2, rho_id_min=0.5, rho_id_max=0.8,
        tree=TreeConfig(max_depth=3, num_bins=16, hist_subtraction=False),
    )
    import dataclasses

    sub = dataclasses.replace(
        base, tree=dataclasses.replace(base.tree, hist_subtraction=True)
    )
    _, h_scan = boosting.train_fedgbf(x, y, sub, jax.random.PRNGKey(0))
    _, h_loop = boosting.train_fedgbf(x, y, sub, jax.random.PRNGKey(0),
                                      engine="loop")
    for a, b in zip(h_scan.train, h_loop.train):
        for k in a:
            assert abs(a[k] - b[k]) <= 1e-5, (k, a[k], b[k])
    _, h_direct = boosting.train_fedgbf(x, y, base, jax.random.PRNGKey(0))
    for a, b in zip(h_scan.train, h_direct.train):
        for k in a:
            assert abs(a[k] - b[k]) <= 5e-3, (k, a[k], b[k])


def test_subtraction_unsplittable_frontier():
    """Degenerate case: a level with no split keeps every sample in the left
    child, so the derived right siblings are all-zero histograms — the tree
    must match the direct pipeline's split-free structure exactly."""
    n, d, B = 128, 3, 8
    binned = jnp.zeros((n, d), jnp.int32)
    g = jnp.asarray(np.random.default_rng(0).normal(size=n), jnp.float32)
    ones = jnp.ones(n, jnp.float32)
    cfg = TreeConfig(max_depth=3, num_bins=B, hist_subtraction=True)
    tr, assign = tree.build_tree(binned, g, ones, ones, jnp.ones(d, bool), cfg)
    assert np.all(np.asarray(tr.feature) == -1)
    assert np.all(np.asarray(assign) == 0)


def test_masks_compose_with_subtraction():
    """Weighted (GOSS-style) sample masks ride the same weight channel the
    child provider left-masks — forest build agrees with the direct path."""
    rng = np.random.default_rng(13)
    n, d, B = 600, 5, 16
    binned = jnp.asarray(rng.integers(0, B, (n, d)), jnp.int32)
    g = jnp.asarray(rng.normal(size=n), jnp.float32)
    h = jnp.ones(n, jnp.float32)
    n_top, n_rand = forest.goss_counts(n, 0.4, 0.5)
    smask, fmask = forest.goss_masks(
        jax.random.PRNGKey(3), g, d, 3, n_top, n_rand, d
    )
    cfg_d = TreeConfig(max_depth=3, num_bins=B, hist_subtraction=False)
    cfg_s = TreeConfig(max_depth=3, num_bins=B, hist_subtraction=True)
    trees_d, pred_d = forest.build_forest(binned, g, h, smask, fmask, cfg_d)
    trees_s, pred_s = forest.build_forest(binned, g, h, smask, fmask, cfg_s)
    np.testing.assert_array_equal(
        np.asarray(trees_d.feature), np.asarray(trees_s.feature)
    )
    np.testing.assert_allclose(
        np.asarray(pred_d), np.asarray(pred_s), rtol=1e-5, atol=1e-6
    )


def test_derive_sibling_zeroes_empty_bins_and_leaves_countless_payloads():
    """A derived bin with no rows is exactly 0, not the parent's rounding
    residue; a payload that carries no counts (count 0 in the parent, as
    the quantized transports ship) is derived as plain ``parent - left``."""
    rng = np.random.default_rng(3)
    parent = jnp.asarray(rng.random((2, 3, 4, 3)) * 100.0, jnp.float32)
    parent = parent.at[..., -1].set(jnp.asarray(rng.integers(1, 9, (2, 3, 4)),
                                                jnp.float32))
    # left takes every row of bin 0 (same count), its sums rounded apart
    left = (parent * 0.5).at[:, :, 0, :].set(parent[:, :, 0, :] * (1 + 1e-7))
    left = left.at[:, :, 0, -1].set(parent[:, :, 0, -1])
    right = derive_sibling(parent, left).reshape(2, 2, 3, 4, 3)[:, 1]
    np.testing.assert_array_equal(np.asarray(right[:, :, 0, :]), 0.0)
    np.testing.assert_array_equal(np.asarray(right[:, :, 1:, :]),
                                  np.asarray((parent - left)[:, :, 1:, :]))
    countless = parent.at[..., -1].set(0.0)
    left_c = left.at[..., -1].set(0.0)
    np.testing.assert_array_equal(
        np.asarray(derive_sibling(countless, left_c).reshape(2, 2, 3, 4, 3)[:, 1]),
        np.asarray(countless - left_c))


@pytest.mark.parametrize("backend", ["local", "local-pallas"])
def test_deep_subtraction_never_splits_off_an_empty_child(backend):
    """Depth 8, B 256, large root sums and a tiny ``min_child_weight``: the
    rounding residue that subtraction carries down would pass the weight
    test in empty bins; with empty derived bins zeroed, every split keeps
    sampled rows on both sides."""
    from repro.core.backend import get_backend

    binned, g, h, _, _ = _case(0, 3000, 5, 256, 1)
    rng = np.random.default_rng(1)
    smask = jnp.asarray(rng.random((2, 3000)) < 0.9, jnp.float32)
    fmask = jnp.ones((2, 5), bool)
    cfg = TreeConfig(max_depth=8, num_bins=256, hist_subtraction=True,
                     min_child_weight=1e-6)
    trees, _ = tree.build_round(binned, g * 100.0, h * 100.0, smask, fmask,
                                cfg, backend=get_backend(backend))
    b = np.asarray(binned)
    for t in range(2):
        rows = np.flatnonzero(np.asarray(smask[t]) > 0)
        feat, thr = np.asarray(trees.feature[t]), np.asarray(trees.threshold[t])
        idx = np.zeros(rows.size, np.int64)
        for level in range(8):
            off = 2 ** level - 1
            f, th = feat[off + idx], thr[off + idx]
            right = (f >= 0) & (b[rows, np.clip(f, 0, None)] > th)
            for k in np.flatnonzero(feat[off:off + 2 ** level] >= 0):
                side = right[idx == k]
                assert side.any() and not side.all(), (t, level, k)
            idx = idx * 2 + right
