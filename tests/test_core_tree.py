"""Tree builder correctness: against a pure-numpy oracle and invariants."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import binning, forest, losses, split, tree
from repro.core.histogram import compute_histogram
from repro.core.types import TreeConfig


# ----------------------------------------------------------------------------
# Pure-numpy reference GBDT tree (level-wise, same semantics) — the oracle.
# ----------------------------------------------------------------------------
def numpy_build_tree(binned, g, h, w, fmask, cfg: TreeConfig):
    n, d = binned.shape
    assign = np.zeros(n, np.int32)
    feats, thrs = [], []
    for level in range(cfg.max_depth):
        num_nodes = 2**level
        level_feat = np.full(num_nodes, -1, np.int32)
        level_thr = np.full(num_nodes, cfg.num_bins, np.int32)
        for node in range(num_nodes):
            in_node = (assign == node) & (w > 0)
            best_gain, best = 0.0, None
            Gt, Ht = g[in_node].sum(), h[in_node].sum()
            parent = Gt**2 / (Ht + cfg.lambda_)
            for f in range(d):
                if not fmask[f]:
                    continue
                for b in range(cfg.num_bins - 1):
                    left = in_node & (binned[:, f] <= b)
                    Gl, Hl = g[left].sum(), h[left].sum()
                    Gr, Hr = Gt - Gl, Ht - Hl
                    if Hl < cfg.min_child_weight or Hr < cfg.min_child_weight:
                        continue
                    gain = 0.5 * (
                        Gl**2 / (Hl + cfg.lambda_)
                        + Gr**2 / (Hr + cfg.lambda_)
                        - parent
                    ) - cfg.gamma
                    if gain > best_gain:
                        best_gain, best = gain, (f, b)
            if best is not None:
                level_feat[node], level_thr[node] = best
        # route everyone (masked included), matching the JAX builder
        nf = level_feat[assign]
        nt = level_thr[assign]
        fv = binned[np.arange(n), np.clip(nf, 0, None)]
        go_right = (nf >= 0) & (fv > nt)
        assign = assign * 2 + go_right.astype(np.int32)
        feats.append(level_feat)
        thrs.append(level_thr)
    leaf_w = np.zeros(cfg.num_leaves, np.float64)
    for leaf in range(cfg.num_leaves):
        in_leaf = (assign == leaf) & (w > 0)
        if in_leaf.any():
            leaf_w[leaf] = -g[in_leaf].sum() / (h[in_leaf].sum() + cfg.lambda_)
    return np.concatenate(feats), np.concatenate(thrs), leaf_w, assign


@pytest.mark.parametrize("max_depth", [1, 2, 3])
@pytest.mark.parametrize("seed", [0, 1])
def test_tree_matches_numpy_oracle(max_depth, seed):
    # The numpy oracle accumulates every node's histogram directly, so the
    # JAX side must run the direct pipeline too (hist_subtraction now
    # defaults ON; sibling derivation is only tolerance-equivalent and has
    # its own parity suite in test_subtraction.py) — this keeps bit-exact
    # oracle coverage on the reference path.
    rng = np.random.default_rng(seed)
    n, d, B = 300, 6, 8
    cfg = TreeConfig(max_depth=max_depth, num_bins=B, lambda_=1.0,
                     hist_subtraction=False)
    binned = rng.integers(0, B, (n, d)).astype(np.int32)
    g = rng.normal(size=n).astype(np.float64)
    h = rng.random(n).astype(np.float64) + 0.1
    w = (rng.random(n) < 0.8).astype(np.float64)
    fmask = rng.random(d) < 0.9

    ref_f, ref_t, ref_w, ref_assign = numpy_build_tree(binned, g, h, w, fmask, cfg)

    tr, assign = tree.build_tree(
        jnp.asarray(binned), jnp.asarray(g, jnp.float32), jnp.asarray(h, jnp.float32),
        jnp.asarray(w, jnp.float32), jnp.asarray(fmask), cfg,
    )
    np.testing.assert_array_equal(np.asarray(tr.feature), ref_f)
    np.testing.assert_array_equal(np.asarray(tr.threshold), ref_t)
    np.testing.assert_allclose(np.asarray(tr.leaf_weight), ref_w, rtol=2e-4, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(assign), ref_assign)


def test_predict_tree_consistent_with_build_routing():
    rng = np.random.default_rng(3)
    n, d, B = 500, 5, 16
    cfg = TreeConfig(max_depth=3, num_bins=B)
    binned = jnp.asarray(rng.integers(0, B, (n, d)), jnp.int32)
    g = jnp.asarray(rng.normal(size=n), jnp.float32)
    h = jnp.ones(n, jnp.float32)
    tr, assign = tree.build_tree(
        binned, g, h, jnp.ones(n, jnp.float32), jnp.ones(d, bool), cfg
    )
    pred = tree.predict_tree(tr, binned, cfg.max_depth)
    np.testing.assert_allclose(
        np.asarray(pred), np.asarray(tr.leaf_weight)[np.asarray(assign)]
    )


def test_chosen_split_is_argmax_over_enumeration():
    """The gain of the selected split must dominate every enumerated candidate."""
    rng = np.random.default_rng(4)
    n, d, B = 400, 4, 8
    cfg = TreeConfig(max_depth=1, num_bins=B)
    binned = jnp.asarray(rng.integers(0, B, (n, d)), jnp.int32)
    g = jnp.asarray(rng.normal(size=n), jnp.float32)
    h = jnp.asarray(rng.random(n) + 0.1, jnp.float32)
    w = jnp.ones(n, jnp.float32)
    hist = compute_histogram(binned, g, h, w, jnp.zeros(n, jnp.int32), 1, B)
    decision = split.choose_splits(hist, jnp.ones(d, bool), cfg)
    gains = split.split_gains(hist, cfg)
    assert float(decision.gain[0]) == pytest.approx(float(jnp.max(gains)), rel=1e-6)


def test_unsplittable_node_routes_all_left():
    """Constant features -> no split -> all samples land in leaf 0."""
    n, d, B = 64, 3, 8
    cfg = TreeConfig(max_depth=2, num_bins=B)
    binned = jnp.zeros((n, d), jnp.int32)
    g = jnp.asarray(np.random.default_rng(0).normal(size=n), jnp.float32)
    tr, assign = tree.build_tree(
        binned, g, jnp.ones(n, jnp.float32), jnp.ones(n, jnp.float32),
        jnp.ones(d, bool), cfg,
    )
    assert np.all(np.asarray(tr.feature) == -1)
    assert np.all(np.asarray(assign) == 0)
    # the single populated leaf carries the global weight
    expected = -float(jnp.sum(g)) / (n + cfg.lambda_)
    assert float(tr.leaf_weight[0]) == pytest.approx(expected, rel=1e-5)


def test_forest_mean_combines_trees():
    rng = np.random.default_rng(5)
    n, d, B = 256, 4, 8
    cfg = TreeConfig(max_depth=2, num_bins=B)
    binned = jnp.asarray(rng.integers(0, B, (n, d)), jnp.int32)
    g = jnp.asarray(rng.normal(size=n), jnp.float32)
    h = jnp.ones(n, jnp.float32)
    smask, fmask = forest.sample_masks(jax.random.PRNGKey(0), n, d, 3, 0.7, 1.0)
    trees, train_pred = forest.build_forest(binned, g, h, smask, fmask, cfg)
    per_tree = jax.vmap(lambda t: tree.predict_tree(t, binned, cfg.max_depth))(trees)
    np.testing.assert_allclose(
        np.asarray(train_pred), np.asarray(per_tree.mean(0)), rtol=1e-5, atol=1e-6
    )


def test_sample_masks_exact_counts():
    smask, fmask = forest.sample_masks(jax.random.PRNGKey(1), 1000, 10, 8, 0.3, 0.5)
    assert smask.shape == (8, 1000) and fmask.shape == (8, 10)
    np.testing.assert_array_equal(np.asarray(smask.sum(1)), np.full(8, 300.0))
    np.testing.assert_array_equal(np.asarray(fmask.sum(1)), np.full(8, 5))


# ----------------------------------------------------------------------------
# Round routing: the row-compare form against the per-row gather form.
# ----------------------------------------------------------------------------
def per_row_gather_route(binned, idx, feature, threshold):
    """The element-wise oracle: each (tree, row) gathers its node's feature,
    threshold and bin."""
    f = jnp.take_along_axis(feature, idx, axis=1)
    t = jnp.take_along_axis(threshold, idx, axis=1)
    rows = jnp.arange(binned.shape[0])
    fv = binned[rows[None, :], jnp.clip(f, 0, None)]
    return idx * 2 + ((f >= 0) & (fv > t)).astype(jnp.int32)


def _route_case(T, width, d, B=32, n=997, seed=0):
    """Random level: about a third of the nodes unsplit (-1 / B), and the
    split nodes' thresholds include both ends, 0 and B - 1."""
    rng = np.random.default_rng(seed)
    binned = rng.integers(0, B, (n, d))
    binned[: n // 8] = B - 1       # some rows at the top bin
    binned[n // 8: n // 4] = 0     # and some at the bottom
    feature = rng.integers(0, d, (T, width))
    threshold = rng.integers(0, B, (T, width))
    threshold.flat[::2] = rng.choice([0, B - 1], threshold.flat[::2].shape)
    unsplit = rng.random((T, width)) < 1 / 3
    feature[unsplit], threshold[unsplit] = -1, B
    idx = rng.integers(0, width, (T, n))
    decision = split.SplitDecision(
        feature=jnp.asarray(feature, jnp.int32),
        threshold=jnp.asarray(threshold, jnp.int32),
        gain=jnp.zeros((T, width), jnp.float32),
    )
    return jnp.asarray(binned, jnp.int32), jnp.asarray(idx, jnp.int32), decision


@pytest.mark.parametrize("d", [5, 10, 23])
@pytest.mark.parametrize("width", [1, 2, 4, 64])
@pytest.mark.parametrize("T", [1, 2, 5])
def test_row_compare_routing_equals_per_row_gather(T, width, d):
    binned, idx, dec = _route_case(T, width, d, seed=T * 1000 + width * 10 + d)
    got = jax.jit(tree.route_local_round)(binned, idx, dec)
    want = per_row_gather_route(binned, idx, dec.feature, dec.threshold)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("width", [1, 4])
def test_route_level_is_counted_as_rows(width):
    """Each level traced into a round route counts once in
    ``fedgbf_route_levels_total{form="rows"}``; a repeat call of the
    compiled program counts nothing."""
    from repro.obs import compiles

    binned, idx, dec = _route_case(3, width, 7, seed=width)
    counter = compiles.install().route_levels
    before = counter.value
    route = jax.jit(tree.route_local_round)
    got = route(binned, idx, dec)
    route(binned, idx, dec)
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(per_row_gather_route(binned, idx, dec.feature,
                                        dec.threshold)),
    )
    assert counter.value - before == 1
