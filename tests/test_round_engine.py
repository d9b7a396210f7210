"""Round-native forest engine (core/tree.py::build_round, DESIGN.md §9).

The contract lattice:

* ROUND == PER-TREE — ``build_round`` is bit-identical to vmapping the
  T = 1 special case (``build_tree``) over the tree axis, for every local
  registry backend, subtraction on and off (the federated twin of this
  check lives in federation/selftest.py);
* COMPACTION — with a ``max_active_nodes`` budget the trees stay
  bit-identical to the uncompacted build whenever the live frontier fits
  the budget, and remain structurally consistent (routing == prediction)
  when the budget truncates;
* SHARED ROOT — ``shared − delta`` equals the direct per-tree root
  histogram (float-reassociation tolerance; the hypothesis-property twin
  lives in tests/test_properties.py), end-to-end training stays in the
  §5/§6 tolerance class, and the level-0 row volume drops from ``T·n`` to
  ``n + T·rdr`` (asserted through the trace-time pass meter).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import boosting, forest, histogram as hist_mod, tree
from repro.core.backend import get_backend
from repro.core.types import FedGBFConfig, TreeConfig


def _case(seed=0, n=700, d=7, B=16, T=4, rho=0.8):
    rng = np.random.default_rng(seed)
    binned = jnp.asarray(rng.integers(0, B, (n, d)), jnp.int32)
    g = jnp.asarray(rng.normal(size=n), jnp.float32)
    h = jnp.asarray(rng.random(n) + 0.1, jnp.float32)
    smask, fmask = forest.sample_masks(
        jax.random.PRNGKey(seed + 1), n, d, T, rho, 0.9
    )
    return binned, g, h, smask, fmask


def _assert_trees_equal(a, b, leaf_tol=0.0):
    np.testing.assert_array_equal(np.asarray(a.feature), np.asarray(b.feature))
    np.testing.assert_array_equal(
        np.asarray(a.threshold), np.asarray(b.threshold)
    )
    if leaf_tol:
        np.testing.assert_allclose(
            np.asarray(a.leaf_weight), np.asarray(b.leaf_weight),
            rtol=leaf_tol, atol=leaf_tol,
        )
    else:
        np.testing.assert_array_equal(
            np.asarray(a.leaf_weight), np.asarray(b.leaf_weight)
        )


# ---------------------------------------------------------------------------
# Round == per-tree vmap
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["local", "local-pallas"])
@pytest.mark.parametrize("subtraction", [False, True])
def test_build_round_bit_identical_to_per_tree_vmap(backend, subtraction):
    """The round engine must reproduce the per-tree path bit-for-bit on the
    non-lossy backends (acceptance bar of the round refactor)."""
    binned, g, h, smask, fmask = _case()
    cfg = TreeConfig(max_depth=3, num_bins=16, hist_subtraction=subtraction)
    bk = get_backend(backend)
    trees_r, assign_r = tree.build_round(
        binned, g, h, smask, fmask, cfg, backend=bk
    )
    trees_v, assign_v = jax.vmap(
        lambda sm, fm: tree.build_tree(binned, g, h, sm, fm, cfg, backend=bk)
    )(smask, fmask)
    _assert_trees_equal(trees_r, trees_v, leaf_tol=1e-6)
    np.testing.assert_array_equal(np.asarray(assign_r), np.asarray(assign_v))


@pytest.mark.parametrize("max_depth", [6, 8])
def test_local_pallas_deep_256_bin_round_matches_local(max_depth):
    """Depth 6-8 at B 256 (the HIGGS shape's levels, every one wider than
    the flat kernel's limit): the Pallas round providers choose the same
    features and thresholds as the segment-sum ``local`` path, with leaves
    within float32 tolerance.

    g and h are multiples of 1/64, so every histogram sum is exact in
    float32 in any order: the two paths' histograms are equal, and a split
    can differ only by a fault, not by a near-tie rounded two ways."""
    binned, g, h, smask, fmask = _case(seed=3, n=900, d=5, B=256, T=2,
                                       rho=0.9)
    rng = np.random.default_rng(4)
    g = jnp.asarray(rng.integers(-64, 65, g.shape) / 64.0, jnp.float32)
    h = jnp.asarray(rng.integers(1, 65, h.shape) / 64.0, jnp.float32)
    cfg = TreeConfig(max_depth=max_depth, num_bins=256, hist_subtraction=True)
    trees_p, assign_p = tree.build_round(
        binned, g, h, smask, fmask, cfg, backend=get_backend("local-pallas"))
    trees_l, assign_l = tree.build_round(
        binned, g, h, smask, fmask, cfg, backend=get_backend("local"))
    assert int(np.sum(np.asarray(trees_l.feature) >= 0)) > 2 ** (max_depth - 1)
    _assert_trees_equal(trees_p, trees_l, leaf_tol=1e-5)
    np.testing.assert_array_equal(np.asarray(assign_p), np.asarray(assign_l))


def test_build_tree_is_t1_special_case():
    """``build_tree`` delegates to the round engine with a singleton tree
    axis — same arrays, no leading dim."""
    binned, g, h, smask, fmask = _case(T=1)
    cfg = TreeConfig(max_depth=3, num_bins=16)
    tr, assign = tree.build_tree(binned, g, h, smask[0], fmask[0], cfg)
    trees, assign_r = tree.build_round(binned, g, h, smask, fmask, cfg)
    assert tr.feature.shape == (cfg.num_internal,)
    np.testing.assert_array_equal(np.asarray(tr.feature),
                                  np.asarray(trees.feature[0]))
    np.testing.assert_array_equal(np.asarray(assign), np.asarray(assign_r[0]))


def test_forest_build_matches_round():
    """forest.build_forest rides the round engine: per-tree predictions are
    the leaf gathers of the round assignment."""
    binned, g, h, smask, fmask = _case()
    cfg = TreeConfig(max_depth=3, num_bins=16)
    trees, per_tree = forest.build_forest_per_tree(
        binned, g, h, smask, fmask, cfg
    )
    trees_r, assign_r = tree.build_round(binned, g, h, smask, fmask, cfg)
    _assert_trees_equal(trees, trees_r)
    np.testing.assert_array_equal(
        np.asarray(per_tree),
        np.asarray(jnp.take_along_axis(trees_r.leaf_weight, assign_r, axis=1)),
    )


# ---------------------------------------------------------------------------
# Frontier compaction (max_depth > 3)
# ---------------------------------------------------------------------------
def _live_counts(trees, assign, smask, max_depth):
    """Host-side live-node counts per level of an (uncompacted) build."""
    feat = np.asarray(trees.feature)
    T = feat.shape[0]
    counts = []
    for level in range(1, max_depth):
        width = 2 ** level
        off = width - 1
        parent = feat[:, (2 ** (level - 1) - 1):off]     # (T, width/2)
        parent_split = np.repeat(parent >= 0, 2, axis=1)  # (T, width)
        # recover the level assignment by walking the stored tree
        live = np.zeros((T, width), bool)
        for t in range(T):
            idx = np.zeros(assign.shape[1], np.int64)
            a = np.asarray(assign[t])
            # leaf assignment >> (max_depth - level) is the level-node id
            node = a >> (max_depth - level)
            w = np.asarray(smask[t]) > 0
            present = np.zeros(width, bool)
            present[np.unique(node[w])] = True
            live[t] = present & parent_split[t]
        counts.append(live.sum(axis=1).max())
    return counts


@pytest.mark.parametrize("max_depth", [4, 5])
@pytest.mark.parametrize("subtraction", [False, True])
def test_compaction_bit_identical_when_budget_fits(max_depth, subtraction):
    """With a budget covering the actual live frontier, the compacted build
    is bit-identical to the uncompacted one (dead-node masking provably
    changes nothing: empty nodes and no-split descendants cannot split)."""
    # gamma + min_child_weight prune weak splits so deep frontiers stay
    # sparse (live <= 4 on this seed, verified below)
    binned, g, h, smask, fmask = _case(seed=3, n=500)
    cfg = TreeConfig(max_depth=max_depth, num_bins=16, gamma=2.0,
                     min_child_weight=20.0, hist_subtraction=subtraction)
    trees_u, assign_u = tree.build_round(binned, g, h, smask, fmask, cfg)
    live_max = max(_live_counts(trees_u, assign_u, smask, max_depth))
    budget = int(max(2, live_max))
    assert budget < 2 ** (max_depth - 1), (
        "fixture drifted: frontier too dense for a meaningful budget"
    )
    cfg_b = dataclasses.replace(cfg, max_active_nodes=budget)
    trees_b, assign_b = tree.build_round(binned, g, h, smask, fmask, cfg_b)
    _assert_trees_equal(trees_u, trees_b)
    np.testing.assert_array_equal(np.asarray(assign_u), np.asarray(assign_b))


@pytest.mark.parametrize("budget", [2, 4])
def test_compaction_truncation_stays_consistent(budget):
    """A budget below the live frontier truncates (overflow nodes fall
    through unsplit) but the trees stay structurally valid: stored routing
    equals traversal, leaves carry the routed samples."""
    binned, g, h, smask, fmask = _case(seed=3)
    cfg = TreeConfig(max_depth=5, num_bins=16, max_active_nodes=budget)
    trees, assign = tree.build_round(binned, g, h, smask, fmask, cfg)
    per = jnp.take_along_axis(trees.leaf_weight, assign, axis=1)
    pred = tree.predict_trees(trees, binned, cfg.max_depth)
    np.testing.assert_allclose(np.asarray(per), np.asarray(pred))
    # the per-level split count never exceeds the budget
    feat = np.asarray(trees.feature)
    for level in range(5):
        off, width = 2 ** level - 1, 2 ** level
        split_nodes = (feat[:, off:off + width] >= 0).sum(axis=1)
        assert (split_nodes <= min(width, budget)).all()


def test_compaction_depth45_training_end_to_end():
    """Deep-tree training under compaction: both engines run and agree."""
    rng = np.random.default_rng(7)
    n, d = 900, 6
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = (x[:, 0] - 0.5 * x[:, 1] + rng.normal(0, 0.5, n) > 0).astype(np.float32)
    x, y = jnp.asarray(x), jnp.asarray(y)
    cfg = FedGBFConfig(
        rounds=3, n_trees_max=3, n_trees_min=2, rho_id_min=0.5,
        rho_id_max=0.8,
        tree=TreeConfig(max_depth=4, num_bins=16, max_active_nodes=4),
    )
    _, h_scan = boosting.train_fedgbf(x, y, cfg, jax.random.PRNGKey(0))
    _, h_loop = boosting.train_fedgbf(x, y, cfg, jax.random.PRNGKey(0),
                                      engine="loop")
    for a, b in zip(h_scan.train, h_loop.train):
        for k in a:
            assert abs(a[k] - b[k]) <= 1e-5, (k, a[k], b[k])


# ---------------------------------------------------------------------------
# Shared-root caching
# ---------------------------------------------------------------------------
def test_shared_root_delta_matches_direct_root_histogram():
    """``shared − delta(masked-out rows)`` == the direct per-tree root
    histogram, within float-reassociation tolerance."""
    binned, g, h, smask, _ = _case(rho=0.8)
    T, n = smask.shape
    rdr = int(n - np.asarray(smask).sum(axis=1).min())
    zeros = jnp.zeros((T, n), jnp.int32)
    direct = hist_mod.compute_round_histogram(binned, g, h, smask, zeros, 1, 16)
    delta = hist_mod.compute_round_histogram(
        binned, g, h, smask, zeros, 1, 16, root_delta_rows=rdr
    )
    np.testing.assert_allclose(
        np.asarray(delta), np.asarray(direct), rtol=1e-4, atol=1e-3
    )


def test_shared_root_level0_pass_volume():
    """The level-0 row volume drops from T·n to n + T·rdr: asserted through
    the trace-time pass meter (shape-determined, so the check is exact)."""
    binned, g, h, smask, fmask = _case()
    T, n = smask.shape
    cfg = TreeConfig(max_depth=3, num_bins=16)

    def probe(rdr):
        hist_mod.PASS_METER = []
        try:
            jax.eval_shape(
                lambda: tree.build_round(binned, g, h, smask, fmask, cfg,
                                         root_delta_rows=rdr)
            )
            return [e for e in hist_mod.PASS_METER]
        finally:
            hist_mod.PASS_METER = None

    direct = [e for e in probe(0) if e["tag"] == "round"]
    # level 0 is the first record: T trees over all n rows
    assert direct[0] == {"tag": "round", "rows": n, "trees": T}
    rdr = 140
    entries = probe(rdr)
    shared = [e for e in entries if e["tag"] == "round"][0]
    delta = [e for e in entries if e["tag"] == "root_delta"][0]
    assert shared == {"tag": "round", "rows": n, "trees": 1}
    assert delta == {"tag": "root_delta", "rows": rdr, "trees": T}
    # the crossover's win: n + T·rdr < T·n at rho >= 0.5
    assert n + T * rdr < T * n


def test_shared_root_training_tolerance_and_crossover():
    """End-to-end: shared_root training tracks the direct pipeline within
    the §5/§6 tolerance class; rounds below the rho crossover take the
    direct path (exercised via a mixed schedule)."""
    rng = np.random.default_rng(11)
    n, d = 1200, 6
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = (x[:, 0] - 0.5 * x[:, 1] + rng.normal(0, 0.5, n) > 0).astype(np.float32)
    x, y = jnp.asarray(x), jnp.asarray(y)
    base = FedGBFConfig(
        rounds=4, n_trees_max=3, n_trees_min=2,
        rho_id_min=0.3, rho_id_max=0.9,   # crosses the 0.5 threshold
        tree=TreeConfig(max_depth=3, num_bins=16),
    )
    shared = dataclasses.replace(
        base, tree=dataclasses.replace(base.tree, shared_root=True)
    )
    _, h_dir = boosting.train_fedgbf(x, y, base, jax.random.PRNGKey(0))
    m_shared, h_shared = boosting.train_fedgbf(x, y, shared,
                                               jax.random.PRNGKey(0))
    m_loop, h_loop = boosting.train_fedgbf(x, y, shared, jax.random.PRNGKey(0),
                                           engine="loop")
    for a, b in zip(h_shared.train, h_dir.train):
        for k in a:
            assert abs(a[k] - b[k]) <= 5e-3, (k, a[k], b[k])
    # scan == loop even when a constant-width segment spans the rho 0.5
    # crossover: segments additionally split at the eligibility boundary,
    # so every round makes the loop engine's exact delta-vs-direct choice,
    # and surplus (bucketed) buffer rows carry weight 0 — the engines'
    # trees are bit-identical, not merely close.
    for fs, fl in zip(m_shared.forests, m_loop.forests):
        np.testing.assert_array_equal(np.asarray(fs.feature),
                                      np.asarray(fl.feature))
    for a, b in zip(h_shared.train, h_loop.train):
        for k in a:
            assert abs(a[k] - b[k]) <= 1e-5, (k, a[k], b[k])


def test_root_delta_rows_crossover_rule():
    """The schedule-driven crossover: delta only at rho >= 0.5 and uniform
    sampling; GOSS always routes direct.  Buffer widths bucket to powers of
    two (surplus rows are weight-0 inert) so a dynamic rho schedule compiles
    O(log n) programs, not one per round."""
    tree_cfg = TreeConfig(shared_root=True)
    cfg = FedGBFConfig(tree=tree_cfg)
    assert boosting._root_delta_rows(cfg, 1000, 0.8) == 256  # 200 -> pow2
    assert boosting._root_delta_rows(cfg, 1000, 0.4) == 0
    assert boosting._root_delta_rows(cfg, 1000, 1.0) == 1  # minimal buffer
    goss = dataclasses.replace(cfg, sampling="goss")
    assert boosting._root_delta_rows(goss, 1000, 0.8) == 0
    off = FedGBFConfig(tree=TreeConfig())
    assert boosting._root_delta_rows(off, 1000, 0.8) == 0
    # distinct rho values collapse into few static widths
    widths = {boosting._root_delta_rows(cfg, 1000, r)
              for r in (0.6, 0.65, 0.7, 0.75, 0.8, 0.9)}
    assert widths == {512, 256, 128}
    assert boosting._delta_bucket(700, 1000) == 1000  # capped at n
