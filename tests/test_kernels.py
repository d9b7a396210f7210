"""Pallas kernel validation: shape/dtype sweeps against the pure-jnp oracle,
executed with interpret=True on CPU (the kernel body itself runs)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.histogram import ref
from repro.kernels.histogram.ops import compute_histogram_pallas


def _random_case(rng, n, d, B, nodes, g_dtype):
    binned = jnp.asarray(rng.integers(0, B, (n, d)), jnp.int32)
    g = jnp.asarray(rng.normal(size=n), g_dtype)
    h = jnp.asarray(rng.random(n) + 0.05, g_dtype)
    w = jnp.asarray(rng.integers(0, 2, n), g_dtype)
    assign = jnp.asarray(rng.integers(0, nodes, n), jnp.int32)
    return binned, g, h, w, assign


# Sweep: tile-divisible and ragged sample counts, feature counts around the
# feat_block boundary, bin counts, frontier widths incl. the non-128 NB case.
@pytest.mark.parametrize(
    "n,d,B,nodes",
    [
        (512, 8, 32, 1),       # exactly one tile, one feature block
        (1000, 10, 32, 4),     # ragged n and d (the paper's dataset shapes)
        (700, 23, 32, 4),      # default-credit width
        (256, 5, 16, 2),       # NB = 32 << 128 lane pad
        (2048, 3, 64, 8),      # NB = 512, deep frontier
        (130, 1, 8, 1),        # degenerate single feature (leaf-stats shape)
        (513, 9, 32, 2),       # off-by-one over the tile boundary
    ],
)
def test_histogram_kernel_matches_ref(n, d, B, nodes):
    rng = np.random.default_rng(n + d + B + nodes)
    binned, g, h, w, assign = _random_case(rng, n, d, B, nodes, jnp.float32)
    out = compute_histogram_pallas(binned, g, h, w, assign, nodes, B)
    expected = ref.histogram_ref(binned, g, h, w, assign, nodes, B)
    assert out.shape == (nodes, d, B, 3)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expected), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_histogram_kernel_dtypes(dtype):
    """bf16 inputs accumulate in f32 inside the kernel (preferred_element_type)."""
    rng = np.random.default_rng(99)
    binned, g, h, w, assign = _random_case(rng, 600, 7, 32, 4, dtype)
    out = compute_histogram_pallas(binned, g, h, w, assign, 4, 32)
    expected = ref.histogram_ref(
        binned, g.astype(jnp.float32), h.astype(jnp.float32),
        w.astype(jnp.float32), assign, 4, 32,
    )
    assert out.dtype == jnp.float32
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), rtol=tol, atol=tol)


@pytest.mark.parametrize("tile_n,feat_block", [(256, 4), (512, 8), (1024, 16)])
def test_histogram_kernel_tilings(tile_n, feat_block):
    """Block-shape sweep: result must be invariant to the BlockSpec tiling."""
    rng = np.random.default_rng(7)
    binned, g, h, w, assign = _random_case(rng, 900, 11, 32, 2, jnp.float32)
    out = compute_histogram_pallas(
        binned, g, h, w, assign, 2, 32, tile_n=tile_n, feat_block=feat_block
    )
    expected = ref.histogram_ref(binned, g, h, w, assign, 2, 32)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expected), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize(
    "n,d,B,nodes",
    [
        (512, 8, 32, 1),       # exactly one tile, one feature block
        (1000, 10, 32, 4),     # ragged n and d
        (700, 23, 32, 4),      # default-credit width
        (256, 5, 16, 2),       # NB = 32 << 128 lane pad
        (130, 1, 8, 1),        # degenerate single feature (leaf-stats shape)
        (513, 9, 32, 2),       # off-by-one over the tile boundary
    ],
)
def test_fused_train_histogram_kernel_matches_ref(n, d, B, nodes):
    """The kernel (in-kernel id + stats staging) agrees with the oracle on
    a second seed of the sweep."""
    rng = np.random.default_rng(1000 + n + d + B + nodes)
    binned, g, h, w, assign = _random_case(rng, n, d, B, nodes, jnp.float32)
    out = compute_histogram_pallas(binned, g, h, w, assign, nodes, B)
    expected = ref.histogram_ref(binned, g, h, w, assign, nodes, B)
    assert out.shape == (nodes, d, B, 3)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expected), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("tile_n,feat_block", [(256, 4), (512, 8)])
def test_fused_train_histogram_kernel_tilings(tile_n, feat_block):
    rng = np.random.default_rng(17)
    binned, g, h, w, assign = _random_case(rng, 900, 11, 32, 2, jnp.float32)
    out = compute_histogram_pallas(
        binned, g, h, w, assign, 2, 32, tile_n=tile_n, feat_block=feat_block
    )
    expected = ref.histogram_ref(binned, g, h, w, assign, 2, 32)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expected), rtol=1e-5, atol=1e-5
    )


def test_fused_kernel_vmaps_over_trees():
    """The forest layer vmaps the histogram over per-tree (weight, assign) —
    the fused kernel must batch exactly like the reference."""
    rng = np.random.default_rng(23)
    n, d, B, nodes, T = 600, 7, 16, 4, 3
    binned = jnp.asarray(rng.integers(0, B, (n, d)), jnp.int32)
    g = jnp.asarray(rng.normal(size=n), jnp.float32)
    h = jnp.asarray(rng.random(n) + 0.05, jnp.float32)
    w = jnp.asarray(rng.integers(0, 2, (T, n)), jnp.float32)
    assign = jnp.asarray(rng.integers(0, nodes, (T, n)), jnp.int32)
    out = jax.vmap(
        lambda wt, at: compute_histogram_pallas(
            binned, g, h, wt, at, nodes, B)
    )(w, assign)
    expected = jax.vmap(
        lambda wt, at: ref.histogram_ref(binned, g, h, wt, at, nodes, B)
    )(w, assign)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expected), rtol=1e-5, atol=1e-5
    )


def test_onehot_identity_matches_segment_sum():
    """The algebraic identity behind the kernel (DESIGN.md §2), in plain jnp."""
    rng = np.random.default_rng(11)
    binned, g, h, w, assign = _random_case(rng, 400, 6, 16, 4, jnp.float32)
    a = ref.histogram_ref(binned, g, h, w, assign, 4, 16)
    b = ref.compute_histogram_onehot(binned, g, h, w, assign, 4, 16)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)


def test_kernel_inside_tree_builder():
    """End-to-end: trees built with the Pallas histogram == segment-sum trees.

    Only the per-tree provider rides an ad-hoc ``TreeBackend``, so
    ``build_round`` lifts it over the tree axis itself (vmap of the
    kernel), unlike ``local-pallas`` whose round providers put the tree on
    the kernel grid."""
    from repro.core import tree
    from repro.core.backend import BackendDescriptor, TreeBackend
    from repro.core.histogram import histogram_dispatch
    from repro.core.types import TreeConfig

    rng = np.random.default_rng(21)
    n, d, B = 800, 10, 32
    cfg = TreeConfig(max_depth=3, num_bins=B)
    binned = jnp.asarray(rng.integers(0, B, (n, d)), jnp.int32)
    g = jnp.asarray(rng.normal(size=n), jnp.float32)
    h = jnp.asarray(rng.random(n) + 0.1, jnp.float32)
    w = jnp.ones(n, jnp.float32)
    fm = jnp.ones(d, bool)

    bk = TreeBackend(
        BackendDescriptor(impl="adhoc-pallas-per-tree", histogram_impl="pallas"),
        histogram_fn=histogram_dispatch("pallas"),
    )
    t_ref, a_ref = tree.build_tree(binned, g, h, w, fm, cfg)
    t_pal, a_pal = tree.build_tree(binned, g, h, w, fm, cfg, backend=bk)
    np.testing.assert_array_equal(np.asarray(t_ref.feature), np.asarray(t_pal.feature))
    np.testing.assert_array_equal(
        np.asarray(t_ref.threshold), np.asarray(t_pal.threshold)
    )
    np.testing.assert_allclose(
        np.asarray(t_ref.leaf_weight), np.asarray(t_pal.leaf_weight),
        rtol=1e-5, atol=1e-6,
    )
    np.testing.assert_array_equal(np.asarray(a_ref), np.asarray(a_pal))


# ---------------------------------------------------------------------------
# round (tree-grid) kernel
# ---------------------------------------------------------------------------
from repro.kernels.histogram.ops import (  # noqa: E402
    compute_round_histogram_pallas,
    compute_round_histogram_pallas_child,
)


@pytest.mark.parametrize(
    "n,d,B,nodes,T",
    [
        (512, 8, 32, 1, 1),    # T = 1 degenerates to the per-tree kernel
        (700, 9, 16, 4, 3),    # ragged n/d, multi-tree round
        (513, 5, 8, 2, 5),     # off-by-one tile boundary, paper-width round
    ],
)
def test_round_kernel_matches_round_ref(n, d, B, nodes, T):
    """The tree-grid kernel (one launch, tree axis on the grid) agrees with
    the round-native segment reference for every tree of the round."""
    from repro.core.histogram import compute_round_histogram

    rng = np.random.default_rng(n + d + T)
    binned = jnp.asarray(rng.integers(0, B, (n, d)), jnp.int32)
    g = jnp.asarray(rng.normal(size=n), jnp.float32)
    h = jnp.asarray(rng.random(n) + 0.05, jnp.float32)
    w = jnp.asarray(rng.integers(0, 2, (T, n)), jnp.float32)
    assign = jnp.asarray(rng.integers(0, nodes, (T, n)), jnp.int32)
    out = compute_round_histogram_pallas(binned, g, h, w, assign, nodes, B)
    ref = compute_round_histogram(binned, g, h, w, assign, nodes, B)
    assert out.shape == (T, nodes, d, B, 3)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5
    )


def test_round_child_kernel_matches_adapted_ref():
    """The tree-grid child kernel (in-kernel left-mask + parent ids) agrees
    with the generic ``as_round_child_fn`` adaptation."""
    from repro.core.histogram import as_round_child_fn, compute_round_histogram

    rng = np.random.default_rng(42)
    n, d, B, parents, T = 700, 9, 16, 4, 3
    binned = jnp.asarray(rng.integers(0, B, (n, d)), jnp.int32)
    g = jnp.asarray(rng.normal(size=n), jnp.float32)
    h = jnp.asarray(rng.random(n) + 0.05, jnp.float32)
    w = jnp.asarray(rng.integers(0, 2, (T, n)), jnp.float32)
    assign = jnp.asarray(rng.integers(0, 2 * parents, (T, n)), jnp.int32)
    out = compute_round_histogram_pallas_child(
        binned, g, h, w, assign, parents, B
    )
    ref = as_round_child_fn(compute_round_histogram)(
        binned, g, h, w, assign, parents, B
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5
    )


# ---------------------------------------------------------------------------
# wide-frontier form (nodes on the MXU's rows, node-block grid axis)
# ---------------------------------------------------------------------------
from repro.kernels.histogram import ops as _hist_ops  # noqa: E402


def _onehot_round_ref(binned, g, h, w, assign, nodes, B, child):
    """``core.histogram``'s one-hot reference per tree, with the child
    pipeline's left mask and halved ids applied outside it."""
    from repro.core.histogram import compute_histogram_onehot

    if child:
        w = w * (1 - assign % 2).astype(w.dtype)
        assign = assign // 2
    return jax.vmap(lambda wt, at: compute_histogram_onehot(
        binned, g, h, wt, at, nodes, B))(w, assign)


@pytest.mark.parametrize("child", [False, True])
@pytest.mark.parametrize("B", [32, 256])
@pytest.mark.parametrize("nodes,T", [(1, 1), (8, 3), (64, 1), (64, 3)])
def test_nodes_form_matches_onehot_ref(nodes, T, B, child):
    """The wide-frontier form against the one-hot reference at frontier
    widths inside one node block (1, 8) and at a full block (64), plain
    and child, with one tree and with several."""
    rng = np.random.default_rng(nodes * 7 + T + B + child)
    n, d = 600, 5
    binned = jnp.asarray(rng.integers(0, B, (n, d)), jnp.int32)
    g = jnp.asarray(rng.normal(size=n), jnp.float32)
    h = jnp.asarray(rng.random(n) + 0.05, jnp.float32)
    w = jnp.asarray(rng.integers(0, 2, (T, n)), jnp.float32)
    assign = jnp.asarray(
        rng.integers(0, (2 if child else 1) * nodes, (T, n)), jnp.int32)
    out = _hist_ops._round_histogram(
        binned, g, h, w, assign, nodes, B, form="nodes", tile_n=512,
        feat_block=8, child=child)
    ref = _onehot_round_ref(binned, g, h, w, assign, nodes, B, child)
    assert out.shape == (T, nodes, d, B, 3)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("nodes,child", [(9, False), (70, True)])
def test_nodes_form_k_channels_and_several_node_blocks(nodes, child):
    """K = 3 gradient channels over T 2 trees (14 rows a node, 16 nodes a
    block), so 9 nodes fit one block and 70 parents span five, the last
    one partly padding."""
    rng = np.random.default_rng(nodes)
    n, d, B, T, K = 700, 3, 16, 2, 3
    binned = jnp.asarray(rng.integers(0, B, (n, d)), jnp.int32)
    g = jnp.asarray(rng.normal(size=(n, K)), jnp.float32)
    h = jnp.asarray(rng.random((n, K)) + 0.05, jnp.float32)
    w = jnp.asarray(rng.integers(0, 2, (T, n)), jnp.float32)
    assign = jnp.asarray(
        rng.integers(0, (2 if child else 1) * nodes, (T, n)), jnp.int32)
    out = _hist_ops._round_histogram(
        binned, g, h, w, assign, nodes, B, form="nodes", tile_n=256,
        feat_block=8, child=child)
    ref = _onehot_round_ref(binned, g, h, w, assign, nodes, B, child)
    assert out.shape == (T, nodes, d, B, 2 * K + 1)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5
    )


def test_bf16_split_is_exact_and_its_sums_match_highest():
    """The wide-frontier form's three bf16 parts add back to each float32
    operand exactly, and its one-pass sums match the same contraction at
    ``Precision.HIGHEST`` to float32 reassociation."""
    from repro.kernels.histogram.train_histogram import split_bf16

    rng = np.random.default_rng(31)
    x = jnp.asarray(np.concatenate([
        rng.normal(size=4000), rng.normal(size=4000) * 1e-3,
        rng.random(4000) * 0.25, np.ldexp(rng.random(200), -60),
        np.ldexp(rng.random(200), 60), [0.0, 1.0, -1.0]]), jnp.float32)
    hi, mid, lo = split_bf16(x)
    assert hi.dtype == mid.dtype == lo.dtype == jnp.bfloat16
    back = (hi.astype(jnp.float32) + mid.astype(jnp.float32)
            + lo.astype(jnp.float32))
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x))

    n, d, B, nodes, T = 900, 4, 256, 8, 3
    binned = jnp.asarray(rng.integers(0, B, (n, d)), jnp.int32)
    g = jnp.asarray(rng.normal(size=n), jnp.float32)
    h = jnp.asarray(rng.random(n) * 0.25, jnp.float32)
    w = jnp.asarray(rng.integers(0, 2, (T, n)), jnp.float32)
    assign = jnp.asarray(rng.integers(0, nodes, (T, n)), jnp.int32)
    out = _hist_ops._round_histogram(
        binned, g, h, w, assign, nodes, B, form="nodes", tile_n=512,
        feat_block=8, child=False)
    with jax.default_matmul_precision("highest"):
        ref = _onehot_round_ref(binned, g, h, w, assign, nodes, B, False)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-6, atol=1e-6
    )


@pytest.mark.parametrize("nodes,B", [(1, 32), (2, 32), (4, 32), (1, 16)])
def test_credit_widths_stay_on_the_flat_kernel(nodes, B):
    """Every level width of the depth-3, B-32 credit cells (nodes 1/2/4,
    child parents 1/2) keeps today's flat kernel."""
    assert _hist_ops.histogram_form(nodes, B) == "flat"


@pytest.mark.parametrize("nodes", [1, 2, 8, 64, 128])
def test_wide_levels_take_the_nodes_form(nodes):
    """A 256-bin level is wider than the flat kernel's limit at any
    frontier, so every depth-8 HIGGS level takes the nodes form."""
    assert nodes * 256 > _hist_ops.FLAT_MAX_LANES
    assert _hist_ops.histogram_form(nodes, 256) == "nodes"


def test_hist_levels_counter_counts_each_call_by_form():
    """``fedgbf_hist_levels_total{form=...}`` counts a provider call at
    trace time, by the form its width chose, repeat calls included."""
    from repro.obs import compiles

    levels = compiles.install().hist_levels
    rng = np.random.default_rng(5)
    n, d = 300, 3
    binned = jnp.asarray(rng.integers(0, 256, (n, d)), jnp.int32)
    g = jnp.asarray(rng.normal(size=n), jnp.float32)
    h = jnp.ones(n, jnp.float32)
    w = jnp.ones((1, n), jnp.float32)
    a = jnp.zeros((1, n), jnp.int32)
    before = {f: c.value for f, c in levels.items()}
    compute_round_histogram_pallas(binned, g, h, w, a, 2, 32)
    compute_round_histogram_pallas(binned, g, h, w, a, 2, 32)
    compute_round_histogram_pallas(binned, g, h, w, a, 1, 256)
    assert levels["flat"].value - before["flat"] == 2
    assert levels["nodes"].value - before["nodes"] == 1
    snap = compiles.installed().snapshot()
    assert 'fedgbf_hist_levels_total{form="nodes"}' in snap


# ---------------------------------------------------------------------------
# ensemble_predict kernel
# ---------------------------------------------------------------------------
import repro.core.forest as _forest
import repro.core.tree as _tree
from repro.core.types import TreeConfig as _TreeConfig
from repro.kernels.ensemble_predict.ops import predict_forest_pallas


@pytest.mark.parametrize(
    "n,d,B,D,ntrees",
    [
        (500, 10, 16, 3, 5),    # paper-shaped
        (300, 23, 32, 2, 3),    # wide features, shallow
        (257, 5, 8, 4, 2),      # ragged tile boundary, deeper
        (64, 3, 8, 1, 1),       # stumps, single tree
    ],
)
def test_predict_kernel_matches_traversal(n, d, B, D, ntrees):
    rng = np.random.default_rng(n + D)
    cfg = _TreeConfig(max_depth=D, num_bins=B)
    binned = jnp.asarray(rng.integers(0, B, (n, d)), jnp.int32)
    g = jnp.asarray(rng.normal(size=n), jnp.float32)
    h = jnp.asarray(rng.random(n) + 0.1, jnp.float32)
    smask, fmask = _forest.sample_masks(
        jax.random.PRNGKey(1), n, d, ntrees, 0.8, 0.9
    )
    trees, _ = _forest.build_forest(binned, g, h, smask, fmask, cfg)
    ref_out = _tree.predict_forest(trees, binned, D)
    out = predict_forest_pallas(trees, binned, D)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref_out), rtol=1e-5, atol=1e-6
    )


@pytest.mark.parametrize("tile_n", [128, 256, 512])
def test_predict_kernel_tiling_invariance(tile_n):
    rng = np.random.default_rng(9)
    cfg = _TreeConfig(max_depth=3, num_bins=16)
    n, d = 700, 8
    binned = jnp.asarray(rng.integers(0, 16, (n, d)), jnp.int32)
    g = jnp.asarray(rng.normal(size=n), jnp.float32)
    h = jnp.ones(n, jnp.float32)
    smask, fmask = _forest.sample_masks(jax.random.PRNGKey(2), n, d, 4, 1.0, 1.0)
    trees, _ = _forest.build_forest(binned, g, h, smask, fmask, cfg)
    ref_out = _tree.predict_forest(trees, binned, 3)
    out = predict_forest_pallas(trees, binned, 3, tile_n=tile_n)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref_out), rtol=1e-5, atol=1e-6
    )
