"""Communication-efficiency subsystem (federation/compress.py, DESIGN.md §5).

Single-device coverage of the codec, the GOSS masks, the wire model and the
measured-bytes reconciliation (on a 1-party mesh the full shard_map +
transport path runs on one CPU device); the multi-party strict/tolerance
equivalence checks live in federation/selftest.py (subprocess, forced
devices) via tests/test_federation.py.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import binning, forest, losses, split
from repro.core.types import FedGBFConfig, TreeConfig
from repro.federation import compress, protocol, vfl
from repro.launch.mesh import make_mesh


# ---------------------------------------------------------------------------
# Quantization codec
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("bits", [8, 16])
@pytest.mark.parametrize("stochastic", [True, False])
def test_quantize_roundtrip_error_bound(bits, stochastic):
    """|dequantize(quantize(x)) - x| <= scale per element (one rounding step),
    and exact zeros survive exactly (scale-1 guard)."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4, 6, 16, 2)) * 100.0, jnp.float32)
    x = x.at[0, 0].set(0.0)  # an all-zero (node, feature) slice
    q, scale = compress.quantize_stats(x, bits, jax.random.PRNGKey(1), stochastic)
    assert q.dtype == (jnp.int8 if bits == 8 else jnp.int16)
    assert scale.shape == (4, 6, 2)
    deq = compress.dequantize_stats(q, scale)
    err = np.abs(np.asarray(deq) - np.asarray(x))
    bound = np.asarray(scale)[:, :, None, :] * (1.0 if stochastic else 0.5)
    assert (err <= bound + 1e-6).all()
    np.testing.assert_array_equal(np.asarray(deq[0, 0]), 0.0)


def test_quantize_stochastic_is_unbiased():
    """Stochastic rounding is unbiased: averaging many independent roundings
    of the same value converges to the value."""
    x = jnp.full((1, 1, 8, 1), 3.1415926, jnp.float32)
    outs = []
    for s in range(200):
        q, scale = compress.quantize_stats(x, 8, jax.random.PRNGKey(s), True)
        outs.append(np.asarray(compress.dequantize_stats(q, scale)))
    mean = np.stack(outs).mean()
    # one rounding step is ~scale = 3.14/127 ~ 0.025; the mean over 200
    # draws must sit well inside it
    assert abs(mean - 3.1415926) < 0.005


def test_transport_spec_validation():
    with pytest.raises(ValueError, match="kind"):
        compress.TransportSpec(kind="zstd")
    with pytest.raises(ValueError, match="bits"):
        compress.TransportSpec(kind="quantized", bits=4)
    with pytest.raises(ValueError, match="k >= 1"):
        compress.TransportSpec(kind="topk", k=0)
    assert compress.Q8.tag == "q8" and compress.Q16.tag == "q16"
    assert compress.TOPK.tag == "topk" and compress.RAW.tag == "raw"


def test_transport_aggregation_mismatch_rejected():
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = TreeConfig(max_depth=2, num_bins=8)
    with pytest.raises(ValueError, match="does not apply"):
        vfl.make_vfl_backend(mesh, cfg, aggregation="histogram",
                             transport=compress.TOPK)
    with pytest.raises(ValueError, match="does not apply"):
        vfl.make_vfl_backend(mesh, cfg, aggregation="argmax",
                             transport=compress.Q8)


def test_named_backend_rejects_conflicting_transport_kwarg():
    """The registry name encodes the transport; a conflicting explicit
    transport= must error rather than silently ship a different format."""
    from repro.core import backend as backend_mod

    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = TreeConfig(max_depth=2, num_bins=8)
    with pytest.raises(ValueError, match="encodes transport"):
        backend_mod.get_backend("vfl-histogram-q8", mesh=mesh, tree=cfg,
                                transport=compress.Q16)
    # explicit None defers to the name; explicit on the plain name works
    bk = backend_mod.get_backend("vfl-histogram-q8", mesh=mesh, tree=cfg,
                                 transport=None)
    assert bk.descriptor.transport == "q8"
    bk = backend_mod.get_backend("vfl-histogram", mesh=mesh, tree=cfg,
                                 transport=compress.Q8)
    assert bk.descriptor.transport == "q8"


# ---------------------------------------------------------------------------
# Transport correctness on a 1-party mesh (full shard_map path, one device)
# ---------------------------------------------------------------------------
def _toy_forest_inputs(n=600, d=4, num_bins=16, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 2, n), jnp.float32)
    binned, _ = binning.fit_bin(x, num_bins)
    g, h = losses.grad_hess("logistic", y, jnp.zeros(n))
    smask, fmask = forest.sample_masks(jax.random.PRNGKey(7), n, d, 3, 0.8, 1.0)
    return binned, g, h, smask, fmask


def test_topk_bit_identical_to_centralized():
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = TreeConfig(max_depth=3, num_bins=16)
    binned, g, h, smask, fmask = _toy_forest_inputs()
    trees_c, _ = forest.build_forest(binned, g, h, smask, fmask, cfg)
    bk = vfl.make_vfl_backend(mesh, cfg, aggregation="argmax",
                              transport=compress.TOPK)
    with jax.set_mesh(mesh):
        trees_f, _ = bk.build_forest(binned, g, h, smask, fmask, cfg)
    np.testing.assert_array_equal(np.asarray(trees_c.feature),
                                  np.asarray(trees_f.feature))
    np.testing.assert_array_equal(np.asarray(trees_c.threshold),
                                  np.asarray(trees_f.threshold))


def test_quantized_backend_close_to_centralized():
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = TreeConfig(max_depth=3, num_bins=16)
    binned, g, h, smask, fmask = _toy_forest_inputs()
    trees_c, pred_c = forest.build_forest(binned, g, h, smask, fmask, cfg)
    bk = vfl.make_vfl_backend(mesh, cfg, aggregation="histogram",
                              transport=compress.Q16)
    with jax.set_mesh(mesh):
        trees_f, pred_f = bk.build_forest(binned, g, h, smask, fmask, cfg)
    # int16 quantization at toy scale: identical structure, close leaves
    np.testing.assert_array_equal(np.asarray(trees_c.feature),
                                  np.asarray(trees_f.feature))
    np.testing.assert_allclose(np.asarray(pred_c), np.asarray(pred_f),
                               rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# Measured bytes == predicted wire model
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("aggregation,transport", [
    ("histogram", None),
    ("histogram", compress.Q8),
    ("histogram", compress.Q16),
    ("argmax", None),
    ("argmax", compress.TOPK),
])
def test_probe_matches_wire_model(aggregation, transport):
    """Every collective's actual traced payload == the per-party wire-model
    formula, byte for byte (1-party mesh; multi-party in selftest.py)."""
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = TreeConfig(max_depth=3, num_bins=16)  # hist_subtraction default ON
    n, d = 500, 4
    per_tree, grad = compress.probe_tree_cost(
        mesh, cfg, aggregation=aggregation, transport=transport,
        n_samples=n, num_features=d,
    )
    wire = protocol.wire_party_tree_cost(n, d, cfg.num_bins, cfg.max_depth,
                                         aggregation, transport,
                                         cfg.hist_subtraction)
    expected = {k: v for k, v in wire.items() if v and k != "grad_broadcast"}
    assert per_tree == expected
    assert grad == n * 2 * 4


def test_ledger_reconciles_and_breaks_down():
    cfg = FedGBFConfig(rounds=3, n_trees_max=4, n_trees_min=2,
                       rho_id_min=0.2, rho_id_max=0.5)
    spec = protocol.ProtocolSpec(n_samples=400, party_dims=(3, 3),
                                 num_bins=16, max_depth=3)
    ledger = protocol.ProtocolLedger(spec=spec, cfg=cfg)
    per_tree = protocol.wire_party_tree_cost(400, 3, 16, 3, "histogram", None)
    per_tree = {k: v for k, v in per_tree.items() if v}
    ledger.record_run(per_tree, grad_per_round=400 * 2 * 4)
    assert ledger.matches()
    rec = ledger.reconcile()
    assert rec["total"]["measured"] == rec["total"]["predicted"] > 0
    # a deliberate mismatch is caught
    ledger.record_measured("histograms", 1)
    assert not ledger.matches()
    # per-mode totals let benchmarks diff aggregation modes directly
    bd = ledger.breakdown()
    assert set(bd["modes"]) == {"histogram", "histogram+sub", "argmax"}
    assert bd["modes"]["histogram"] > bd["modes"]["argmax"]
    # the subtraction pipeline's histogram-phase cut is visible in the
    # breakdown: 7 -> 4 node-histograms per depth-3 tree, exactly 1.75x
    hp = bd["hist_phase_by_mode"]
    assert hp["histogram"] / hp["histogram+sub"] == 7 / 4
    assert bd["modes"]["histogram"] > bd["modes"]["histogram+sub"]
    # and the paper-world Paillier model rides along
    assert bd["predicted_paillier"]["total"] > bd["modes"]["histogram"]


def test_wire_model_quantized_reduction_factor():
    """The q8 histogram-phase formula yields the >= 4x reduction the
    acceptance demands (5.33x at B = 32, channel scales included)."""
    raw = protocol.wire_party_tree_cost(1000, 8, 32, 3, "histogram", None)
    q8 = protocol.wire_party_tree_cost(1000, 8, 32, 3, "histogram", compress.Q8)
    assert raw["histograms"] / q8["histograms"] >= 4.0
    q16 = protocol.wire_party_tree_cost(1000, 8, 32, 3, "histogram", compress.Q16)
    assert raw["histograms"] / q16["histograms"] >= 2.0


def test_wire_model_compaction_active_width():
    """Frontier compaction (DESIGN.md §9): the wire model ships the static
    live-slot budget per level, not the 2^level frontier — at depth 5 with
    budget 4 the direct pipeline drops 31 -> 15 node-histograms per tree
    and the subtraction pipeline 16 -> 12 (left children at PARENT active
    width), composing in one expression."""
    full = protocol.wire_party_tree_cost(1000, 8, 32, 5, "histogram", None,
                                         hist_subtraction=False)
    comp = protocol.wire_party_tree_cost(1000, 8, 32, 5, "histogram", None,
                                         hist_subtraction=False,
                                         max_active_nodes=4)
    assert full["histograms"] / comp["histograms"] == 31 / 15
    sub = protocol.wire_party_tree_cost(1000, 8, 32, 5, "histogram", None,
                                        hist_subtraction=True)
    sub_comp = protocol.wire_party_tree_cost(1000, 8, 32, 5, "histogram",
                                             None, hist_subtraction=True,
                                             max_active_nodes=4)
    assert sub["histograms"] / sub_comp["histograms"] == 16 / 12
    # per-level profile: full root, parent-width left children, budget cap
    levels = protocol.wire_hist_level_bytes(8, 32, 5, None, True, 4)
    per_node = 32 * 3 * 4 * 8
    assert levels == [1 * per_node, 1 * per_node, 2 * per_node,
                      4 * per_node, 4 * per_node]


# ---------------------------------------------------------------------------
# GOSS masks
# ---------------------------------------------------------------------------
def test_goss_counts_edges():
    assert forest.goss_counts(100, 0.3, 0.5) == (15, 15)
    n_top, n_rand = forest.goss_counts(100, 0.01, 0.5)  # tiny budget
    assert n_top == 0 and n_rand == 1
    n_top, n_rand = forest.goss_counts(100, 1.0, 1.0)   # degenerate top-heavy
    assert n_top <= 99 and n_rand >= 1 and n_top + n_rand <= 100


def test_goss_masks_counts_weights_and_top_set():
    rng = np.random.default_rng(2)
    n, d = 500, 6
    g = jnp.asarray(rng.normal(size=n), jnp.float32)
    n_top, n_rand = forest.goss_counts(n, 0.3, 0.5)
    smask, fmask = forest.goss_masks(
        jax.random.PRNGKey(3), g, d, 4, n_top, n_rand, d_keep=4
    )
    sm = np.asarray(smask)
    amp = (n - n_top) / n_rand
    order = np.argsort(-np.abs(np.asarray(g)))
    for t in range(4):
        kept = sm[t] > 0
        assert (sm[t] == 1.0).sum() == n_top
        assert kept.sum() == n_top + n_rand
        np.testing.assert_allclose(sm[t][kept & (sm[t] != 1.0)], amp, rtol=1e-6)
    # the top-|g| set is deterministic and shared by all trees
    assert (sm[:, order[:n_top]] == 1.0).all()
    assert np.asarray(fmask).sum(axis=1).tolist() == [4] * 4


def test_goss_prefix_stable_and_fmask_matches_uniform():
    """fold_in key discipline: any subset of tree slots draws exactly the
    masks a full draw produces, and the feature masks equal the uniform
    path's draw for the same keys (same (sample, feature) key split)."""
    rng = np.random.default_rng(4)
    n, d = 300, 5
    g = jnp.asarray(rng.normal(size=n), jnp.float32)
    key = jax.random.PRNGKey(11)
    s5, f5 = forest.goss_masks(key, g, d, 5, 40, 50, d_keep=3)
    s2, f2 = forest.goss_masks(key, g, d, 2, 40, 50, d_keep=3)
    np.testing.assert_array_equal(np.asarray(s5[:2]), np.asarray(s2))
    np.testing.assert_array_equal(np.asarray(f5[:2]), np.asarray(f2))
    _, f_uniform = forest.sample_masks_counts(key, n, d, 5, 90, 3)
    np.testing.assert_array_equal(np.asarray(f5), np.asarray(f_uniform))


def test_goss_histogram_sums_unbiased():
    """The amplified weights keep the masked (g, h, count) sums unbiased:
    averaging over many keys recovers the full-data sums."""
    rng = np.random.default_rng(5)
    n = 400
    g = jnp.asarray(rng.normal(size=n), jnp.float32)
    n_top, n_rand = forest.goss_counts(n, 0.4, 0.5)
    keys = forest.fold_in_keys(jax.random.PRNGKey(0), jnp.arange(256))
    smask, _ = forest.goss_masks_from_keys(keys, g, 2, n_top, n_rand, 2)
    est = np.asarray(smask * g[None, :]).sum(axis=1)
    full = float(jnp.sum(g))
    assert abs(est.mean() - full) < 4 * est.std() / 16 + 1e-3
