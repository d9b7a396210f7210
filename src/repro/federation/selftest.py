"""Federated-vs-centralized self-checks: strict losslessness + tolerance.

Two equivalence contracts (DESIGN.md §5):

* **strict** (``check*``): lossless backends (raw transports, top-k
  candidate pruning, GOSS masks over a lossless transport) must produce
  trees *bit-identical* to the centralized builder — the SecureBoost
  property the paper's §4.2.1 relies on.
* **tolerance** (``check_tolerance``): lossy transports (quantized
  histogram exchange) cannot be bit-identical by construction; the contract
  is instead a bound on the end-metric delta of a full training run against
  the centralized model (same config, same rng, same masks).

Plus **reconciliation** (``check_reconciliation``): the bytes every
collective actually ships (``compress.probe_tree_cost``) must equal the
predicted wire model (``protocol.wire_run_cost``) *exactly*, for every
transport — payload sizes are shape-determined even when values are lossy.

Sibling subtraction (DESIGN.md §6) slots into the same lattice:
federated-vs-centralized stays *bit-identical* with the pipeline enabled on
both sides; subtraction-vs-direct is a float-reassociation *tolerance*
relation (``check_subtraction_vs_direct``), composing with q8's existing
tolerance bound; and the half-width child payloads reconcile exactly, with
the measured histogram-phase cut asserted >= 1.7x at depth 3
(``check_subtraction_hist_cut``).

The round engine (DESIGN.md §9) extends the lattice again: depth-4/5 trees
under frontier compaction stay *bit-identical* fed-vs-central (compaction is
deterministic in the TreeConfig, so both sides build the same trees); the
traced round program ships exactly ONE histogram collective per level
regardless of the round's tree count (``check_round_collective_counts``);
shared-root caching is a *tolerance* relation like subtraction-vs-direct
(``check_shared_root_tolerance``); and the active-width wire model
reconciles exactly at depth 5 under compaction.

Row sharding and the async exchange (DESIGN.md §8/§10) extend it once more:
training under an explicit ``data_shards=2`` grid — including n uneven over
the shards, padded with weight-0 rows inside the backend — stays
*bit-identical* fed-vs-central; the async double-buffered backends are
bit-identical to their synchronous twins, keep ONE logical histogram
collective per level, and reconcile byte-for-byte; and the bit-packed
id_partition bitmap measures ``ceil(n/8)`` per level (>= 8x under the
legacy encodings, ``check_id_partition_packing``) with the per-shard ceil
arithmetic exact for any shard count.

The objective layer (DESIGN.md §11) widens the whole lattice by a channel
axis: K-channel objectives (softmax3, constant-hessian quantile) must keep
fed-vs-central *bit-identical* through every backend combination, the
widened 2K+1-stat histograms and (n, K) grad broadcast must reconcile
exactly at any K, and the gradient-less party-local mode must ship ZERO
histogram/gradient/routing bytes — its margin/rate inventory reconciled
against ``gradientless.wire_cost`` (``check_gradientless``).

Run in a subprocess with multiple CPU devices, e.g.:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m repro.federation.selftest

Exits non-zero on any mismatch. tests/test_federation.py shells out to this
module so the main pytest process keeps its single-device view.
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import binning, boosting, forest, losses, metrics
from repro.core import objective as objective_mod
from repro.core.types import FedGBFConfig, TreeConfig
from repro.federation import compress, gradientless, protocol, vfl
from repro.launch.mesh import make_mesh


def check(num_parties: int, aggregation: str, shard_samples: bool,
          subtraction: bool = False, max_depth: int = 3,
          max_active_nodes: int = 0, data_shards: int = 0,
          async_exchange: bool = False, n: int = 512,
          loss: str = "logistic") -> None:
    """Fed-vs-central bit-identity.  ``data_shards`` pins the mesh's data
    axis extent (0 = spread all remaining devices); an ``n`` not divisible
    by the data extent exercises the backend's weight-0 row padding.
    ``loss`` selects the objective (DESIGN.md §11): a K-channel objective
    widens g/h to (n, K) and the exchanged histograms to 2K+1 stats, and
    the bit-identity contract must hold unchanged."""
    mesh_axes = ("data", "model")
    n_dev = len(jax.devices())
    data_dim = data_shards or n_dev // num_parties
    mesh = make_mesh((data_dim, num_parties), mesh_axes)

    rng = np.random.default_rng(0)
    obj = objective_mod.get_objective(loss)
    d = num_parties * 3
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    y = jnp.asarray(rng.integers(0, max(2, obj.n_classes), n), jnp.float32)
    cfg = TreeConfig(max_depth=max_depth, num_bins=16,
                     hist_subtraction=subtraction,
                     max_active_nodes=max_active_nodes)

    binned, _ = binning.fit_bin(x, cfg.num_bins)
    g, h = obj.grad_hess(y, obj.init_raw(n))
    smask, fmask = forest.sample_masks(jax.random.PRNGKey(7), n, d, 4, 0.8, 1.0)

    trees_c, pred_c = forest.build_forest(binned, g, h, smask, fmask, cfg)

    backend = vfl.make_vfl_backend(
        mesh, cfg, aggregation=aggregation, shard_samples=shard_samples,
        async_exchange=async_exchange,
    )
    with jax.set_mesh(mesh):
        trees_f, pred_f = backend.build_forest(binned, g, h, smask, fmask, cfg)

    np.testing.assert_array_equal(
        np.asarray(trees_c.feature), np.asarray(trees_f.feature),
        err_msg=f"feature mismatch ({aggregation}, shard_samples={shard_samples})",
    )
    np.testing.assert_array_equal(
        np.asarray(trees_c.threshold), np.asarray(trees_f.threshold)
    )
    np.testing.assert_allclose(
        np.asarray(trees_c.leaf_weight), np.asarray(trees_f.leaf_weight),
        rtol=1e-5, atol=1e-6,
    )
    np.testing.assert_allclose(
        np.asarray(pred_c), np.asarray(pred_f), rtol=1e-5, atol=1e-6
    )
    print(
        f"OK lossless: parties={num_parties} aggregation={aggregation} "
        f"shard_samples={shard_samples} subtraction={subtraction} "
        f"depth={max_depth} budget={max_active_nodes} "
        f"data_shards={data_dim} async={async_exchange} n={n} loss={loss}"
    )


def check_no_valid_split(num_parties: int, aggregation: str, degenerate: str) -> None:
    """Equivalence on the degenerate frontier: when NO valid split exists
    anywhere (every gain <= 0, or min_child_weight filters every candidate),
    the federated builders must still produce trees bit-identical to the
    centralized one — all-(-1) features, threshold == B everywhere, and the
    single populated leaf carrying the global weight.  This is the edge the
    argmax aggregation is most exposed to (its per-party candidate exchange
    must agree on "no split" without exchanging histograms)."""
    mesh = make_mesh((1, num_parties), ("data", "model"))

    rng = np.random.default_rng(13)
    n, d = 256, num_parties * 2
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 2, n), jnp.float32)
    if degenerate == "gamma":
        # every candidate's gain is pushed below zero
        cfg = TreeConfig(max_depth=2, num_bins=8, gamma=1e9)
    else:
        # every candidate fails the child-weight filter -> gain = -inf
        cfg = TreeConfig(max_depth=2, num_bins=8, min_child_weight=1e9)

    binned, _ = binning.fit_bin(x, cfg.num_bins)
    g, h = losses.grad_hess("logistic", y, jnp.zeros(n))
    smask, fmask = forest.sample_masks(jax.random.PRNGKey(3), n, d, 3, 0.9, 1.0)

    trees_c, pred_c = forest.build_forest(binned, g, h, smask, fmask, cfg)
    assert np.all(np.asarray(trees_c.feature) == -1), "expected a split-free tree"

    backend = vfl.make_vfl_backend(mesh, cfg, aggregation=aggregation)
    with jax.set_mesh(mesh):
        trees_f, pred_f = backend.build_forest(binned, g, h, smask, fmask, cfg)

    np.testing.assert_array_equal(
        np.asarray(trees_c.feature), np.asarray(trees_f.feature),
        err_msg=f"no-valid-split feature mismatch ({aggregation}, {degenerate})",
    )
    np.testing.assert_array_equal(
        np.asarray(trees_c.threshold), np.asarray(trees_f.threshold)
    )
    np.testing.assert_allclose(
        np.asarray(trees_c.leaf_weight), np.asarray(trees_f.leaf_weight),
        rtol=1e-5, atol=1e-6,
    )
    np.testing.assert_allclose(
        np.asarray(pred_c), np.asarray(pred_f), rtol=1e-5, atol=1e-6
    )
    print(
        f"OK no-valid-split lossless: parties={num_parties} "
        f"aggregation={aggregation} degenerate={degenerate}"
    )


def check_topk_lossless(num_parties: int, k: int) -> None:
    """Top-k candidate pruning is lossless for ANY k >= 1: every party's own
    best candidate is in its top-k, and the party-major merge reproduces the
    centralized first-occurrence tie-break (compress.topk_choose_fn)."""
    mesh = make_mesh((1, num_parties), ("data", "model"))
    rng = np.random.default_rng(5)
    n, d = 512, num_parties * 3
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 2, n), jnp.float32)
    cfg = TreeConfig(max_depth=3, num_bins=16)

    binned, _ = binning.fit_bin(x, cfg.num_bins)
    g, h = losses.grad_hess("logistic", y, jnp.zeros(n))
    smask, fmask = forest.sample_masks(jax.random.PRNGKey(7), n, d, 4, 0.8, 1.0)

    trees_c, pred_c = forest.build_forest(binned, g, h, smask, fmask, cfg)
    backend = vfl.make_vfl_backend(
        mesh, cfg, aggregation="argmax",
        transport=compress.TransportSpec(kind="topk", k=k),
    )
    with jax.set_mesh(mesh):
        trees_f, pred_f = backend.build_forest(binned, g, h, smask, fmask, cfg)
    np.testing.assert_array_equal(
        np.asarray(trees_c.feature), np.asarray(trees_f.feature),
        err_msg=f"topk feature mismatch (k={k})",
    )
    np.testing.assert_array_equal(
        np.asarray(trees_c.threshold), np.asarray(trees_f.threshold)
    )
    np.testing.assert_allclose(
        np.asarray(pred_c), np.asarray(pred_f), rtol=1e-5, atol=1e-6
    )
    print(f"OK topk lossless: parties={num_parties} k={k}")


def check_goss_lossless(num_parties: int, aggregation: str) -> None:
    """GOSS is a masking policy, not a transport: the same weighted masks
    fed to the centralized and federated builders must yield bit-identical
    trees (weights ride the existing sample_mask channel)."""
    mesh = make_mesh((1, num_parties), ("data", "model"))
    rng = np.random.default_rng(11)
    n, d = 512, num_parties * 2
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 2, n), jnp.float32)
    cfg = TreeConfig(max_depth=3, num_bins=16)

    binned, _ = binning.fit_bin(x, cfg.num_bins)
    g, h = losses.grad_hess("logistic", y, jnp.zeros(n))
    n_top, n_rand = forest.goss_counts(n, 0.4, 0.5)
    smask, fmask = forest.goss_masks(
        jax.random.PRNGKey(9), g, d, 3, n_top, n_rand, d
    )

    trees_c, pred_c = forest.build_forest(binned, g, h, smask, fmask, cfg)
    backend = vfl.make_vfl_backend(mesh, cfg, aggregation=aggregation)
    with jax.set_mesh(mesh):
        trees_f, pred_f = backend.build_forest(binned, g, h, smask, fmask, cfg)
    np.testing.assert_array_equal(
        np.asarray(trees_c.feature), np.asarray(trees_f.feature),
        err_msg=f"goss feature mismatch ({aggregation})",
    )
    np.testing.assert_allclose(
        np.asarray(trees_c.leaf_weight), np.asarray(trees_f.leaf_weight),
        rtol=1e-5, atol=1e-6,
    )
    print(f"OK goss lossless: parties={num_parties} aggregation={aggregation}")


def _metric_deltas(y, model_a, model_b, x) -> dict:
    out = {}
    for name, fn in (
        ("auc", lambda m: float(metrics.auc(y, boosting.predict(m, x)))),
        ("logloss", lambda m: float(losses.loss_value(
            "logistic", y, boosting.predict(m, x)))),
    ):
        out[name] = abs(fn(model_a) - fn(model_b))
    return out


def _tolerance_data(num_parties: int):
    rng = np.random.default_rng(17)
    n, d = 2000, num_parties * 2
    x = rng.normal(size=(n, d)).astype(np.float32)
    logit = x[:, 0] - 0.8 * x[:, 1] + 0.5 * x[:, 2] * x[:, 3]
    y = (logit + rng.normal(0, 0.7, n) > 0).astype(np.float32)
    return jnp.asarray(x), jnp.asarray(y)


def check_tolerance(
    num_parties: int, aggregation: str, transport, bound: float = 5e-3,
    subtraction: bool = False,
) -> None:
    """Tolerance-based equivalence for LOSSY transports (DESIGN.md §5).

    A quantized exchange cannot reproduce centralized trees bit-for-bit;
    the contract is a bound on the end-metric delta: train the same config
    with the same rng centralized and federated-lossy, and require
    |AUC_c - AUC_f| and |logloss_c - logloss_f| within ``bound``.

    ``subtraction`` composes the sibling-subtraction pipeline with the lossy
    transport ON BOTH SIDES (the federated-vs-centralized contract compares
    like with like; subtraction-vs-direct has its own check).
    """
    mesh = make_mesh((1, num_parties), ("data", "model"))
    x, y = _tolerance_data(num_parties)
    cfg = FedGBFConfig(
        rounds=4, n_trees_max=3, n_trees_min=2, rho_id_min=0.5, rho_id_max=0.8,
        tree=TreeConfig(max_depth=3, num_bins=32, hist_subtraction=subtraction),
    )

    model_c, _ = boosting.train_fedgbf(x, y, cfg, jax.random.PRNGKey(0))
    backend = vfl.make_vfl_backend(
        mesh, cfg.tree, aggregation=aggregation, transport=transport
    )
    with jax.set_mesh(mesh):
        model_f, _ = boosting.train_fedgbf(
            x, y, cfg, jax.random.PRNGKey(0), backend=backend
        )
    deltas = _metric_deltas(y, model_c, model_f, x)
    for name, delta in deltas.items():
        assert delta <= bound, (
            f"{name} delta {delta:.2e} exceeds tolerance {bound:.0e} "
            f"({aggregation}, transport={transport.tag}, "
            f"subtraction={subtraction})"
        )
    print(
        f"OK tolerance: parties={num_parties} transport={transport.tag} "
        f"subtraction={subtraction} "
        + " ".join(f"d_{k}={v:.1e}" for k, v in deltas.items())
    )


def check_subtraction_vs_direct(bound: float = 5e-3) -> None:
    """Subtraction-vs-direct contract (DESIGN.md §6): the derived right
    siblings differ from directly accumulated ones only by float
    reassociation, so full-training end metrics must agree within the same
    tolerance class as the §5 lossy transports (the trees themselves are
    typically identical — a near-tie at a split can legitimately flip)."""
    x, y = _tolerance_data(2)
    # hist_subtraction defaults ON; the direct pass is the explicit oracle.
    base = FedGBFConfig(
        rounds=4, n_trees_max=3, n_trees_min=2, rho_id_min=0.5, rho_id_max=0.8,
        tree=TreeConfig(max_depth=3, num_bins=32, hist_subtraction=False),
    )
    import dataclasses

    sub = dataclasses.replace(
        base, tree=dataclasses.replace(base.tree, hist_subtraction=True)
    )
    model_d, _ = boosting.train_fedgbf(x, y, base, jax.random.PRNGKey(0))
    model_s, _ = boosting.train_fedgbf(x, y, sub, jax.random.PRNGKey(0))
    deltas = _metric_deltas(y, model_d, model_s, x)
    for name, delta in deltas.items():
        assert delta <= bound, (
            f"subtraction-vs-direct {name} delta {delta:.2e} exceeds "
            f"{bound:.0e}"
        )
    print("OK subtraction-vs-direct: "
          + " ".join(f"d_{k}={v:.1e}" for k, v in deltas.items()))


def check_reconciliation(num_parties: int, aggregation: str, transport,
                         shard_samples: bool = False,
                         subtraction: bool = False,
                         max_depth: int = 3,
                         max_active_nodes: int = 0,
                         async_exchange: bool = False,
                         n: int = 1536,
                         n_channels: int = 1) -> None:
    """Measured collective payloads == predicted wire model, exactly —
    including the round engine's active-width model under compaction, the
    data-shard-aware bit-packed id_partition arithmetic (an ``n`` uneven
    over the shards exercises the per-shard ceil), the async exchange
    (double-buffering must not change a byte), and any channel count
    (``n_channels=K`` widens histograms to 2K stats + count and the grad
    broadcast to 2K floats per row; DESIGN.md §11)."""
    data_dim = len(jax.devices()) // num_parties if shard_samples else 1
    mesh = make_mesh((data_dim, num_parties), ("data", "model"))
    tree = TreeConfig(max_depth=max_depth, num_bins=32,
                      hist_subtraction=subtraction,
                      max_active_nodes=max_active_nodes)
    d = num_parties * 2
    per_tree, grad = compress.probe_tree_cost(
        mesh, tree, aggregation=aggregation, transport=transport,
        n_samples=n, num_features=d, shard_samples=shard_samples,
        async_exchange=async_exchange, n_channels=n_channels,
    )
    cfg = FedGBFConfig(rounds=3, n_trees_max=4, n_trees_min=2,
                       rho_id_min=0.2, rho_id_max=0.5)
    spec = protocol.ProtocolSpec(
        n_samples=n, party_dims=(d // num_parties,) * num_parties,
        num_bins=tree.num_bins, max_depth=tree.max_depth,
        aggregation=aggregation, hist_subtraction=subtraction,
        max_active_nodes=max_active_nodes,
        data_shards=data_dim if shard_samples else 1,
        n_channels=n_channels,
    )
    ledger = protocol.ProtocolLedger(spec=spec, cfg=cfg, transport=transport)
    ledger.record_run(per_tree, grad)
    rec = ledger.reconcile()
    assert ledger.matches(), (
        f"measured != predicted for {aggregation}"
        f"/{transport.tag if transport else 'raw'}"
        f"{'+sub' if subtraction else ''}"
        f"{'+async' if async_exchange else ''}: {rec}"
    )
    tag = transport.tag if transport else "raw"
    print(
        f"OK reconciliation: parties={num_parties} {aggregation}/{tag} "
        f"shard_samples={shard_samples} subtraction={subtraction} "
        f"depth={max_depth} budget={max_active_nodes} "
        f"async={async_exchange} n={n} K={n_channels} "
        f"total={rec['total']['measured']} bytes (exact match)"
    )


def check_gradientless(num_parties: int, loss: str = "logistic",
                       n: int = 600) -> None:
    """Gradient-less party-local mode (DESIGN.md §11): no gradient or
    histogram message exists; the wire inventory is passive-party margin
    blocks in + the learned rate vector out, and the measured payloads
    must equal ``gradientless.wire_cost`` exactly (with every protocol
    phase of the gradient-sharing mode identically zero).  The rate fit
    must improve on the plain concatenation of the local models, and every
    tree must reference only its owning party's global column range."""
    obj = objective_mod.get_objective(loss)
    rng = np.random.default_rng(23)
    d = num_parties * 3
    x_np = rng.normal(size=(n, d)).astype(np.float32)
    logit = x_np[:, 0] - 0.8 * x_np[:, 1] + 0.5 * x_np[:, 2] * x_np[:, 3]
    if obj.n_classes > 1:
        cuts = np.quantile(logit, np.linspace(0, 1, obj.n_classes + 1)[1:-1])
        y_np = np.searchsorted(cuts, logit).astype(np.float32)
    else:
        y_np = (logit + rng.normal(0, 0.7, n) > 0).astype(np.float32)
    x, y = jnp.asarray(x_np), jnp.asarray(y_np)
    cfg = FedGBFConfig(
        rounds=3, n_trees_max=3, n_trees_min=2, rho_id_min=0.5,
        rho_id_max=0.8, loss=loss,
        tree=TreeConfig(max_depth=3, num_bins=16),
    )
    meter = compress.MessageMeter()
    packed, info = gradientless.train_gradientless(
        x, y, cfg, jax.random.PRNGKey(0), num_parties, meter=meter,
    )
    assert info["loss_after"] <= info["loss_before"] + 1e-6, info
    # party-locality: party p's trees may only touch columns [p*dp, (p+1)*dp)
    d_party = d // num_parties
    offset = 0
    for p, t_p in enumerate(info["tree_counts"]):
        feats = np.asarray(packed.feature[offset:offset + t_p])
        real = feats[feats >= 0]
        assert ((real >= p * d_party) & (real < (p + 1) * d_party)).all(), (
            f"party {p} tree references foreign columns"
        )
        offset += t_p
    predicted = gradientless.wire_cost(n, info["tree_counts"],
                                       n_channels=obj.n_classes)
    measured = meter.phase_totals()
    for phase in ("histograms", "grad_broadcast", "id_partition"):
        assert measured.get(phase, 0) == 0 == predicted[phase], (
            f"gradient-less mode must ship zero {phase} bytes"
        )
    for phase in ("tree_margins", "tree_scales"):
        assert measured[phase] == predicted[phase], (
            f"{phase}: measured {measured[phase]} != "
            f"predicted {predicted[phase]}"
        )
    print(
        f"OK gradientless: parties={num_parties} loss={loss} "
        f"loss {info['loss_before']:.3f} -> {info['loss_after']:.3f}, "
        f"wire={sum(measured.values())} bytes "
        f"(margins+rates only, exact match)"
    )


def check_round_collective_counts(num_parties: int, n_trees: int,
                                  transport=None,
                                  async_exchange: bool = False) -> None:
    """Round-engine structural contract (DESIGN.md §9): the traced round
    program records exactly ONE histogram collective per level — the whole
    round's (T, active, d_party, B, 3) payload — independent of T.  The
    async backends (§10) must preserve the counts: double-buffering splits
    the transfer, never the logical message (quantized transports record 2
    per level either way: int payload + scales)."""
    mesh = make_mesh((1, num_parties), ("data", "model"))
    tree = TreeConfig(max_depth=3, num_bins=16)
    rc = compress.probe_round_collectives(
        mesh, tree, n_trees, aggregation="histogram", transport=transport,
        n_samples=512, num_features=num_parties * 2,
        async_exchange=async_exchange,
    )
    counts = rc["counts"]
    per_level = 2 if transport is not None else 1
    assert counts.get("histograms") == per_level * tree.max_depth, counts
    assert counts.get("feature_mask") == tree.max_depth, counts
    assert counts.get("id_partition") == tree.max_depth, counts
    tag = transport.tag if transport else "raw"
    print(f"OK round collectives: parties={num_parties} T={n_trees} "
          f"transport={tag} async={async_exchange} histogram records per "
          f"level == {per_level} ({tree.max_depth} levels)")


def check_id_partition_packing(num_parties: int) -> None:
    """The bit-packed routing broadcast: measured id_partition bytes are
    the ceil(n/8) bitmap, >= 8x under the legacy 1-byte-per-row encoding
    and 32x under the int32 vector the implementation used to psum."""
    mesh = make_mesh((1, num_parties), ("data", "model"))
    tree = TreeConfig(max_depth=3, num_bins=16)
    n, d = 1536, num_parties * 2
    per_tree, _ = compress.probe_tree_cost(
        mesh, tree, aggregation="histogram", n_samples=n, num_features=d,
    )
    packed = per_tree["id_partition"]
    assert packed == tree.max_depth * ((n + 7) // 8), per_tree
    unpacked_int32 = tree.max_depth * n * 4
    cut = unpacked_int32 / packed
    assert cut >= 8.0, f"id_partition cut {cut:.1f}x below the 8x bar"
    print(f"OK id_partition packing: {unpacked_int32} -> {packed} B/tree "
          f"({cut:.0f}x cut)")


def check_shared_root_tolerance(num_parties: int, bound: float = 5e-3) -> None:
    """Shared-root caching (DESIGN.md §9) composes with the federated path:
    end metrics of a full run with shared_root on (high-rho schedule, so the
    engines take the delta path) track the direct pipeline within the §5/§6
    tolerance class — centralized and federated alike."""
    import dataclasses

    mesh = make_mesh((1, num_parties), ("data", "model"))
    x, y = _tolerance_data(num_parties)
    base = FedGBFConfig(
        rounds=4, n_trees_max=3, n_trees_min=2, rho_id_min=0.6, rho_id_max=0.9,
        tree=TreeConfig(max_depth=3, num_bins=32),
    )
    shared = dataclasses.replace(
        base, tree=dataclasses.replace(base.tree, shared_root=True)
    )
    model_d, _ = boosting.train_fedgbf(x, y, base, jax.random.PRNGKey(0))
    model_s, _ = boosting.train_fedgbf(x, y, shared, jax.random.PRNGKey(0))
    backend = vfl.make_vfl_backend(mesh, shared.tree, aggregation="histogram")
    with jax.set_mesh(mesh):
        model_f, _ = boosting.train_fedgbf(
            x, y, shared, jax.random.PRNGKey(0), backend=backend
        )
    for name, pair in (("central", model_s), ("federated", model_f)):
        deltas = _metric_deltas(y, model_d, pair, x)
        for metric, delta in deltas.items():
            assert delta <= bound, (
                f"shared-root {name} {metric} delta {delta:.2e} exceeds "
                f"{bound:.0e}"
            )
    print("OK shared-root tolerance: central + federated within "
          f"{bound:.0e} of the direct pipeline")


def check_subtraction_hist_cut(num_parties: int, transport) -> None:
    """The subtraction pipeline's measured (ledger-reconciled) histogram-phase
    bytes must show the depth-3 cut: 7 -> 4 node-histograms per tree, i.e.
    exactly 1.75x (>= the 1.7x acceptance bar) — measured from the traced
    programs of both pipelines, not from the formulas."""
    mesh = make_mesh((1, num_parties), ("data", "model"))
    n, d = 1536, num_parties * 2
    measured = {}
    for sub in (False, True):
        tree = TreeConfig(max_depth=3, num_bins=32, hist_subtraction=sub)
        per_tree, _ = compress.probe_tree_cost(
            mesh, tree, aggregation="histogram", transport=transport,
            n_samples=n, num_features=d,
        )
        measured[sub] = per_tree["histograms"]
    cut = measured[False] / measured[True]
    tag = transport.tag if transport else "raw"
    assert cut >= 1.7, (
        f"histogram-phase cut {cut:.3f}x below the 1.7x bar ({tag})"
    )
    print(f"OK subtraction hist cut: {tag} "
          f"{measured[False]} -> {measured[True]} B/tree ({cut:.2f}x)")


def _train_named(mesh, tcfg, cfg, x, y, backend_name, **kw):
    from repro.core.backend import get_backend

    with jax.set_mesh(mesh):
        bk = get_backend(backend_name, mesh=mesh, tree=tcfg, **kw)
        model, _ = boosting.train_fedgbf(
            x, y, cfg, jax.random.PRNGKey(0), backend=bk, engine="scan"
        )
    return [np.asarray(l) for l in jax.tree.leaves(model)]


def check_chaos(backend_name: str, num_parties: int = 4,
                n: int = 512) -> None:
    """Chaos transport equivalence (DESIGN.md §13): the ``-chaos`` twin of a
    registry backend must train a bit-identical model — under the zero-fault
    spec (checksums verify but never fire) AND under injected faults (every
    dropped/corrupted transmission is detected by the payload checksum and
    recovered from a retransmission, so faults cost only wire bytes, never
    bits of the result)."""
    from repro.federation import chaos as chaos_mod

    mesh = make_mesh((1, num_parties), ("data", "model"))
    tcfg = TreeConfig(max_depth=3, num_bins=16)
    cfg = FedGBFConfig(rounds=2, n_trees_max=3, n_trees_min=2,
                       rho_id_min=0.5, rho_id_max=0.8, tree=tcfg)
    rng = np.random.default_rng(0)
    d = num_parties * 2
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    y = jnp.asarray((rng.normal(size=n) + x[:, 0] > 0).astype(np.float32))

    base = _train_named(mesh, tcfg, cfg, x, y, backend_name)
    zero_fault = _train_named(mesh, tcfg, cfg, x, y, backend_name + "-chaos")
    for a, b in zip(base, zero_fault):
        assert a.shape == b.shape and (a == b).all(), (
            f"{backend_name}-chaos (zero-fault) diverged from {backend_name}"
        )
    spec = chaos_mod.ChaosSpec(drop=0.10, corrupt=0.05, dup=0.05, seed=7)
    faulty = _train_named(mesh, tcfg, cfg, x, y, backend_name + "-chaos",
                          chaos=spec)
    for a, b in zip(base, faulty):
        assert (a == b).all(), (
            f"{backend_name}-chaos under {spec.tag} diverged: a fault "
            "escaped checksum detection"
        )
    print(f"OK chaos bit-identity: {backend_name} (zero-fault AND "
          f"{spec.tag})")


def check_chaos_reconciliation(aggregation: str, transport,
                               num_parties: int = 4, n: int = 777) -> None:
    """Under injected faults the ledger must still reconcile EXACTLY: the
    retried payloads + per-transmission checksums land in the dedicated
    ``retries`` wire phase on both the measured and predicted side."""
    from repro.federation import chaos as chaos_mod

    mesh = make_mesh((1, num_parties), ("data", "model"))
    tcfg = TreeConfig(max_depth=3, num_bins=16)
    cfg = FedGBFConfig(rounds=3, n_trees_max=4, n_trees_min=2,
                       rho_id_min=0.2, rho_id_max=0.5)
    spec = chaos_mod.ChaosSpec(drop=0.10, corrupt=0.05, dup=0.05, seed=7)
    ledger = compress.reconciled_ledger(
        mesh, tcfg, cfg, aggregation=aggregation, transport=transport,
        n_samples=n, num_features=num_parties * 2, chaos=spec,
    )
    rec = ledger.reconcile()
    tag = transport.tag if transport else "raw"
    assert ledger.matches(), f"chaos {aggregation}/{tag}: {rec}"
    assert rec["retries"]["measured"] > 0, (
        f"chaos {aggregation}/{tag}: no retry bytes measured under faults"
    )
    print(f"OK chaos reconciliation: {aggregation}/{tag} "
          f"retries={rec['retries']['measured']}B "
          f"total={rec['total']['measured']}B (exact match)")


def check_degradation(num_parties: int = 4, n: int = 512) -> None:
    """Party-dropout degradation oracle (DESIGN.md §13): training with a
    degraded party's columns masked via ``round_feature_mask`` must be
    bit-identical federated-vs-central (the mask composes with the sampled
    candidate masks before the exchange), and no tree may split on a
    degraded column in a masked round."""
    from repro.core.types import pack_ensemble
    from repro.federation import runtime

    mesh = make_mesh((1, num_parties), ("data", "model"))
    tcfg = TreeConfig(max_depth=3, num_bins=16)
    cfg = FedGBFConfig(rounds=4, n_trees_max=3, n_trees_min=2,
                       rho_id_min=0.5, rho_id_max=0.8, tree=tcfg)
    rng = np.random.default_rng(3)
    d = num_parties * 2
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    y = jnp.asarray((rng.normal(size=n) + x[:, 0] > 0).astype(np.float32))

    sched = runtime.dropout_schedule(0.6, cfg.rounds, num_parties, seed=11,
                                     policy=runtime.RetryPolicy(max_retries=0))
    mask = runtime.degradation_masks(sched.degraded, d, num_parties)
    assert mask is not None and not mask.all(), (
        "oracle needs at least one degraded (round, party); reseed"
    )
    backend = vfl.make_vfl_backend(mesh, tcfg, aggregation="histogram")
    with jax.set_mesh(mesh):
        model_f, _ = boosting.train_fedgbf(
            x, y, cfg, jax.random.PRNGKey(0), backend=backend,
            round_feature_mask=mask, engine="scan",
        )
    model_c, _ = boosting.train_fedgbf(
        x, y, cfg, jax.random.PRNGKey(0), round_feature_mask=mask,
        engine="scan",
    )
    for a, b in zip(jax.tree.leaves(model_f), jax.tree.leaves(model_c)):
        assert (np.asarray(a) == np.asarray(b)).all(), (
            "degraded fed run diverged from the masked-candidate oracle"
        )
    # no split on a masked column: walk each round's trees
    packed = pack_ensemble(model_c)
    for r in range(packed.rounds):
        trees_r = packed.round_trees(r)
        feats = np.asarray(trees_r.feature)
        gains = np.asarray(trees_r.gain)
        banned = np.nonzero(~mask[r])[0]
        hit = np.isin(feats, banned) & (gains > 0)
        assert not hit.any(), (
            f"round {r + 1} split on degraded column(s) "
            f"{np.unique(feats[hit])}"
        )
    n_deg = int(sched.degraded.sum())
    print(f"OK degradation oracle: {n_deg} degraded (round, party) cells, "
          "fed == masked-candidate central (bit-identical), no banned splits")


def chaos_main() -> int:
    """The §13 slice of the lattice (``--chaos``): chaos twins across the
    transport x aggregation x async x sharded axes, exact reconciliation
    under faults, and the party-dropout degradation oracle."""
    n_dev = len(jax.devices())
    if n_dev < 4:
        print(f"need >= 4 devices, got {n_dev} (set XLA_FLAGS)",
              file=sys.stderr)
        return 2
    for name in ("vfl-histogram", "vfl-histogram-q8", "vfl-histogram-q16",
                 "vfl-argmax", "vfl-argmax-topk", "vfl-histogram-async",
                 "vfl-histogram-async-q8", "vfl-histogram-sharded"):
        check_chaos(name)
    for aggregation, transport in (
        ("histogram", None), ("histogram", compress.Q8),
        ("argmax", None), ("argmax", compress.TOPK),
    ):
        check_chaos_reconciliation(aggregation, transport)
    check_degradation()
    print("ALL CHAOS SELF-TESTS PASSED")
    return 0


def main() -> int:
    n_dev = len(jax.devices())
    if n_dev < 4:
        print(f"need >= 4 devices, got {n_dev} (set XLA_FLAGS)", file=sys.stderr)
        return 2
    for aggregation in ("histogram", "argmax"):
        for shard_samples in (False, True):
            check(num_parties=4, aggregation=aggregation, shard_samples=shard_samples)
    check(num_parties=2, aggregation="histogram", shard_samples=True)
    # Row sharding (DESIGN.md §8): explicit data_shards=2 grid, both
    # aggregations, plus an n uneven over the shards — the backend pads
    # with weight-0 rows and the result stays bit-identical.
    for aggregation in ("histogram", "argmax"):
        check(num_parties=2, aggregation=aggregation, shard_samples=True,
              data_shards=2)
    check(num_parties=2, aggregation="histogram", shard_samples=True,
          data_shards=2, n=509)
    check(num_parties=4, aggregation="histogram", shard_samples=True,
          data_shards=2, subtraction=True, n=507)
    # Async double-buffered exchange (DESIGN.md §10): bit-identical to the
    # synchronous path, composing with sharding, subtraction, compaction.
    check(num_parties=4, aggregation="histogram", shard_samples=False,
          async_exchange=True)
    check(num_parties=4, aggregation="histogram", shard_samples=True,
          async_exchange=True, subtraction=True)
    check(num_parties=2, aggregation="histogram", shard_samples=True,
          data_shards=2, async_exchange=True, n=509)
    check(num_parties=4, aggregation="histogram", shard_samples=False,
          async_exchange=True, subtraction=True, max_depth=4,
          max_active_nodes=4)
    # K-channel objectives (DESIGN.md §11): softmax3 widens g/h to (n, 3)
    # and the exchanged histograms to 7 stats — bit-identity must survive
    # every backend axis it composes with (sharding, subtraction, async,
    # compaction), and quantile exercises the constant-hessian path.
    for aggregation in ("histogram", "argmax"):
        check(num_parties=4, aggregation=aggregation, shard_samples=False,
              loss="softmax3")
    check(num_parties=4, aggregation="histogram", shard_samples=True,
          subtraction=True, loss="softmax3")
    check(num_parties=4, aggregation="histogram", shard_samples=False,
          async_exchange=True, subtraction=True, loss="softmax3")
    check(num_parties=2, aggregation="histogram", shard_samples=True,
          data_shards=2, loss="softmax3", n=509)
    check(num_parties=4, aggregation="histogram", shard_samples=False,
          subtraction=True, max_depth=4, max_active_nodes=4, loss="softmax3")
    check(num_parties=4, aggregation="histogram", shard_samples=False,
          loss="quantile@0.9")
    # Gradient-less party-local mode (DESIGN.md §11): zero-histogram wire
    # inventory, exact margin/rate byte accounting, party-local trees.
    check_gradientless(num_parties=4, loss="logistic")
    check_gradientless(num_parties=2, loss="softmax3")
    # Sibling subtraction (DESIGN.md §6): federated-vs-centralized stays
    # bit-identical with the pipeline enabled on BOTH sides; the
    # subtraction-vs-direct relation is a separate tolerance contract.
    for aggregation in ("histogram", "argmax"):
        check(num_parties=4, aggregation=aggregation, shard_samples=False,
              subtraction=True)
    check(num_parties=4, aggregation="histogram", shard_samples=True,
          subtraction=True)
    check_subtraction_vs_direct()
    # Round engine (DESIGN.md §9): deep trees under frontier compaction stay
    # bit-identical fed-vs-central (compaction is deterministic in the cfg,
    # so both sides build the same trees), one collective per level
    # regardless of T, and shared-root caching stays in tolerance.
    for max_depth, budget in ((4, 4), (5, 4), (5, 8)):
        check(num_parties=4, aggregation="histogram", shard_samples=False,
              subtraction=True, max_depth=max_depth, max_active_nodes=budget)
    check(num_parties=4, aggregation="argmax", shard_samples=False,
          subtraction=False, max_depth=5, max_active_nodes=4)
    check(num_parties=4, aggregation="histogram", shard_samples=True,
          subtraction=True, max_depth=4, max_active_nodes=4)
    for n_trees in (1, 4):
        check_round_collective_counts(num_parties=4, n_trees=n_trees)
    # one logical collective per level survives the async double-buffering
    for transport in (None, compress.Q8):
        check_round_collective_counts(num_parties=4, n_trees=4,
                                      transport=transport,
                                      async_exchange=True)
    check_id_partition_packing(num_parties=4)
    check_shared_root_tolerance(num_parties=2)
    for aggregation in ("histogram", "argmax"):
        for degenerate in ("gamma", "min_child_weight"):
            check_no_valid_split(4, aggregation, degenerate)
    # Compression subsystem (DESIGN.md §5): strict for the lossless pieces,
    # tolerance for the quantized transports, exact byte reconciliation for all.
    for k in (1, 4):
        check_topk_lossless(num_parties=4, k=k)
    for aggregation in ("histogram", "argmax"):
        check_goss_lossless(num_parties=4, aggregation=aggregation)
    for transport in (compress.Q8, compress.Q16):
        check_tolerance(num_parties=2, aggregation="histogram",
                        transport=transport)
    # q8 composes with the subtraction pipeline under the same bound.
    check_tolerance(num_parties=2, aggregation="histogram",
                    transport=compress.Q8, subtraction=True)
    for aggregation, transport in (
        ("histogram", None), ("histogram", compress.Q8),
        ("histogram", compress.Q16), ("argmax", None),
        ("argmax", compress.TOPK),
    ):
        check_reconciliation(4, aggregation, transport)
    # subtraction: half-width child payloads must reconcile exactly too,
    # and the measured histogram-phase cut must clear the 1.7x bar.
    for aggregation, transport in (
        ("histogram", None), ("histogram", compress.Q8), ("argmax", None),
    ):
        check_reconciliation(4, aggregation, transport, subtraction=True)
    for transport in (None, compress.Q8):
        check_subtraction_hist_cut(4, transport)
    # depth-5 compaction: the active-width wire model reconciles exactly,
    # raw and quantized, with and without the subtraction halving.
    for transport, subtraction in ((None, True), (None, False),
                                   (compress.Q8, True)):
        check_reconciliation(4, "histogram", transport,
                             subtraction=subtraction, max_depth=5,
                             max_active_nodes=4)
    # sharded: the data-sharded routing psum must scale back to the global
    # payload (per-shard slice x shard count)
    check_reconciliation(4, "histogram", compress.Q8, shard_samples=True)
    check_reconciliation(2, "argmax", None, shard_samples=True)
    # uneven n over the shards: the per-shard ceil(ceil(n/shards)/8) bitmap
    # arithmetic must reconcile exactly (rows pad inside the backend)
    check_reconciliation(4, "histogram", None, shard_samples=True, n=1531)
    check_reconciliation(2, "argmax", None, shard_samples=True, n=999)
    # async: double-buffering must not change a single byte
    check_reconciliation(4, "histogram", None, async_exchange=True)
    check_reconciliation(4, "histogram", compress.Q16, async_exchange=True)
    check_reconciliation(4, "histogram", compress.Q8, shard_samples=True,
                         subtraction=True, async_exchange=True, n=1531)
    # K channels: the widened stats axis (2K floats per bin + per-channel
    # q8/q16 scales) and the (n, K) grad broadcast reconcile exactly at
    # K=3, raw and quantized, composing with subtraction + sharding + async
    check_reconciliation(4, "histogram", None, n_channels=3)
    check_reconciliation(4, "histogram", compress.Q8, subtraction=True,
                         n_channels=3)
    check_reconciliation(4, "histogram", compress.Q8, shard_samples=True,
                         subtraction=True, async_exchange=True, n=1531,
                         n_channels=3)
    print("ALL FEDERATION SELF-TESTS PASSED")
    return 0


if __name__ == "__main__":
    # ``--chaos`` runs ONLY the §13 fault-tolerance slice (chaos twins,
    # faulty reconciliation, degradation oracle); the default run is the
    # original lattice, so tier-1 runtime is unchanged.
    sys.exit(chaos_main() if "--chaos" in sys.argv[1:] else main())
