"""Scanned training engine vs legacy loop (DESIGN.md §4) + NaN-safe binning.

The load-bearing guarantee of this PR: the static-shape scanned engine —
the schedule factored into constant-width segments scanned inside one
compiled program — reproduces the legacy per-round loop's history metrics
to float tolerance and its trees structurally bit-for-bit, for static AND
dynamic schedules, so it can be the default engine everywhere.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import binning, boosting
from repro.core.types import FedGBFConfig, TreeConfig


def _data(loss, seed=0, n=600, d=6):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    sig = x[:, 0] - 0.7 * x[:, 1] + rng.normal(0, 0.4, n).astype(np.float32)
    y = (sig > 0).astype(np.float32) if loss == "logistic" else sig
    xv = rng.normal(size=(211, d)).astype(np.float32)
    sv = xv[:, 0] - 0.7 * xv[:, 1]
    yv = (sv > 0).astype(np.float32) if loss == "logistic" else sv
    return map(jnp.asarray, (x, y, xv, yv))


def _dyn_cfg(loss, rounds=5):
    return FedGBFConfig(
        rounds=rounds, loss=loss, n_trees_max=5, n_trees_min=2,
        rho_id_min=0.3, rho_id_max=0.7,
        tree=TreeConfig(max_depth=3, num_bins=16),
    )


@pytest.mark.parametrize("loss", ["logistic", "squared"])
def test_scanned_engine_history_equals_loop(loss):
    """Acceptance bar: per-round train/valid metrics within 1e-5 of the
    legacy loop, same recorded schedule, structurally identical trees."""
    x, y, xv, yv = _data(loss)
    cfg = _dyn_cfg(loss)
    m_loop, h_loop = boosting.train_fedgbf(
        x, y, cfg, jax.random.PRNGKey(0), x_valid=xv, y_valid=yv, engine="loop")
    m_scan, h_scan = boosting.train_fedgbf(
        x, y, cfg, jax.random.PRNGKey(0), x_valid=xv, y_valid=yv, engine="scan")

    assert h_loop.engine == "loop" and h_scan.engine == "scan"
    assert h_scan.rounds == h_loop.rounds
    assert h_scan.n_trees == h_loop.n_trees
    np.testing.assert_allclose(h_scan.rho_id, h_loop.rho_id, rtol=1e-6)
    for a, b in zip(h_loop.train, h_scan.train):
        assert set(a) == set(b)
        for k in a:
            assert abs(a[k] - b[k]) < 1e-5, (k, a[k], b[k])
    for a, b in zip(h_loop.valid, h_scan.valid):
        for k in a:
            assert abs(a[k] - b[k]) < 1e-5, (k, a[k], b[k])

    # the dynamic schedule's ragged forests come out structurally identical
    assert m_scan.rounds == m_loop.rounds
    for f_loop, f_scan in zip(m_loop.forests, m_scan.forests):
        np.testing.assert_array_equal(
            np.asarray(f_loop.feature), np.asarray(f_scan.feature))
        np.testing.assert_array_equal(
            np.asarray(f_loop.threshold), np.asarray(f_scan.threshold))
        np.testing.assert_allclose(
            np.asarray(f_loop.leaf_weight), np.asarray(f_scan.leaf_weight),
            rtol=1e-5, atol=1e-6)


def test_goss_sampling_scan_equals_loop():
    """The GOSS rho-mask (DESIGN.md §5) rides the scan engine unchanged:
    per-slot keys stay prefix-stable, so loop and scan draw identical GOSS
    masks from the round's gradients — trees come out bit-identical and the
    history metrics agree like the uniform path's."""
    import dataclasses

    x, y, xv, yv = _data("logistic")
    cfg = dataclasses.replace(_dyn_cfg("logistic"), sampling="goss",
                              goss_top_share=0.5)
    m_loop, h_loop = boosting.train_fedgbf(
        x, y, cfg, jax.random.PRNGKey(0), x_valid=xv, y_valid=yv, engine="loop")
    m_scan, h_scan = boosting.train_fedgbf(
        x, y, cfg, jax.random.PRNGKey(0), x_valid=xv, y_valid=yv, engine="scan")
    for f_loop, f_scan in zip(m_loop.forests, m_scan.forests):
        np.testing.assert_array_equal(
            np.asarray(f_loop.feature), np.asarray(f_scan.feature))
        np.testing.assert_array_equal(
            np.asarray(f_loop.threshold), np.asarray(f_scan.threshold))
    for a, b in zip(h_loop.train, h_scan.train):
        for k in a:
            assert abs(a[k] - b[k]) < 1e-5, (k, a[k], b[k])


def test_goss_changes_masks_but_trains():
    """GOSS actually alters the sampling (different trees than uniform) and
    still learns the signal."""
    import dataclasses

    x, y, _, _ = _data("logistic", seed=9)
    cfg_u = _dyn_cfg("logistic", rounds=3)
    cfg_g = dataclasses.replace(cfg_u, sampling="goss")
    m_u, h_u = boosting.train_fedgbf(x, y, cfg_u, jax.random.PRNGKey(0))
    m_g, h_g = boosting.train_fedgbf(x, y, cfg_g, jax.random.PRNGKey(0))
    assert any(
        not np.array_equal(np.asarray(fu.feature), np.asarray(fg.feature))
        or not np.array_equal(np.asarray(fu.threshold), np.asarray(fg.threshold))
        for fu, fg in zip(m_u.forests, m_g.forests)
    )
    assert h_g.train[-1]["auc"] > 0.8


@pytest.mark.parametrize("engine", ["loop", "scan"])
def test_history_records_every_round_with_eval_gating(engine):
    """Satellite guarantee: with eval_every > 1 the schedule and timing are
    still recorded for EVERY round; only the metric evals are gated."""
    x, y, xv, yv = _data("logistic")
    cfg = _dyn_cfg("logistic", rounds=5)
    _, hist = boosting.train_fedgbf(
        x, y, cfg, jax.random.PRNGKey(1), x_valid=xv, y_valid=yv,
        eval_every=2, engine=engine)
    assert len(hist.n_trees) == cfg.rounds
    assert len(hist.rho_id) == cfg.rounds
    assert len(hist.wall_time_s) == cfg.rounds
    assert hist.n_trees == [5, 5, 4, 3, 2]
    assert hist.rounds == [2, 4, 5]  # evals: every 2nd round + final
    assert len(hist.train) == 3 and len(hist.valid) == 3
    assert hist.total_wall_time_s > 0.0


def test_scanned_engine_eval_gating_matches_loop_values():
    """The gated (in-graph, lax.cond) evals equal the loop's host evals."""
    x, y, _, _ = _data("logistic", seed=3)
    cfg = _dyn_cfg("logistic", rounds=4)
    _, h_loop = boosting.train_fedgbf(
        x, y, cfg, jax.random.PRNGKey(2), eval_every=3, engine="loop")
    _, h_scan = boosting.train_fedgbf(
        x, y, cfg, jax.random.PRNGKey(2), eval_every=3, engine="scan")
    assert h_scan.rounds == h_loop.rounds == [3, 4]
    for a, b in zip(h_loop.train, h_scan.train):
        for k in a:
            assert abs(a[k] - b[k]) < 1e-5


def test_scanned_is_default_engine():
    x, y, _, _ = _data("logistic", seed=5)
    cfg = _dyn_cfg("logistic", rounds=2)
    _, hist = boosting.train_fedgbf(x, y, cfg, jax.random.PRNGKey(0))
    assert hist.engine == "scan"
    with pytest.raises(ValueError, match="unknown engine"):
        boosting.train_fedgbf(x, y, cfg, jax.random.PRNGKey(0), engine="bogus")


def test_static_schedule_single_forest_shape():
    """SecureBoost degeneration (1 tree/round) through the scanned engine."""
    x, y, _, _ = _data("logistic", seed=7)
    cfg = boosting.secureboost_config(rounds=3, tree=TreeConfig(max_depth=2,
                                                                num_bins=8))
    m_loop, h_loop = boosting.train_fedgbf(x, y, cfg, jax.random.PRNGKey(4),
                                           engine="loop")
    m_scan, h_scan = boosting.train_fedgbf(x, y, cfg, jax.random.PRNGKey(4),
                                           engine="scan")
    for f1, f2 in zip(m_loop.forests, m_scan.forests):
        np.testing.assert_array_equal(np.asarray(f1.feature),
                                      np.asarray(f2.feature))
    for a, b in zip(h_loop.train, h_scan.train):
        for k in a:
            assert abs(a[k] - b[k]) < 1e-5


# ---------------------------------------------------------------------------
# NaN-safe binning (missing values)
# ---------------------------------------------------------------------------
def test_bin_edges_nan_safe():
    rng = np.random.default_rng(11)
    x = rng.normal(size=(500, 4)).astype(np.float32)
    x_miss = x.copy()
    x_miss[rng.random((500, 4)) < 0.3] = np.nan  # 30% missing
    edges = binning.quantile_bin_edges(jnp.asarray(x_miss), 16)
    assert np.all(np.isfinite(np.asarray(edges))), "NaNs leaked into edges"
    # edges fit on the observed values only: close to the edges nanquantile
    # of the dense column would give on the same observed subset
    col = x_miss[:, 0]
    obs = col[~np.isnan(col)]
    qs = np.linspace(0, 1, 17)[1:-1]
    np.testing.assert_allclose(
        np.asarray(edges)[0], np.quantile(obs, qs), rtol=1e-4, atol=1e-4)


def test_bin_data_routes_nan_deterministically():
    x = jnp.asarray(np.array([[0.0], [np.nan], [5.0], [np.nan]], np.float32))
    edges = jnp.asarray(np.array([[1.0, 2.0, 3.0]], np.float32))
    b = np.asarray(binning.bin_data(x, edges))
    assert b[1, 0] == binning.NAN_BIN and b[3, 0] == binning.NAN_BIN
    assert b[0, 0] == 0 and b[2, 0] == 3


def test_all_nan_column_degrades_to_unsplittable():
    rng = np.random.default_rng(12)
    x = rng.normal(size=(300, 3)).astype(np.float32)
    x[:, 1] = np.nan  # a completely missing feature
    binned, edges = binning.fit_bin(jnp.asarray(x), 8)
    assert np.all(np.isfinite(np.asarray(edges)))
    assert np.all(np.asarray(binned)[:, 1] == binning.NAN_BIN)


@pytest.mark.parametrize("engine", ["loop", "scan"])
def test_training_with_missing_values(engine):
    """End-to-end: a credit-scoring-shaped table with missing cells trains
    to finite metrics and predicts finite margins on missing-valued input."""
    rng = np.random.default_rng(13)
    n, d = 500, 6
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = (x[:, 0] + 0.5 * x[:, 1] > 0).astype(np.float32)
    x[rng.random((n, d)) < 0.15] = np.nan
    cfg = FedGBFConfig(rounds=3, n_trees_max=3, n_trees_min=2,
                       rho_id_min=0.5, rho_id_max=0.8,
                       tree=TreeConfig(max_depth=3, num_bins=16))
    model, hist = boosting.train_fedgbf(
        jnp.asarray(x), jnp.asarray(y), cfg, jax.random.PRNGKey(5),
        engine=engine)
    assert all(np.isfinite(v) for rep in hist.train for v in rep.values())
    assert hist.train[-1]["loss"] < hist.train[0]["loss"] + 1e-6
    x_test = rng.normal(size=(97, d)).astype(np.float32)
    x_test[rng.random((97, d)) < 0.15] = np.nan
    margin = boosting.predict(model, jnp.asarray(x_test))
    assert np.all(np.isfinite(np.asarray(margin)))


# ---------------------------------------------------------------------------
# Observability (DESIGN.md §12): no host callback unless a tracer records
# ---------------------------------------------------------------------------
def test_default_scan_program_holds_no_host_callback():
    from repro.core import backend as backend_mod

    x, y, _, _ = _data("logistic", n=256, d=5)
    cfg = boosting.dynamic_fedgbf_config(rounds=4)
    binned, _ = binning.fit_bin(x, cfg.tree.num_bins)
    bk = backend_mod.resolve_backend(None)

    def lowered(**kw):
        return boosting._scan_train_program.lower(
            binned, y, None, None, jax.random.PRNGKey(0), cfg, bk, 1, **kw
        ).as_text()

    assert "callback" not in lowered()
    assert "callback" in lowered(ticks=True)  # the recording-tracer program


def test_ticks_change_no_bit_of_the_model():
    from repro.obs import trace

    x, y, _, _ = _data("logistic", n=256, d=5)
    cfg = boosting.dynamic_fedgbf_config(rounds=4)
    m0, h0 = boosting.train_fedgbf(x, y, cfg, jax.random.PRNGKey(3))
    m1, h1 = boosting.train_fedgbf(x, y, cfg, jax.random.PRNGKey(3),
                                   tracer=trace.Tracer())
    for f0, f1 in zip(m0.forests, m1.forests):
        for a, b in zip(jax.tree_util.tree_leaves(f0),
                        jax.tree_util.tree_leaves(f1)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(h0.final_margin, h1.final_margin)
    # without ticks the call's wall is smeared uniformly, as documented
    assert h0.overhead_s == 0.0
    assert len(set(h0.wall_time_s)) == 1 and len(h0.wall_time_s) == 4
    assert [s["rounds"] for s in h0.segments] == \
        [s["rounds"] for s in h1.segments]


SCOPES_PROBE = r"""
import json, re
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import backend as backend_mod, binning, boosting
from repro.launch.mesh import make_vfl_mesh

def scopes(bk, x, y, cfg):
    binned, _ = binning.fit_bin(x, cfg.tree.num_bins)
    hlo = boosting._scan_train_program.lower(
        binned, y, None, None, jax.random.PRNGKey(0), cfg, bk, 1
    ).compile().as_text()
    return sorted({p for op in re.findall(r'op_name="([^"]*)"', hlo)
                   for p in op.split("/") if p.startswith("fedgbf.")})

rng = np.random.default_rng(0)
x = rng.normal(size=(256, 4)).astype(np.float32)
y = (x[:, 0] > 0).astype(np.float32)
cfg = boosting.dynamic_fedgbf_config(rounds=3)
out = {"local": scopes(backend_mod.get_backend("local"), jnp.asarray(x),
                       jnp.asarray(y), cfg)}
mesh = make_vfl_mesh(2, 2)
bk = backend_mod.get_backend("vfl-histogram-sharded", mesh=mesh, tree=cfg.tree)
out["vfl-histogram-sharded"] = scopes(
    bk, jax.device_put(x, NamedSharding(mesh, P("data", "model"))),
    jax.device_put(y, NamedSharding(mesh, P("data"))), cfg)
print(json.dumps(out))
"""

PHASES = {"fedgbf.sample", "fedgbf.grad", "fedgbf.histogram", "fedgbf.split",
          "fedgbf.route", "fedgbf.leaf", "fedgbf.update", "fedgbf.eval"}


def test_compiled_program_names_every_phase_scope():
    """The op metadata of the compiled training program carries every phase
    scope, for one device and for 2 parties x 2 row shards on 4 virtual CPU
    devices; only the sharded backend exchanges."""
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(repo, "src")
    proc = subprocess.run([sys.executable, "-c", SCOPES_PROBE], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    for backend, scopes in got.items():
        assert PHASES <= set(scopes), (backend, scopes)
        assert any(s.startswith("fedgbf.segment.T") for s in scopes)
    assert "fedgbf.exchange" in got["vfl-histogram-sharded"]
    assert "fedgbf.exchange" not in got["local"]
