"""Operation and byte counts, and the peaks table."""

import numpy as np
import pytest

from bench import counts, peaks, reference


def test_histogram_round_hand_count():
    # 2 trees x 10 sampled rows x 3 features x 3 statistics accumulates;
    # 10 rows x (3 one-byte bins + f32 g and h) read once for the round;
    # 2 trees x 3 features x 4 bins x 3 statistics x 4 bytes written.
    w = counts.histogram_round(n_keep=10, trees=2, d=3, num_bins=4)
    assert w.ops == 2 * 10 * 3 * 3
    assert w.nbytes == 10 * (3 + 8) + 2 * 3 * 4 * 3 * 4


def test_boosting_round_hand_count():
    n, k, t, d, b, depth = 20, 10, 2, 3, 4, 2
    w = counts.boosting_round(n, k, t, d, b, depth)
    hist = counts.histogram_round(k, t, d, b)
    split = t * 3 * d * (b - 1) * 6       # 3 internal nodes, 3 thresholds
    route = t * n * depth
    leaf = t * n * 2
    grad = n * 4
    assert w.ops == hist.ops + split + route + leaf + grad
    assert w.nbytes == hist.nbytes + n * (d + 4 + 8)


def test_traversal_hand_count():
    w = counts.traversal(rows=5, trees=2, depth=3, d=4, calls=2)
    assert w.ops == 5 * 2 * (3 + 2)
    assert w.nbytes == 5 * (4 * 4 + 4) + 2 * 2 * (7 * 8 + 8 * 4 + 4)


def test_wider_bins_cost_two_bytes():
    assert counts.bin_bytes(256) == 1
    assert counts.bin_bytes(257) == 2


def test_counts_do_not_depend_on_the_histogram_implementation():
    """The segment-sum and the Pallas histogram build the same trees of the
    same shapes, and the counts are a function of those shapes alone: both
    implementations are charged the same work."""
    import jax
    import jax.numpy as jnp

    from repro.core import boosting
    from bench.drivers import train_jobs
    from bench import datagen, harness

    files = harness.cell_files("gmsc.train")
    model = dict(files["config"]["model"], rounds=2)
    table = datagen.credit_table(dict(files["config"]["dataset"], rows=1500), 3)
    n, d = table.x_train.shape
    charged = {}
    for backend in ("local", "local-pallas"):
        m, _ = boosting.train_fedgbf(jnp.asarray(table.x_train),
                                     jnp.asarray(table.y_train),
                                     train_jobs.fedgbf_config(model),
                                     jax.random.PRNGKey(0), backend=backend)
        shapes = [(f.feature.shape[0], n) for f in m.forests]
        charged[backend] = [
            counts.boosting_round(rows, k, trees, d, 32, 3)
            for (trees, rows), (_, k) in zip(shapes,
                                             reference.round_plan(model, n))]
        charged[backend + " trees"] = np.concatenate(
            [np.asarray(f.feature) for f in m.forests])
    assert charged["local"] == charged["local-pallas"]
    np.testing.assert_array_equal(charged["local trees"],
                                  charged["local-pallas trees"])


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.least_seconds(1.0, 1.0, "cpu")


def test_least_seconds_takes_the_larger_term():
    p = peaks.peaks("TPU v5 lite")
    assert peaks.least_seconds(p.flops, 0.0, "TPU v5 lite") == pytest.approx(1.0)
    assert peaks.least_seconds(0.0, 2 * p.hbm_bytes_per_s, "TPU v5 lite",
                               chips=2) == pytest.approx(1.0)
