"""Registry-parameterized checkpoint round-trip (guards sidecar drift).

Every registered backend name — including the compressed-transport VFL
backends — trains a tiny model, packs, saves, reloads, and predicts
bit-identically.  New backends land in the registry (DESIGN.md §1), so this
sweep catches any whose models stop round-tripping through the packed
checkpoint sidecar (checkpoint/io.py) the moment they are registered.

VFL backends run on a degenerate 1-party mesh: one CPU device drives the
full shard_map + transport code path (multi-party equivalence is
federation/selftest.py's job).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import io as ckpt_io
from repro.core import backend as backend_mod
from repro.core import boosting
from repro.core.types import FedGBFConfig, PackedEnsemble, TreeConfig, pack_ensemble
from repro.launch.mesh import make_mesh

TREE = TreeConfig(max_depth=2, num_bins=8)
CFG = FedGBFConfig(rounds=2, n_trees_max=3, n_trees_min=2,
                   rho_id_min=0.5, rho_id_max=0.8, tree=TREE)


def _build(name):
    if name.startswith("vfl"):
        mesh = make_mesh((1, 1), ("data", "model"))
        return backend_mod.get_backend(name, mesh=mesh, tree=TREE)
    return backend_mod.get_backend(name)


def _data(n=300, d=4, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = ((x[:, 0] - 0.6 * x[:, 1] + rng.normal(0, 0.4, n)) > 0).astype(np.float32)
    x_test = rng.normal(size=(97, d)).astype(np.float32)
    return jnp.asarray(x), jnp.asarray(y), jnp.asarray(x_test)


@pytest.mark.parametrize("name", backend_mod.available_backends())
def test_checkpoint_roundtrip_every_backend(name, tmp_path):
    x, y, x_test = _data()
    backend = _build(name)
    ctx = jax.set_mesh(make_mesh((1, 1), ("data", "model"))) \
        if name.startswith("vfl") else None
    if ctx is not None:
        with ctx:
            model, _ = boosting.train_fedgbf(x, y, CFG, jax.random.PRNGKey(0),
                                             backend=backend)
    else:
        model, _ = boosting.train_fedgbf(x, y, CFG, jax.random.PRNGKey(0),
                                         backend=backend)

    packed = pack_ensemble(model)
    path = str(tmp_path / f"ckpt-{name}")
    ckpt_io.save_ensemble(path, packed)
    loaded = ckpt_io.load_ensemble(path)
    assert isinstance(loaded, PackedEnsemble)
    # sidecar metadata survives exactly
    assert loaded.round_offsets == packed.round_offsets
    assert loaded.loss == packed.loss
    assert loaded.max_depth == packed.max_depth
    assert loaded.learning_rate == packed.learning_rate
    np.testing.assert_array_equal(np.asarray(loaded.tree_scale),
                                  np.asarray(packed.tree_scale))
    # and prediction is bit-identical through the round-trip
    np.testing.assert_array_equal(
        np.asarray(boosting.predict(packed, x_test)),
        np.asarray(boosting.predict(loaded, x_test)),
    )


def test_checkpoint_roundtrip_goss_config(tmp_path):
    """GOSS is a config knob, not a backend: its models round-trip too."""
    x, y, x_test = _data(seed=1)
    cfg = dataclasses.replace(CFG, sampling="goss")
    model, _ = boosting.train_fedgbf(x, y, cfg, jax.random.PRNGKey(0))
    path = str(tmp_path / "ckpt-goss")
    ckpt_io.save_ensemble(path, model)
    loaded = ckpt_io.load_ensemble(path)
    np.testing.assert_array_equal(
        np.asarray(boosting.predict(model, x_test, impl="loop")),
        np.asarray(boosting.predict(loaded, x_test)),
    )
