"""Jitted wrappers: drop-ins for ``core.tree.predict_forest`` (bagging mean
of one forest layer), ``core.tree.predict_packed_weighted`` (whole packed
ensemble in one kernel sweep) and ``core.tree.predict_packed_fused`` (the
same on raw floats).  The kernel runs compiled on TPU and in interpret mode
on every other backend."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.types import PackedEnsemble, TreeArrays, serving_tables
from repro.kernels import interpret_mode
from repro.kernels.ensemble_predict.ensemble_predict import (
    predict_forest_pallas_call,
)


@partial(jax.jit, static_argnames=("max_depth", "tile_n", "raw"))
def _ensemble_pallas(
    feature: jnp.ndarray,    # (n_trees, num_internal)
    threshold: jnp.ndarray,  # (n_trees, num_internal) bins or value-space
    leaf: jnp.ndarray,       # (n_trees, num_leaves)
    scale: jnp.ndarray,      # (n_trees,)
    base: jnp.ndarray,       # () starting margin
    x: jnp.ndarray,          # (n, d) int32 bins or float32 RAW features
    max_depth: int,
    tile_n: int,
    raw: bool,
) -> jnp.ndarray:
    n, _ = x.shape
    n_pad = ((n + tile_n - 1) // tile_n) * tile_n
    x_dtype = jnp.float32 if raw else jnp.int32
    out = predict_forest_pallas_call(
        jnp.pad(x.astype(x_dtype), ((0, n_pad - n), (0, 0))),
        feature.astype(jnp.int32),
        threshold.astype(x_dtype),
        leaf.astype(jnp.float32),
        scale.astype(jnp.float32),
        jnp.asarray(base, jnp.float32),
        max_depth=max_depth,
        raw=raw,
        tile_n=tile_n,
        interpret=interpret_mode(),
    )
    return out[:n]


def predict_forest_pallas(
    trees: TreeArrays,       # stacked: leading axis n_trees
    binned: jnp.ndarray,     # (n, d) int32
    max_depth: int,
    *,
    tile_n: int = 256,
) -> jnp.ndarray:
    """Bagging-mean forest prediction, (n,) float32."""
    n_trees = trees.feature.shape[0]
    scale = jnp.full((n_trees,), 1.0 / n_trees, jnp.float32)
    return _ensemble_pallas(
        trees.feature, trees.threshold, trees.leaf_weight, scale, 0.0, binned,
        max_depth, tile_n, False,
    )


def predict_packed_pallas(
    packed: PackedEnsemble,
    binned: jnp.ndarray,     # (n, d) int32
    *,
    tile_n: int = 256,
) -> jnp.ndarray:
    """Whole-ensemble margin in ONE kernel sweep, (n,) float32.

    The per-tree ``tree_scale`` (= lr / n_trees of the tree's round) folds
    the boosting learning rate and every round's bagging mean into the
    kernel's accumulation, so all ``total_trees`` trees ride a single grid.
    """
    return _ensemble_pallas(
        packed.feature, packed.threshold, packed.leaf_weight,
        packed.tree_scale, packed.base_score, binned, packed.max_depth,
        tile_n, False,
    )


def predict_packed_fused_pallas(
    model,
    x: jnp.ndarray,          # (n, d) float32 RAW features
    *,
    tile_n: int = 256,
) -> jnp.ndarray:
    """Fused bin+traverse ensemble margin in ONE kernel sweep (DESIGN.md §14).

    Takes RAW floats — no ``bin_data`` dispatch — and accepts either a
    ``PackedEnsemble`` or a ``QuantizedEnsemble`` (``serving_tables``
    rewrites thresholds to value space and dequantizes quantized leaves
    in-graph).  The margin is bit-identical to ``tree.predict_packed_fused``
    (same routing, same accumulation order) for all inputs, including
    NaN/±inf rows (sanitized in-kernel).  K-channel leaf tables are not
    supported here — use the vmap fused path.
    """
    feature, thr_value, leaf, scale = serving_tables(model)
    if leaf.ndim != 2:
        raise ValueError(
            "pallas ensemble_predict serves 2-D (trees, leaves) tables; "
            "K-channel ensembles must use impl='fused'"
        )
    return _ensemble_pallas(
        feature, thr_value, leaf, scale, model.base_score, x,
        model.max_depth, tile_n, True,
    )
