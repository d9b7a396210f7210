"""Pallas TPU kernel: fused forest inference (bagging combiner, Alg. 1 l.7).

TPU adaptation: tree traversal is pointer-chasing on GPU (per-thread gather
chains); TPUs have no efficient per-lane gather, so every gather becomes a
one-hot select and a lane reduction on the VPU:

  * node lookup  — select(idx == node) over the tree's node table
  * feature read — select(f == column) over the feature tile
  * leaf lookup  — select(idx == leaf) over the leaf table

Each reduction has exactly one nonzero term, so it is exact in f32 and the
margin is bit-identical to the gather traversal (``core.tree``).  The depth
loop is unrolled (max_depth static, paper uses 3), and a per-tree *scale*
accumulates across the tree grid axis — the innermost, sequential axis, so
the output block stays resident while one kernel evaluates the entire
forest without materialising per-tree outputs in HBM.  Scale =
1/num_trees reproduces the bagging mean of a single forest layer; scale =
lr/n_trees(round) evaluates a whole PackedEnsemble — every boosting round
of every forest — in the same single sweep (DESIGN.md §3).

Layout: per-tree tables arrive as (n_trees, 1, width) and the per-tree
scale as (n_trees, 1, 1), so each block's last two dims equal the array's;
the margin is a (n_pad, 1) column, initialised to ``base``.

VMEM per step (tile_n=256, d<=64, leaves=8, f32): features 64 KiB,
selects <= 256*64*4 = 64 KiB, tree tables a few KiB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _pick(sel: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    """Row-wise one-hot select: (T, W) bool x (1, W) -> (T, 1)."""
    return jnp.sum(jnp.where(sel, table, jnp.zeros_like(table)), axis=1,
                   keepdims=True)


def _predict_kernel(x_ref, feat_ref, thr_ref, leaf_ref, scale_ref, base_ref,
                    out_ref, *, max_depth: int, raw: bool):
    """Grid step: one sample tile (axis 0) x one tree (axis 1).

    x_ref: (tile_n, d) — int32 bins, or RAW float32 features when ``raw``;
    feat_ref: (1, 1, num_internal) int32 — this tree's split features;
    thr_ref: (1, 1, num_internal) — int32 bin thresholds, or float32
        value-space thresholds (``types.float_thresholds``) when ``raw``;
    leaf_ref: (1, 1, num_leaves) float32;
    scale_ref: (1, 1, 1) float32 — this tree's contribution weight;
    base_ref: (1, 1) float32 — the margin the accumulation starts from;
    out_ref: (tile_n, 1) float32 accumulated margin.

    With ``raw`` the tile is sanitized up front (DESIGN.md §14): NaN maps to
    -FLOAT_MAX (compares ``<=`` every threshold -> routes left, the NAN_BIN
    semantics) and ±inf clips to ±FLOAT_MAX (still beyond every finite
    edge), so routing matches the binned oracle for ALL inputs.
    """

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.broadcast_to(base_ref[...], out_ref.shape)

    x = x_ref[...]
    if raw:
        fmax = jnp.float32(jnp.finfo(jnp.float32).max)
        x = jnp.where(jnp.isnan(x), -fmax, jnp.clip(x, -fmax, fmax))
    tile_n, d = x.shape
    feats = feat_ref[0]   # (1, num_internal)
    thrs = thr_ref[0]
    leaves = leaf_ref[0]  # (1, num_leaves)
    node_iota = jax.lax.broadcasted_iota(jnp.int32, (tile_n, feats.shape[1]), 1)
    col_iota = jax.lax.broadcasted_iota(jnp.int32, (tile_n, d), 1)
    idx = jnp.zeros((tile_n, 1), jnp.int32)
    for level in range(max_depth):
        sel = node_iota == idx + (2**level - 1)  # this level's node of each row
        f = _pick(sel, feats)                    # (T, 1) feature id, -1 = leaf
        t = _pick(sel, thrs)
        fv = _pick(col_iota == f, x)             # (T, 1) value of feature f
        go_right = jnp.logical_and(f >= 0, fv > t)
        idx = idx * 2 + go_right.astype(jnp.int32)
    leaf_iota = jax.lax.broadcasted_iota(jnp.int32, (tile_n, leaves.shape[1]), 1)
    pred = _pick(leaf_iota == idx, leaves)
    out_ref[...] = out_ref[...] + scale_ref[0] * pred


def predict_forest_pallas_call(
    x: jnp.ndarray,          # (n_pad, d) int32 bins or float32 RAW features
    feature: jnp.ndarray,    # (n_trees, num_internal) int32
    threshold: jnp.ndarray,  # (n_trees, num_internal) int32 / float32
    leaf: jnp.ndarray,       # (n_trees, num_leaves) float32
    scale: jnp.ndarray,      # (n_trees,) float32 per-tree contribution
    base: jnp.ndarray,       # () float32 starting margin
    *,
    max_depth: int,
    raw: bool,
    tile_n: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """Traverse + combine the whole ensemble in one kernel: returns the
    (n_pad,) margin ``base + sum_t scale[t] * tree_t(x)``, accumulated in
    tree order.  ``raw`` selects the fused bin+traverse variant."""
    n_pad, d = x.shape
    n_trees, num_internal = feature.shape
    num_leaves = leaf.shape[1]
    table = lambda w: pl.BlockSpec((1, 1, w), lambda i, j: (j, 0, 0))
    out = pl.pallas_call(
        functools.partial(_predict_kernel, max_depth=max_depth, raw=raw),
        grid=(n_pad // tile_n, n_trees),
        in_specs=[
            pl.BlockSpec((tile_n, d), lambda i, j: (i, 0)),
            table(num_internal),
            table(num_internal),
            table(num_leaves),
            table(1),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tile_n, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
        name="fedgbf_ensemble_predict",
    )(
        x,
        feature[:, None, :],
        threshold[:, None, :],
        leaf[:, None, :],
        scale.reshape(n_trees, 1, 1),
        jnp.reshape(base, (1, 1)),
    )
    return out[:, 0]
