"""Random-forest layer: the bagging base learner of FedGBF (Alg. 1 lines 3-7).

The N trees of a round share (g, h) — all fit the same boosting residual —
and differ only in their sampling masks P_m(j), Q_m(j) (eq. 4). TPU
adaptation: the per-tree parallelism the paper gets from multi-worker FATE
becomes the round-native forest engine (``core.tree.build_round``,
DESIGN.md §9) — one XLA program builds the whole layer with the tree axis
explicit in every provider, and the sampling matrices become boolean masks
so shapes stay static.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import tree as tree_mod
from repro.core.types import TreeArrays, TreeConfig


def sample_masks(
    rng: jax.Array, n: int, d: int, n_trees: int, rho_id, rho_feat: float
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Exact-count subsampling masks per tree.

    The paper samples exactly n_m(j) = n * rho_id rows and d_m(j) = d * rho_feat
    features without replacement (eq. 4); ``random.permutation(n) < k`` places
    exactly k ones uniformly at random.

    ``rho_id`` may be a python float (host path) — the keep-count is then
    rounded on the host exactly as the legacy loop always did.

    Returns:
      sample_mask: (n_trees, n) float32 in {0, 1}
      feature_mask: (n_trees, d) bool
    """
    n_keep = max(1, int(round(n * rho_id)))
    return sample_masks_counts(rng, n, d, n_trees, n_keep,
                               feature_keep_count(d, rho_feat))


def feature_keep_count(d: int, rho_feat: float) -> int:
    """The ONE rounding rule for d_m(j) = d * rho_feat (eq. 4).

    Loop/scan mask equivalence depends on every call site sharing this exact
    expression — both engines and the GOSS path resolve d_keep through here.
    """
    return max(1, int(round(d * rho_feat)))


def masks_from_keys(
    keys: jnp.ndarray, n: int, d: int, n_keep, d_keep
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Exact-count masks from pre-derived per-tree keys (batched).

    ``keys`` is (K, 2) uint32; ``n_keep`` is a scalar or a (K,) vector of
    keep-counts (may be traced).  One batched draw for any number of trees —
    the scanned engine precomputes ALL its steps' masks through this in a
    single vmap (a batched sort is far cheaper than per-step sorts).
    """
    n_keep = jnp.broadcast_to(jnp.asarray(n_keep), keys.shape[:1])

    def one(k, nk):
        ks, kf = jax.random.split(k)
        smask = (jax.random.permutation(ks, n) < nk).astype(jnp.float32)
        fmask = jax.random.permutation(kf, d) < d_keep
        return smask, fmask

    return jax.vmap(one)(keys, n_keep)


def fold_in_keys(rng: jax.Array, indices: jnp.ndarray) -> jnp.ndarray:
    """Per-tree keys via ``random.fold_in(rng, t)`` — *prefix-stable* in the
    tree count (unlike ``random.split(rng, k)``, whose keys depend on k), so
    any subset of tree slots draws exactly the masks a full-round draw
    produces.  The scanned training engine (DESIGN.md §4) relies on this to
    stay mask-for-mask equivalent to the legacy per-round loop."""
    return jax.vmap(lambda t: jax.random.fold_in(rng, t))(indices)


def sample_masks_counts(
    rng: jax.Array, n: int, d: int, n_trees: int, n_keep, d_keep
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``sample_masks`` with explicit keep-counts; counts may be traced."""
    return masks_from_keys(
        fold_in_keys(rng, jnp.arange(n_trees)), n, d, n_keep, d_keep
    )


def goss_counts(n: int, rho_id: float, top_share: float) -> tuple[int, int]:
    """Split the round's rho_id sample budget into GOSS (top, random) counts.

    ``n_keep = round(n * rho_id)`` samples total (the exact host expression
    the uniform path uses), of which ``round(n_keep * top_share)`` are the
    largest-|g| samples and the rest are drawn uniformly from the remainder.
    Clamped so at least one random sample is always drawn (the amplification
    factor divides by it) and the top set never swallows the whole dataset.
    """
    n_keep = max(1, min(n, int(round(n * rho_id))))
    n_top = max(0, min(int(round(n_keep * top_share)), n_keep - 1, n - 1))
    n_rand = max(1, min(n_keep - n_top, n - n_top))
    return n_top, n_rand


def goss_masks_from_keys(
    keys: jnp.ndarray, g: jnp.ndarray, d: int, n_top, n_rand, d_keep: int
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """GOSS weight masks from prefix-stable per-tree keys (DESIGN.md §5).

    Gradient-based one-side sampling (LightGBM; the subsampling lever
    SecureBoost+ carries into VFL): every tree keeps the ``n_top``
    largest-|g| samples at weight 1 (ties broken toward the lower sample
    index — ``argsort`` is stable), then draws exactly ``n_rand`` of the
    remaining samples uniformly at weight ``(n - n_top) / n_rand``, which
    keeps the histogram (g, h, count) sums unbiased estimates of the
    full-data sums over the small-gradient region.

    The returned ``smask`` is therefore a *weight* vector, not 0/1 — every
    consumer already multiplies stats by the mask (``core/histogram.py``), so
    the tree builders and both training engines run unchanged.  ``keys`` uses
    the same ``fold_in`` per-slot discipline as ``masks_from_keys`` (and the
    same (sample, feature) key split, so the feature masks are identical to
    the uniform path's draw for the same keys); the top-|g| set is
    deterministic in ``g`` and shared by all trees of the round.

    Args:
      keys: (K, 2) uint32 per-tree keys (``fold_in_keys``).
      g: (n,) first-order gradients of the round.
      n_top, n_rand: scalars or (K,) vectors; may be traced.
      d_keep: static feature keep-count.
    """
    n = g.shape[0]
    n_top = jnp.broadcast_to(jnp.asarray(n_top), keys.shape[:1])
    n_rand = jnp.broadcast_to(jnp.asarray(n_rand), keys.shape[:1])
    if g.ndim > 1:
        # K-channel objectives: rank by the per-sample L1 gradient norm
        # (reduces to |g| at K = 1, where the branch below stays bit-exact).
        g = jnp.abs(g).sum(axis=-1)
    order = jnp.argsort(-jnp.abs(g))  # stable: ties toward lower index
    rank = jnp.zeros(n, jnp.int32).at[order].set(jnp.arange(n, dtype=jnp.int32))

    def one(k, nt, nr):
        ks, kf = jax.random.split(k)
        is_top = rank < nt
        u = jax.random.uniform(ks, (n,))
        u = jnp.where(is_top, 2.0, u)  # sentinel > any uniform: tops excluded
        thr = jnp.sort(u)[jnp.clip(nr - 1, 0, n - 1)]  # nr-th smallest
        is_rand = (~is_top) & (u <= thr)
        amplify = (n - nt).astype(jnp.float32) / jnp.maximum(nr, 1).astype(
            jnp.float32
        )
        smask = is_top.astype(jnp.float32) + is_rand.astype(jnp.float32) * amplify
        fmask = jax.random.permutation(kf, d) < d_keep
        return smask, fmask

    return jax.vmap(one)(keys, n_top, n_rand)


def goss_masks(
    rng: jax.Array, g: jnp.ndarray, d: int, n_trees: int,
    n_top: int, n_rand: int, d_keep: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``goss_masks_from_keys`` over a round key (the legacy-loop entry)."""
    return goss_masks_from_keys(
        fold_in_keys(rng, jnp.arange(n_trees)), g, d, n_top, n_rand, d_keep
    )


def _forest_per_tree(binned, g, h, sample_mask, feature_mask, cfg, backend=None,
                     root_delta_rows=0):
    """Un-jitted core: build the whole round, return per-tree predictions.

    One ``tree.build_round`` call (DESIGN.md §9) — the tree axis is explicit
    in every provider, not closed over by a vmap.  Returns (trees,
    per_tree_pred) with per_tree_pred (n_trees, n) — the raw leaf outputs of
    every tree on the full training set, *before* any bagging combiner, so
    the caller owns the combine.
    """
    trees, assign = tree_mod.build_round(
        binned, g, h, sample_mask, feature_mask, cfg, backend=backend,
        root_delta_rows=root_delta_rows,
    )
    with jax.named_scope("fedgbf.update"):
        if trees.leaf_weight.ndim == 3:  # K-channel leaf table: (T, L, K)
            per_tree_pred = jnp.take_along_axis(
                trees.leaf_weight, assign[..., None], axis=1
            )  # (T, n, K)
        else:
            per_tree_pred = jnp.take_along_axis(trees.leaf_weight, assign,
                                                axis=1)
    return trees, per_tree_pred


@partial(jax.jit, static_argnames=("cfg", "backend", "root_delta_rows"))
def build_forest(
    binned: jnp.ndarray,
    g: jnp.ndarray,
    h: jnp.ndarray,
    sample_mask: jnp.ndarray,
    feature_mask: jnp.ndarray,
    cfg: TreeConfig,
    backend=None,
    root_delta_rows: int = 0,
) -> tuple[TreeArrays, jnp.ndarray]:
    """Build all trees of one forest layer as one round (tree axis explicit).

    Args:
      binned: (n, d) shared binned features.
      g, h: (n,) shared derivatives (all trees of round m fit y_hat^(m-1)).
      sample_mask: (n_trees, n); feature_mask: (n_trees, d).
      backend: ``core.backend.TreeBackend`` execution providers (hashable,
        rides through jit as one static argument); None = centralized-local.
        Reuse one backend instance across rounds to reuse the jit cache.
      root_delta_rows: static shared-root delta-buffer width (DESIGN.md §9;
        0 = direct level-0 pass).  The training engines derive it from the
        rho_id schedule when ``cfg.shared_root`` is set.

    Returns:
      (trees, train_pred): trees is a stacked TreeArrays (leading axis
      n_trees); train_pred (n,) is the bagging-averaged raw output on the
      full training set, ready for the boosting update
      y_hat^(m) = y_hat^(m-1) + lr * train_pred (Alg. 1 line 8).
    """
    trees, per_tree_pred = _forest_per_tree(
        binned, g, h, sample_mask, feature_mask, cfg, backend, root_delta_rows
    )
    train_pred = jnp.mean(per_tree_pred, axis=0)
    return trees, train_pred


@partial(jax.jit, static_argnames=("cfg", "backend", "root_delta_rows"))
def build_forest_per_tree(
    binned: jnp.ndarray,
    g: jnp.ndarray,
    h: jnp.ndarray,
    sample_mask: jnp.ndarray,
    feature_mask: jnp.ndarray,
    cfg: TreeConfig,
    backend=None,
    root_delta_rows: int = 0,
) -> tuple[TreeArrays, jnp.ndarray]:
    """Like ``build_forest`` but returns *per-tree* predictions (n_trees, n).

    The scanned training engine consumes this: it owns the bagging combine
    (and the validation-set prediction reuses the same tree stack), so the
    builder must not reduce over the tree axis itself.
    """
    return _forest_per_tree(
        binned, g, h, sample_mask, feature_mask, cfg, backend, root_delta_rows
    )
