"""Gradient/hessian histogram accumulation (Alg. 2 steps 6-8).

This is the compute hot-spot of every histogram GBDT (and the quantity the
VFL protocol ships between parties), so it has three implementations:

* ``compute_histogram``      — portable jnp ``segment_sum`` path (default on CPU),
* ``kernels/histogram``      — the Pallas TPU kernel (one-hot matmul on the MXU),
  selected via ``histogram_dispatch("pallas")``,
* ``kernels/histogram/ref.py`` — the oracle the kernel is tested against
  (re-exports this module's function).

Layout: ``hist[node, feature, bin, stat]`` with ``stat = (sum_g, sum_h, count)``
for K = 1 objectives and ``stat = (g_1..g_K, h_1..h_K, count)`` — ``2K + 1``
channels, count LAST — for K-channel objectives (DESIGN.md §11).  Every
provider derives the channel extent from the gradient rank (``(n,)`` vs
``(n, K)``), so the K = 1 path is byte-for-byte the historical 3-channel
one.  Histograms are *additive* in samples, which is what makes both the
data-parallel ``psum`` and the VFL per-party decomposition exact.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NUM_STATS = 3  # sum_g, sum_h, count

#: Optional trace-time recorder of histogram row-passes (the round engine's
#: level-0 accounting; benchmarks/ci_guard.py and tests/test_round_engine.py
#: probe through it).  Like ``compress.MessageMeter``, entries accumulate
#: once per *trace* — set it, ``jax.eval_shape`` exactly one program, read
#: it, reset it.  None (the default) skips recording entirely.
PASS_METER: Optional[list] = None


def _record_pass(tag: str, rows: int, trees: int) -> None:
    if PASS_METER is not None:
        PASS_METER.append({"tag": tag, "rows": int(rows), "trees": int(trees)})


def _stack_stats(g: jnp.ndarray, h: jnp.ndarray, weight: jnp.ndarray):
    """Per-row stat channels: (n, 3) for (n,) gradients — the historical
    K = 1 expression, unchanged — else (n, 2K+1) with the count LAST."""
    if g.ndim == 1:
        return jnp.stack([g * weight, h * weight, weight], axis=-1)  # (n, 3)
    w = weight[:, None]
    return jnp.concatenate([g * w, h * w, w], axis=-1)  # (n, 2K+1)


def _stack_round_stats(g: jnp.ndarray, h: jnp.ndarray, weight: jnp.ndarray):
    """Round-native twin of ``_stack_stats``: (T, n) weights folded flat to
    (T*n, 2K+1) stat rows (K = 1 path byte-identical to the historical)."""
    t, n = weight.shape
    if g.ndim == 1:
        return jnp.stack(
            [g[None] * weight, h[None] * weight, weight], axis=-1
        ).reshape(t * n, NUM_STATS)  # (T*n, 3)
    w = weight[..., None]  # (T, n, 1)
    return jnp.concatenate(
        [g[None] * w, h[None] * w, w], axis=-1
    ).reshape(t * n, 2 * g.shape[-1] + 1)


def compute_histogram(
    binned: jnp.ndarray,
    g: jnp.ndarray,
    h: jnp.ndarray,
    weight: jnp.ndarray,
    assign: jnp.ndarray,
    num_nodes: int,
    num_bins: int,
) -> jnp.ndarray:
    """Accumulate (sum_g, sum_h, count) per (node, feature, bin).

    Args:
      binned: (n, d) int32 bin indices in [0, num_bins).
      g, h:   (n,) float32 first/second-order derivatives — or (n, K) for
        K-channel objectives, widening the stat axis to 2K+1.
      weight: (n,) float32 0/1 sample-subsampling mask (P_m(j) of eq. 4).
      assign: (n,) int32 node assignment at the current level, in [0, num_nodes).
      num_nodes: static frontier width (2**level).
      num_bins:  static B.

    Returns:
      (num_nodes, d, num_bins, 2K+1) float32 histogram (3 channels at K = 1).
    """
    n, d = binned.shape
    data = _stack_stats(g, h, weight)  # (n, 2K+1)
    ids = assign[None, :] * num_bins + binned.T  # (d, n)

    def per_feature(ids_col: jnp.ndarray) -> jnp.ndarray:
        return jax.ops.segment_sum(data, ids_col, num_segments=num_nodes * num_bins)

    hist = jax.vmap(per_feature)(ids)  # (d, num_nodes * B, 2K+1)
    return hist.reshape(
        d, num_nodes, num_bins, data.shape[-1]
    ).transpose(1, 0, 2, 3)


def compute_histogram_onehot(
    binned: jnp.ndarray,
    g: jnp.ndarray,
    h: jnp.ndarray,
    weight: jnp.ndarray,
    assign: jnp.ndarray,
    num_nodes: int,
    num_bins: int,
) -> jnp.ndarray:
    """MXU-shaped formulation: histogram as a dense one-hot matmul.

    This is the mathematical statement of the TPU adaptation (DESIGN.md §2):
    ``hist = onehot(node*B + bin)^T @ [g, h, 1]`` per feature. The Pallas
    kernel tiles exactly this contraction; this jnp version exists so the
    algebraic identity itself is testable without Pallas.
    """
    n, d = binned.shape
    data = _stack_stats(g, h, weight)  # (n, 2K+1)
    ids = assign[:, None] * num_bins + binned  # (n, d)
    onehot = jax.nn.one_hot(ids, num_nodes * num_bins, dtype=data.dtype)  # (n, d, NB)
    hist = jnp.einsum("ndk,ns->dks", onehot, data)  # (d, NB, 2K+1)
    return hist.reshape(
        d, num_nodes, num_bins, data.shape[-1]
    ).transpose(1, 0, 2, 3)


# ---------------------------------------------------------------------------
# Round-native providers (DESIGN.md §9): the tree axis is explicit
# ---------------------------------------------------------------------------
def compute_round_histogram(
    binned: jnp.ndarray,
    g: jnp.ndarray,
    h: jnp.ndarray,
    weight: jnp.ndarray,
    assign: jnp.ndarray,
    num_nodes: int,
    num_bins: int,
    *,
    root_delta_rows: int = 0,
    level: int = 0,
) -> jnp.ndarray:
    """Round-native histogram: all T trees of a round in ONE segment pass.

    The trees of a FedGBF round share ``(binned, g, h)`` and differ only in
    their masks (eq. 4), so the tree axis folds into the segment ids — one
    ``segment_sum`` over ``T·n`` rows replaces T per-tree passes (what the
    per-tree vmap formulation lowers to anyway, stated here as the explicit
    contract every round provider satisfies).

    Args:
      binned: (n, d) int32 shared binned features.
      g, h: (n,) float32 shared derivatives — or (n, K), widening the stat
        axis to 2K+1.
      weight: (T, n) float32 per-tree sample masks/weights.
      assign: (T, n) int32 per-tree node assignment in [0, num_nodes).
      num_nodes: static frontier (slot) width.
      num_bins: static B.
      root_delta_rows: when > 0 (level 0 only, ``num_nodes == 1``), compute
        the roots via shared-root caching: ONE unmasked histogram plus a
        per-tree delta over at most this many masked-out rows
        (``root_histogram_via_delta``).  0 = direct masked accumulation.
      level: static tree level of this pass.  Unused here; part of the
        round-provider contract so stateful transports (the quantized
        exchange's stochastic-rounding keys) can derive per-level state —
        ``num_nodes`` stopped being a level proxy once subtraction and
        compaction made several levels share a width.

    Returns:
      (T, num_nodes, d, num_bins, 2K+1) float32.
    """
    if root_delta_rows:
        return root_histogram_via_delta(
            binned, g, h, weight, num_bins, root_delta_rows
        )
    n, d = binned.shape
    t = weight.shape[0]
    _record_pass("round", n, t)
    data = _stack_round_stats(g, h, weight)  # (T*n, 2K+1)
    # segment id = ((tree * num_nodes) + node) * B + bin, per feature column.
    tree_node = (
        jnp.arange(t, dtype=jnp.int32)[:, None] * num_nodes + assign
    )  # (T, n)
    ids = tree_node.reshape(1, t * n) * num_bins + jnp.tile(
        binned.T, (1, t)
    )  # (d, T*n)

    def per_feature(ids_col: jnp.ndarray) -> jnp.ndarray:
        return jax.ops.segment_sum(
            data, ids_col, num_segments=t * num_nodes * num_bins
        )

    hist = jax.vmap(per_feature)(ids)  # (d, T*nodes*B, 2K+1)
    return hist.reshape(
        d, t, num_nodes, num_bins, data.shape[-1]
    ).transpose(1, 2, 0, 3, 4)


def root_histogram_via_delta(
    binned: jnp.ndarray,
    g: jnp.ndarray,
    h: jnp.ndarray,
    weight: jnp.ndarray,
    num_bins: int,
    n_rows: int,
    base_tree_fn=None,
) -> jnp.ndarray:
    """Shared-root caching (DESIGN.md §9): per-tree root histograms as
    ``shared − delta(masked-out rows)``.

    Histograms are linear in the sample weights, so the root of tree t is
    ``hist(w_t) = hist(1) − hist(1 − w_t)``; the first term is ONE unmasked
    pass shared by the whole round, and the second touches only the rows
    tree t masked out — gathered into a static ``(T, n_rows)`` buffer, so
    the level-0 row volume drops from ``T·n`` to ``n + T·n_rows``.

    The caller guarantees ``n_rows`` covers every tree's masked-out count
    (the engines' rho_id >= 0.5 crossover implies ``n − n_keep <= n // 2``)
    and that weights are 0/1 (uniform sampling; GOSS's amplified weights
    would leave ``1 − w`` nonzero on kept rows outside the buffer, so the
    engines route GOSS rounds through the direct pass).  Surplus buffer
    entries land on kept rows whose delta weight ``1 − w`` is 0 — inert.

    Args:
      weight: (T, n) float32 0/1 per-tree masks.
      n_rows: static delta-buffer width (rows per tree).
      base_tree_fn: per-tree histogram provider used for BOTH the shared
        full-n pass and the gathered per-tree delta rows
        (``compute_histogram`` signature); None = the portable segment-sum
        path.  Routing the dominant shared pass through the same provider
        keeps e.g. local-pallas on its fused kernel for the whole level-0
        derivation.

    Returns:
      (T, 1, d, B, 3) float32 — same contract as the direct level-0 call.
    """
    if base_tree_fn is None:
        base_tree_fn = compute_histogram
    t, n = weight.shape
    n_rows = min(n_rows, n)
    # The shared pass is the one full-n pass the feature makes dominant, so
    # it runs on the SAME provider as the deltas (the fused Pallas kernel
    # for local-pallas, not the portable fallback); recorded explicitly
    # since it bypasses compute_round_histogram's meter hook.
    _record_pass("round", n, 1)
    shared = base_tree_fn(
        binned, g, h, jnp.ones((n,), jnp.float32),
        jnp.zeros((n,), jnp.int32), 1, num_bins,
    )[None]  # (1, 1, d, B, 3)
    _record_pass("root_delta", n_rows, t)
    # Stable sort puts masked-out rows (w == 0) first, ascending row index.
    order = jnp.argsort(weight > 0, axis=1)[:, :n_rows]  # (T, n_rows)
    sub_w = 1.0 - jnp.take_along_axis(weight, order, axis=1)  # (T, n_rows)
    zeros = jnp.zeros((n_rows,), jnp.int32)

    def one_delta(rows, w_t):
        return base_tree_fn(
            binned[rows], g[rows], h[rows], w_t, zeros, 1, num_bins
        )

    delta = jax.vmap(one_delta)(order, sub_w)  # (T, 1, d, B, 3)
    return shared - delta


def as_round_child_fn(round_histogram_fn):
    """Round-native twin of ``as_child_fn``: adapt any (T, ...) histogram
    provider into the subtraction pipeline's left-child-only provider.
    ``assign`` is the current level's (T, n) slot assignment (width
    ``2 * num_parents``); odd slots are weight-masked out and the ids halve
    to parent slots, inside whatever program the provider runs (so federated
    round transports ship the half-width payload)."""

    def fn(binned, g, h, weight, assign, num_parents, num_bins, *, level=0):
        left_w = weight * (1 - (assign % 2)).astype(weight.dtype)
        return round_histogram_fn(binned, g, h, left_w, assign // 2,
                                  num_parents, num_bins, level=level)

    return fn


def round_leaf_stats(
    g: jnp.ndarray,
    h: jnp.ndarray,
    weight: jnp.ndarray,
    assign: jnp.ndarray,
    num_leaves: int,
) -> jnp.ndarray:
    """Round-native ``leaf_stats``: (T, n) masks/assignment → (T, leaves, 3)
    in one flat stat-channel ``segment_sum`` (tree folded into segments)."""
    t, n = weight.shape
    data = _stack_round_stats(g, h, weight)
    ids = (
        jnp.arange(t, dtype=jnp.int32)[:, None] * num_leaves + assign
    ).reshape(t * n)
    out = jax.ops.segment_sum(data, ids, num_segments=t * num_leaves)
    return out.reshape(t, num_leaves, data.shape[-1])


# ---------------------------------------------------------------------------
# Sibling-subtraction pipeline (DESIGN.md §6)
# ---------------------------------------------------------------------------
def as_child_fn(histogram_fn):
    """Adapt any histogram provider into the *child-only* provider of the
    subtraction pipeline: accumulate only the samples routed to LEFT
    children, at half-frontier width indexed by parent.

    The child provider keeps the histogram signature except that ``assign``
    is the CURRENT level's assignment (width ``2 * num_parents``) and the
    frontier argument is ``num_parents``: left children have even ``assign``
    (routing is ``assign * 2 + go_right``), so masking odd-assign samples to
    weight 0 and halving the ids yields exactly the left-child histogram of
    each parent.  Because the adaptation happens *inside* whatever program
    ``histogram_fn`` runs (a shard_map collective, a quantized transport…),
    every transport's wire payload shrinks to the half-width frontier for
    free.  The Pallas training kernel has a fused variant instead
    (``kernels/histogram/ops.compute_histogram_pallas_child``) so the
    mask/halve staging never touches HBM.
    """

    def fn(binned, g, h, weight, assign, num_parents, num_bins):
        left_w = weight * (1 - (assign % 2)).astype(weight.dtype)
        return histogram_fn(binned, g, h, left_w, assign // 2,
                            num_parents, num_bins)

    return fn


def derive_sibling(parent_hist: jnp.ndarray, left_hist: jnp.ndarray) -> jnp.ndarray:
    """Sibling-subtraction combiner: ``right = parent − left``, interleaved
    back to the full frontier.

    Args:
      parent_hist: (..., P, d, B, 3) — the previous level's histograms
        (optionally with a leading tree axis — the round engine passes
        (T, P, d, B, 3)); after routing, node ``p``'s samples are exactly
        the union of its children, so additivity gives
        ``parent == left + right`` (bit-exact only in exact arithmetic;
        float reassociation is why the direct pass stays the reference
        oracle).
      left_hist: (..., P, d, B, 3) — left-child histograms indexed by
        parent (``as_child_fn`` / ``as_round_child_fn``).

    Returns:
      (..., 2P, d, B, 3) with node ``2p`` = left child, ``2p + 1`` = derived
      right sibling, matching the routing order ``assign * 2 + go_right``.
    """
    right = parent_hist - left_hist
    *batch, p, d, b, s = left_hist.shape
    return jnp.stack([left_hist, right], axis=-4).reshape(
        *batch, 2 * p, d, b, s
    )


def leaf_stats(
    g: jnp.ndarray,
    h: jnp.ndarray,
    weight: jnp.ndarray,
    assign: jnp.ndarray,
    num_leaves: int,
) -> jnp.ndarray:
    """Aggregate (G, H, count) per leaf: the leaf-statistics fast path.

    A direct three-channel ``segment_sum`` over the final assignment —
    bit-identical to (and replacing) the old pseudo-feature
    ``compute_histogram`` call, which built an (n, 1) zeros operand and a
    4-D reshape just to read back ``hist[:, 0, 0, :]``.

    Returns (num_leaves, 2K+1) float32 (3 channels at K = 1).
    """
    data = _stack_stats(g, h, weight)  # (n, 2K+1)
    return jax.ops.segment_sum(data, assign, num_segments=num_leaves)


def histogram_dispatch(impl: str = "segment"):
    """Select a histogram implementation by name.

    ``"pallas"`` is the Pallas kernel (id and stats staging fused in-kernel;
    what ``local-pallas`` runs); ``"pallas-child"`` is its child-only
    variant for the subtraction pipeline (left-mask and parent ids formed
    in-kernel).  The ``round-*`` family serves the round-native contract
    (DESIGN.md §9, explicit (T, ...) tree axis): ``"round-segment"`` is the
    portable fold-the-tree-into-the-segment-ids path; ``"pallas-round
    [-child]"`` put the tree on the kernel grid (what ``local-pallas``'
    round providers run).
    """
    if impl == "segment":
        return compute_histogram
    if impl == "onehot":
        return compute_histogram_onehot
    if impl == "round-segment":
        return compute_round_histogram
    pallas = {
        "pallas": "compute_histogram_pallas",
        "pallas-child": "compute_histogram_pallas_child",
        "pallas-round": "compute_round_histogram_pallas",
        "pallas-round-child": "compute_round_histogram_pallas_child",
    }
    if impl in pallas:
        from repro.kernels.histogram import ops as _ops

        return getattr(_ops, pallas[impl])
    raise ValueError(f"unknown histogram impl {impl!r}")
