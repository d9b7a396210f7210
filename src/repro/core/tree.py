"""Level-wise round-native forest construction (Alg. 2 over a whole round).

TPU adaptation (DESIGN.md §2): instead of growing nodes one at a time from a
pending-split queue, we grow complete trees *level by level* with static
shapes — one histogram pass per level covers the whole frontier, the routing
update compares each node's whole feature column at once
(``level_go_right``), and the depth loop is unrolled (max_depth is static
and small, paper uses 3).

Round-native engine (DESIGN.md §9): FedGBF's N trees of a round are ONE
parallel unit — they share (g, h) and differ only in their masks (eq. 4) —
so ``build_round`` builds the whole round with the tree axis *explicit* in
every provider (histograms take and return a leading ``(T, ...)`` axis)
instead of closing per-tree builders over a ``jax.vmap``.  That seam is what
enables shared-root caching (one unmasked level-0 histogram + per-tree
deltas), frontier compaction for deep trees (a static ``max_active_nodes``
budget with dead nodes masked out of histograms and the party exchange), and
ONE federated collective per level carrying the ``(T, active, d_party, B,
3)`` payload.  ``build_tree`` is the T = 1 special case.

The providers are injectable via a ``core.backend.TreeBackend``: the
centralized path uses ``core.histogram.compute_round_histogram``; the
federated path passes shard_map wrappers that compute per-party shard
histograms and reassemble them (federation/aggregator.py). Because
histograms are additive and reassembly is exact, both paths produce
*identical* trees — the paper's losslessness claim, asserted in
tests/test_federation.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import histogram as hist_mod
from repro.core import split as split_mod
from repro.core.types import PackedEnsemble, TreeArrays, TreeConfig
from repro.obs import compiles as compiles_mod

#: nodes of a level routed in one straight-line block (``level_go_right``)
ROUTE_UNROLL = 8


def traverse_level(
    binned: jnp.ndarray,
    idx: jnp.ndarray,
    feature: jnp.ndarray,
    threshold: jnp.ndarray,
) -> jnp.ndarray:
    """The ONE node-traversal gather body: each sample reads its current
    node's (feature, threshold) and goes right iff its bin value is strictly
    above the threshold; unsplit nodes (feature == -1, threshold == B) route
    every sample left.

    Shared by builder routing (``route_local``), tree prediction
    (``predict_tree``), and — via the latter — the ``ensemble_predict``
    kernel oracle, so the routing semantics live in exactly one place.

    Args:
      binned: (n, d) int32.
      idx: (n,) int32 within-level node index.
      feature / threshold: (width,) int32 — the level's nodes only.
    Returns:
      (n,) int32 next-level node index ``idx * 2 + go_right``.
    """
    rows = jnp.arange(binned.shape[0])
    f = feature[idx]    # (n,)
    t = threshold[idx]  # (n,)
    fv = binned[rows, jnp.clip(f, 0, None)]
    go_right = (f >= 0) & (fv > t)
    return idx * 2 + go_right.astype(jnp.int32)


def route_local(binned: jnp.ndarray, assign: jnp.ndarray, decision) -> jnp.ndarray:
    """Centralized routing: one ``traverse_level`` step over the frontier."""
    return traverse_level(binned, assign, decision.feature, decision.threshold)


def traverse_level_values(
    x: jnp.ndarray,
    idx: jnp.ndarray,
    feature: jnp.ndarray,
    thr_value: jnp.ndarray,
) -> jnp.ndarray:
    """Raw-float twin of ``traverse_level`` — the fused bin+traverse body.

    ``types.float_thresholds`` rewrites bin-space thresholds into value
    space (``bin(v) <= t  <=>  v <= edges[f, t]``), so serving compares the
    raw feature float directly and the separate binning dispatch disappears.
    NaN features route left (``NaN > thr`` is False) — exactly the reserved
    ``binning.NAN_BIN = 0`` semantics; ±inf compares past every finite edge,
    matching the extreme bins.  Leaf routing is bit-identical to binning
    followed by ``traverse_level``.

    Args:
      x: (n, d) float32 RAW features (not binned).
      idx: (n,) int32 within-level node index.
      feature: (width,) int32; thr_value: (width,) float32 value-space.
    Returns:
      (n,) int32 next-level node index.
    """
    rows = jnp.arange(x.shape[0])
    f = feature[idx]
    t = thr_value[idx]
    fv = x[rows, jnp.clip(f, 0, None)]
    go_right = (f >= 0) & (fv > t)
    return idx * 2 + go_right.astype(jnp.int32)


def level_go_right(
    binned: jnp.ndarray,
    idx: jnp.ndarray,
    feature: jnp.ndarray,
    threshold: jnp.ndarray,
) -> jnp.ndarray:
    """The round routing body: which rows go right at one tree level.

    ``traverse_level``'s rule with the tree axis explicit: a row goes right
    iff its node splits (feature >= 0) and the row's bin of the node's
    feature is strictly above the node's threshold.

    Each node's split column is read once as a contiguous row of the
    feature-major table, compared lane-dense over n, and every row keeps
    the compare of its own node.  Nothing is gathered per element, and the
    loop over the level's nodes accumulates into one (T, n) array.  Per
    level on a TPU v5e at T 5, n 105,000, d 10 (``benchmarks/route_probe.py``):
    0.036 ms at width 2, 0.047 at 4, 0.24 at 64, 0.70 at 128, 4.49 at 256,
    against 11.9-15.7 ms for per-row gathers at every width from 2 to 256.

    The node loop is a ``fori_loop`` unrolled ``ROUTE_UNROLL`` nodes at a
    time, so a level of up to ``ROUTE_UNROLL`` nodes is straight-line code
    and a wider one a loop of such blocks: compile time stops growing with
    the width.  Fully unrolled, a depth-8 round (T 5, n 700,000, d 28, B 256)
    compiled for a v5e in 111 s, 83 s of it the routing of its 255 nodes;
    in blocks of 8, in 28 s.  ``fedgbf_route_levels_total`` counts each
    traced level.

    Args:
      binned: (n, d) int32 binned features.
      idx: (T, n) int32 per-tree within-level node index.
      feature / threshold: (T, width) int32 — the level's nodes per tree.
    Returns:
      (T, n) bool go-right decisions.
    """
    counter = compiles_mod.installed()
    if counter is not None:
        counter.route_levels.inc()
    cols = binned.T                               # (d, n) feature-major
    f_safe = jnp.clip(feature, 0, None)
    width = feature.shape[1]

    def node(j, go_right):
        f = jax.lax.dynamic_index_in_dim(feature, j, 1, keepdims=False)
        fs = jax.lax.dynamic_index_in_dim(f_safe, j, 1, keepdims=False)
        t = jax.lax.dynamic_index_in_dim(threshold, j, 1, keepdims=False)
        right = (f[:, None] >= 0) & (cols[fs] > t[:, None])   # (T, n)
        return go_right | ((idx == j) & right)

    return jax.lax.fori_loop(0, width, node, jnp.zeros(idx.shape, bool),
                             unroll=min(width, ROUTE_UNROLL))


def route_local_round(binned, assign, decision) -> jnp.ndarray:
    """Centralized round routing: (T, n) next-level node indices."""
    go_right = level_go_right(
        binned, assign, decision.feature, decision.threshold
    )
    return assign * 2 + go_right.astype(jnp.int32)


def _derive_round_hist(per_tree_fn):
    """Lift a per-tree histogram provider to the round contract (vmap over
    the (weight, assign) tree axis — the explicit seam stays, only this
    provider's implementation batches implicitly).  Shared-root caching
    (``root_delta_rows``) routes through ``root_histogram_via_delta`` with
    the per-tree provider as the delta accumulator, so ad-hoc per-tree
    backends support the full round contract."""

    def fn(binned, g, h, weight, assign, num_nodes, num_bins,
           root_delta_rows=0, level=0):
        if root_delta_rows:
            return hist_mod.root_histogram_via_delta(
                binned, g, h, weight, num_bins, root_delta_rows,
                base_tree_fn=per_tree_fn,
            )
        return jax.vmap(
            lambda w, a: per_tree_fn(binned, g, h, w, a, num_nodes, num_bins)
        )(weight, assign)

    return fn


def _derive_round_choose(per_tree_fn):
    return lambda hist, fmask: jax.vmap(per_tree_fn)(hist, fmask)


def _derive_round_route(per_tree_fn):
    def fn(binned, assign, decision):
        return jax.vmap(lambda a, d: per_tree_fn(binned, a, d))(assign, decision)

    return fn


def _derive_round_leaf(per_tree_fn):
    def fn(g, h, weight, assign, num_leaves):
        return jax.vmap(
            lambda w, a: per_tree_fn(g, h, w, a, num_leaves)
        )(weight, assign)

    return fn


def _round_providers(cfg: TreeConfig, backend):
    """Resolve the round-native providers: a backend's ``round_*`` provider
    wins; a per-tree provider lifts via vmap; None selects the centralized
    round-native default."""
    hist_fn = choose_fn = route_fn = leaf_fn = child_fn = None
    if backend is not None:
        hist_fn = backend.round_histogram_fn
        if hist_fn is None and backend.histogram_fn is not None:
            hist_fn = _derive_round_hist(backend.histogram_fn)
        choose_fn = backend.round_choose_fn
        if choose_fn is None and backend.choose_fn is not None:
            choose_fn = _derive_round_choose(backend.choose_fn)
        route_fn = backend.round_route_fn
        if route_fn is None and backend.route_fn is not None:
            route_fn = _derive_round_route(backend.route_fn)
        leaf_fn = backend.round_leaf_fn
        if leaf_fn is None and backend.leaf_fn is not None:
            leaf_fn = _derive_round_leaf(backend.leaf_fn)
        child_fn = backend.round_child_histogram_fn
        if child_fn is None and backend.child_histogram_fn is not None:
            child_fn = _derive_round_hist(backend.child_histogram_fn)
    if hist_fn is None:
        hist_fn = hist_mod.compute_round_histogram
    if choose_fn is None:
        choose_fn = lambda hist, fm: split_mod.choose_splits_round(hist, fm, cfg)
    if route_fn is None:
        route_fn = route_local_round
    if leaf_fn is None:
        leaf_fn = hist_mod.round_leaf_stats
    if cfg.hist_subtraction and child_fn is None:
        # Any round histogram provider adapts into the child-only provider
        # (the mask/halve staging runs inside its program, so federated
        # transports ship the half-width payload); backends override only to
        # fuse the staging (local-pallas).
        child_fn = hist_mod.as_round_child_fn(hist_fn)
    return hist_fn, child_fn, choose_fn, route_fn, leaf_fn


def build_round(
    binned: jnp.ndarray,
    g: jnp.ndarray,
    h: jnp.ndarray,
    sample_mask: jnp.ndarray,
    feature_mask: jnp.ndarray,
    cfg: TreeConfig,
    backend=None,
    root_delta_rows: int = 0,
) -> tuple[TreeArrays, jnp.ndarray]:
    """Build ALL T trees of one round; returns (stacked trees, (T, n) assign).

    The round-native forest engine (DESIGN.md §9).  Every provider carries
    the tree axis explicitly — histograms take and return ``(T, ...)``
    operands (``histogram.compute_round_histogram`` contract) — so on the
    federated path each level is ONE party collective shipping the whole
    round's ``(T, active, d_party, B, 3)`` payload, and the level-0 pass can
    share work across trees (shared-root caching).

    Every sample (masked or not) is routed in every tree so the caller can
    update y_hat on the full training set; masked-out samples simply do not
    contribute to histograms or leaf weights.

    Each level's phases run under ``jax.named_scope`` — ``fedgbf.histogram``
    (compaction, accumulation, sibling derivation), ``fedgbf.split``,
    ``fedgbf.route`` — and the leaves under ``fedgbf.leaf``: compile-time
    op metadata that a profiler capture reports per device op (DESIGN.md
    §12); the program's arithmetic is unchanged.

    Args:
      binned: (n, d) int32 binned features (the *local feature shard* on the
        federated path — d is then d_party, not d_global).
      g, h: (n,) float32 derivatives w.r.t. y_hat^(m-1), shared by the round.
      sample_mask: (T, n) float32 per-tree weights — P_m(j) of eq. 4.
      feature_mask: (T, d) bool per-tree masks — Q_m(j) of eq. 4.
      cfg: static tree config.  ``hist_subtraction`` runs the §6 sibling
        pipeline; ``max_active_nodes`` bounds the live frontier per level
        (§9 compaction) for deep trees.
      backend: a ``core.backend.TreeBackend`` (DESIGN.md §1); None =
        centralized-local round-native defaults.
      root_delta_rows: static shared-root delta-buffer width (> 0 enables
        the level-0 ``shared − delta`` derivation; the engines drive it
        from the rho_id schedule — see ``TreeConfig.shared_root``).

    Returns:
      (trees, assign): ``trees`` is a stacked ``TreeArrays`` with leading
      tree axis; ``assign`` (T, n) is every sample's leaf index per tree.
    """
    hist_fn, child_fn, choose_fn, route_fn, leaf_fn = _round_providers(
        cfg, backend
    )
    T, n = sample_mask.shape
    assign = jnp.zeros((T, n), dtype=jnp.int32)  # within-level node index
    t_rows = jnp.arange(T, dtype=jnp.int32)[:, None]

    features, thresholds, gains = [], [], []
    live = None          # (T, width) next-level liveness (compacted levels)
    prev_hist = None     # (T, A_prev, d, B, 3), slot space
    prev_id = prev_w = None
    prev_A = None
    prev_table = None    # (T, width_prev + 1) slot-of-node, None = identity
    for level in range(cfg.max_depth):
        width = 2 ** level
        A = cfg.active_width(level)
        compacted = A < width
        with jax.named_scope("fedgbf.histogram"):
            if compacted:
                # Frontier compaction (§9): gather live nodes into dense
                # slots.  ``order`` is a stable permutation putting live
                # node ids first (ascending), so slot k < live_count holds
                # the k-th live node; overflow beyond the budget and dead
                # nodes route through the full-width level arrays as
                # unsplit (-1) entries.
                order = jnp.argsort(~live, axis=1)
                slot_node = order[:, :A].astype(jnp.int32)       # (T, A)
                live_count = jnp.sum(live, axis=1).astype(jnp.int32)
                slot_valid = (jnp.arange(A, dtype=jnp.int32)[None, :]
                              < live_count[:, None])
                # node -> slot table; dead nodes map to the trash id A
                # (their samples are weight-masked out of the histogram
                # pass), invalid slots scatter into a dummy row that is
                # never read.
                scatter_node = jnp.where(slot_valid, slot_node, width)
                table = jnp.full((T, width + 1), A, jnp.int32)
                table = table.at[t_rows, scatter_node].set(
                    jnp.broadcast_to(
                        jnp.arange(A, dtype=jnp.int32)[None, :], (T, A)
                    )
                )
                slot_assign = jnp.take_along_axis(table, assign, axis=1)
                w_level = sample_mask * (slot_assign < A).astype(
                    sample_mask.dtype)
                id_level = jnp.minimum(slot_assign, A - 1)
            else:
                slot_node = table = slot_valid = None
                w_level = sample_mask
                id_level = assign

            if cfg.hist_subtraction and level >= 1:
                # Subtraction pipeline (§6): accumulate only the left
                # children at parent-slot width and derive every right
                # sibling from the carried parent histograms; under
                # compaction the interleaved child-slot frontier is then
                # gathered into this level's dense slots (dead children
                # never reach the histogram/exchange).
                side = (assign % 2).astype(jnp.int32)
                cslot = prev_id * 2 + side      # child-slot space, 2*prev_A
                left = child_fn(binned, g, h, prev_w, cslot, prev_A,
                                cfg.num_bins, level=level)
                # (T, 2*prev_A, ...)
                sib = hist_mod.derive_sibling(prev_hist, left)
                if compacted:
                    # A live slot's parent is itself a valid previous-level
                    # slot (liveness requires a split parent); invalid slots
                    # gather clipped junk that the decision scatter
                    # discards.  The budget is monotone in the level width,
                    # so a compacted level's PREVIOUS level may be
                    # uncompacted (prev_table is None, parent slot ==
                    # parent node) but never vice versa.
                    pslot = (
                        jnp.take_along_axis(prev_table, slot_node // 2, axis=1)
                        if prev_table is not None else slot_node // 2
                    )
                    cidx = jnp.clip(pslot * 2 + slot_node % 2, 0,
                                    2 * prev_A - 1)
                    hist = jnp.take_along_axis(
                        sib, cidx[:, :, None, None, None], axis=1
                    )
                else:
                    hist = sib
            else:
                kw = {"level": level}
                if level == 0 and root_delta_rows:
                    # Shared-root caching (§9): the provider derives every
                    # root as shared − delta inside its own program, so
                    # federated transports still ship the standard
                    # per-tree payload.
                    kw["root_delta_rows"] = root_delta_rows
                hist = hist_fn(binned, g, h, w_level, id_level, A,
                               cfg.num_bins, **kw)

        with jax.named_scope("fedgbf.split"):
            decision = choose_fn(hist, feature_mask)          # (T, A) fields
            gain_pos = jnp.maximum(decision.gain, 0.0)
            if compacted:
                feat = jnp.where(slot_valid, decision.feature, -1)
                thr = jnp.where(slot_valid, decision.threshold, cfg.num_bins)
                gn = jnp.where(slot_valid, gain_pos, 0.0)
                feature_lvl = (
                    jnp.full((T, width), -1, jnp.int32)
                    .at[t_rows, slot_node].set(feat)
                )
                threshold_lvl = (
                    jnp.full((T, width), cfg.num_bins, jnp.int32)
                    .at[t_rows, slot_node].set(thr)
                )
                gain_lvl = (
                    jnp.zeros((T, width), jnp.float32)
                    .at[t_rows, slot_node].set(gn)
                )
                decision_lvl = split_mod.SplitDecision(
                    feature=feature_lvl, threshold=threshold_lvl, gain=gain_lvl
                )
            else:
                feature_lvl, threshold_lvl, gain_lvl = (
                    decision.feature, decision.threshold, gain_pos
                )
                decision_lvl = decision
            features.append(feature_lvl)
            thresholds.append(threshold_lvl)
            gains.append(gain_lvl)
        with jax.named_scope("fedgbf.route"):
            assign = route_fn(binned, assign, decision_lvl)

            next_level = level + 1
            if (next_level < cfg.max_depth
                    and cfg.active_width(next_level) < 2 ** next_level):
                # Liveness for the next (compacted) level: a child is live iff
                # its parent split AND it holds weighted samples.  Counts go
                # through the leaf provider so sample-sharded backends psum to
                # the global count (a cheap (n,) pass, no party collective —
                # weights and routing are party-replicated).
                # count is the LAST stat channel at any K (index 2 when K = 1)
                counts = leaf_fn(g, h, sample_mask, assign,
                                 2 ** next_level)[..., -1]
                live = (counts > 0) & jnp.repeat(feature_lvl >= 0, 2, axis=1)
            else:
                live = None
        prev_hist, prev_id, prev_w = hist, id_level, w_level
        prev_A, prev_table = A, table

    # Leaf statistics: aggregate (G, H, count) per leaf over masked samples.
    # In the VFL protocol the active party owns g, h and the final routing
    # in plaintext, so leaf weights are computed locally (Alg. 2 step 14);
    # the leaf provider is only overridden when samples are sharded over the
    # data axis (psum of the additive stats, no party gather).
    with jax.named_scope("fedgbf.leaf"):
        # (T, L, 2K+1) statistics, (T, L[, K]) weights
        leaf_hist = leaf_fn(g, h, sample_mask, assign, cfg.num_leaves)
        weights = split_mod.leaf_weights(leaf_hist, cfg)

        trees = TreeArrays(
            feature=jnp.concatenate(features, axis=1),
            threshold=jnp.concatenate(thresholds, axis=1),
            gain=jnp.concatenate(gains, axis=1),
            leaf_weight=weights,
        )
    return trees, assign


def build_tree(
    binned: jnp.ndarray,
    g: jnp.ndarray,
    h: jnp.ndarray,
    sample_mask: jnp.ndarray,
    feature_mask: jnp.ndarray,
    cfg: TreeConfig,
    backend=None,
) -> tuple[TreeArrays, jnp.ndarray]:
    """Build one tree — the T = 1 special case of ``build_round``.

    Args:
      sample_mask: (n,) float32 — P_m(j) of eq. 4.
      feature_mask: (d,) bool — Q_m(j) of eq. 4 (local slice when federated).
      backend: a ``core.backend.TreeBackend`` (DESIGN.md §1); None =
        centralized-local defaults.  (The historical per-provider kwargs
        ``histogram_fn``/``choose_fn``/``route_fn``/``leaf_fn`` are gone —
        build an ad-hoc ``TreeBackend`` instead.)

    Returns:
      (tree, leaf_assign_for_all_samples) without the tree axis.
    """
    trees, assign = build_round(
        binned, g, h, sample_mask[None], feature_mask[None], cfg,
        backend=backend,
    )
    return jax.tree_util.tree_map(lambda a: a[0], trees), assign[0]


def predict_tree(tree: TreeArrays, binned: jnp.ndarray, max_depth: int) -> jnp.ndarray:
    """Route samples through one tree and return leaf weights.

    Args:
      tree: TreeArrays (single tree, no leading batch axis).
      binned: (n, d) int32 — binned with the training edges.
      max_depth: static tree depth.
    Returns:
      (n,) float32 raw tree output — (n, K) when the leaf table carries K
      values per leaf (K-channel objectives).
    """
    n = binned.shape[0]
    idx = jnp.zeros(n, dtype=jnp.int32)
    for level in range(max_depth):
        offset = 2**level - 1
        width = 2**level
        idx = traverse_level(
            binned, idx,
            tree.feature[offset:offset + width],
            tree.threshold[offset:offset + width],
        )
    return tree.leaf_weight[idx]


def predict_trees(trees: TreeArrays, binned: jnp.ndarray, max_depth: int) -> jnp.ndarray:
    """Per-tree margins of a stacked forest: (n_trees, n) float32.

    The single vmapped traversal shared by forest prediction, training-time
    validation, and ``PackedEnsemble`` inference (DESIGN.md §3) — every
    prediction consumer funnels through this one program.
    """
    return jax.vmap(lambda tr: predict_tree(tr, binned, max_depth))(trees)


def predict_forest(trees: TreeArrays, binned: jnp.ndarray, max_depth: int) -> jnp.ndarray:
    """Mean over a stacked forest (bagging combiner g of Alg. 1 line 7)."""
    return jnp.mean(predict_trees(trees, binned, max_depth), axis=0)


def _margin_shape(n: int, packed_leaf_weight: jnp.ndarray) -> tuple:
    """Margin accumulator shape from the packed leaf table: (n,) for the
    2-D (trees, leaves) table, (n, K) for the K-channel 3-D one."""
    if packed_leaf_weight.ndim == 2:
        return (n,)
    return (n, packed_leaf_weight.shape[-1])


def predict_packed(packed: PackedEnsemble, binned: jnp.ndarray) -> jnp.ndarray:
    """Raw-margin prediction from the packed layout, bit-for-bit equal to the
    legacy per-round loop (asserted in tests/test_packed.py).

    Per-round sums are accumulated segment-by-segment over the *static*
    ``round_offsets`` boundaries: each round's ``(n_trees_r, n)`` per-tree
    block is a transient of that segment only — the full ``(total_trees, n)``
    per-tree matrix of the original one-shot vmapped formulation is never
    materialised.  That matrix is what made the packed path 0.34x the loop
    on CPU (BENCH_predict.json history); the segmented accumulation restores
    loop-parity while keeping the packed layout's uniform storage.  The
    traversal-count trade-off lives in the combiner choice: this path is the
    bit-exact one; ``predict_packed_weighted`` streams all trees through one
    scanned body (O(1) compile cost), and the Pallas ``ensemble_predict``
    kernel fuses the whole ensemble on TPU.
    """
    out = jnp.full(
        _margin_shape(binned.shape[0], packed.leaf_weight),
        packed.base_score, dtype=jnp.float32,
    )
    for r in range(packed.rounds):
        s, e = packed.round_offsets[r], packed.round_offsets[r + 1]
        seg = TreeArrays(
            feature=packed.feature[s:e], threshold=packed.threshold[s:e],
            gain=packed.gain[s:e], leaf_weight=packed.leaf_weight[s:e],
        )
        per_tree = predict_trees(seg, binned, packed.max_depth)  # (k_r, n)
        out = out + packed.learning_rate * jnp.mean(per_tree, axis=0)
    return out


def predict_packed_weighted(packed: PackedEnsemble, binned: jnp.ndarray) -> jnp.ndarray:
    """Single-pass combiner: ``base + sum_t tree_scale[t] * tree_t(x)``.

    Algebraically identical to ``predict_packed`` (scale = lr / n_trees per
    round) but implemented as a ``lax.scan`` over the packed tree axis with a
    running accumulator: one compiled tree body regardless of ensemble size,
    and the (total_trees, n) per-tree matrix is never materialised — the
    scan's streaming accumulation is the jnp analogue of what the Pallas
    ``ensemble_predict`` kernel does across its tree grid axis.  Prefer this
    for serving; use ``predict_packed`` when bit-exact parity with the
    training-time per-round evaluation matters.
    """
    n = binned.shape[0]

    def body(out, xs):
        feature, threshold, leaf_weight, scale = xs
        tr = TreeArrays(feature=feature, threshold=threshold,
                        gain=jnp.zeros_like(leaf_weight[:0]),
                        leaf_weight=leaf_weight)
        return out + scale * predict_tree(tr, binned, packed.max_depth), None

    out, _ = jax.lax.scan(
        body,
        jnp.full(_margin_shape(n, packed.leaf_weight), packed.base_score,
                 dtype=jnp.float32),
        (packed.feature, packed.threshold, packed.leaf_weight,
         packed.tree_scale),
    )
    return out


def predict_tree_values(
    x: jnp.ndarray,
    feature: jnp.ndarray,
    thr_value: jnp.ndarray,
    leaf: jnp.ndarray,
    max_depth: int,
) -> jnp.ndarray:
    """``predict_tree`` on RAW floats via the value-space threshold table.

    Args:
      x: (n, d) float32 raw features.
      feature: (num_internal,) int32; thr_value: (num_internal,) float32.
      leaf: (num_leaves[, K]) float32.
    Returns:
      (n[, K]) float32 leaf values — leaf-index-identical to binning + the
      bin-space ``predict_tree``.
    """
    n = x.shape[0]
    idx = jnp.zeros(n, dtype=jnp.int32)
    for level in range(max_depth):
        offset = 2**level - 1
        width = 2**level
        idx = traverse_level_values(
            x, idx,
            feature[offset:offset + width],
            thr_value[offset:offset + width],
        )
    return leaf[idx]


def predict_packed_fused(model, x: jnp.ndarray) -> jnp.ndarray:
    """Fused bin+traverse serving margin: ONE program on raw floats.

    The scan structure mirrors ``predict_packed_weighted`` — streaming
    ``base + sum_t tree_scale[t] * tree_t(x)`` accumulation, one compiled
    tree body — but the per-sample binning pass (a ``searchsorted`` over
    every feature column) is gone: thresholds were rewritten into value
    space once at table-build time (``types.serving_tables``).  Accepts a
    ``PackedEnsemble`` or a ``QuantizedEnsemble`` (leaf table dequantized
    in-graph).  Leaf routing, and therefore the margin, is bit-identical to
    ``bin_data`` + ``predict_packed_weighted`` for every input, including
    NaN (routes left, the NAN_BIN semantics) and ±inf rows.
    """
    from repro.core.types import serving_tables

    feature, thr_value, leaf, tree_scale = serving_tables(model)
    n = x.shape[0]

    def body(out, xs):
        f, t, lw, scale = xs
        return out + scale * predict_tree_values(
            x, f, t, lw, model.max_depth
        ), None

    out, _ = jax.lax.scan(
        body,
        jnp.full(_margin_shape(n, leaf), model.base_score, dtype=jnp.float32),
        (feature, thr_value, leaf, tree_scale),
    )
    return out
