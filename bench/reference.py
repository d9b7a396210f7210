"""Plain reference for the FedGBF cells, written from the paper (Algs. 1-3).

It imports nothing of the program.  It serves three ends:

* ``check_training`` verifies a trained ensemble against the algorithm, tree
  by tree, with the program's own earlier trees taken as given (the margin
  every round starts from is the reference's float64 sum of the program's
  earlier trees).  Per tree it recomputes, in float64, the histogram of every
  node under the program's routing, the best split gain among the tree's
  sampled features, and the Newton leaf weights.  Four numbers come out:

    - ``edge_gap``: the program's quantile bin edges against the reference's
      (largest gap, relative, floored at 1);
    - ``split_regret``: how far the gain of a split the program chose lies
      below the best gain at that node, relative to the tree's root gain
      (0 for an optimal choice; a near-tie taken the other way reads tiny);
    - ``leaf_gap``: the program's leaf weights against -G / (H + lambda) of
      the rows its routing puts in each leaf, relative to that leaf or to
      the tree's median leaf, whichever is larger;
    - ``margin_gap``: the program's final training margin against the
      reference's sum of the program's trees (absolute, in logits).

  The tree's bins are those the model states: its edges are part of the
  answer under check, and are checked themselves by ``edge_gap``.
* ``train`` is the same algorithm as a trainer, in float32, in bfloat16 (the
  control), or with one of the faults a broken program could have planted.
* ``scores`` traverses an ensemble made by the benchmark and returns the
  served probabilities, in float64 or, as the control, in bfloat16.
"""

from __future__ import annotations

import math

import numpy as np
from ml_dtypes import bfloat16

BIG = 1e30  # stands for "no such split / structure differs" in a reading


# --------------------------------------------------------------------------
# the Dynamic FedGBF schedule (paper section 3.2.2, k = speed)
# --------------------------------------------------------------------------
def _phase(m: int, rounds: int, speed: float):
    if rounds <= 1:
        return None
    horizon = speed * (rounds - 1)
    if m > horizon + 1:
        return None
    return math.pi * (m - 1) / (2.0 * horizon)


def n_trees(model: dict, m: int) -> int:
    """Trees in round ``m`` (1-based): cosine decay from max to min."""
    lo, hi = float(model["trees_min"]), float(model["trees_max"])
    ph = _phase(m, model["rounds"], model["trees_speed"])
    if model["rounds"] <= 1:
        v = hi
    elif ph is None:
        v = lo
    else:
        v = lo + (hi - lo) * math.cos(ph)
    return max(1, int(round(v)))


def rho_id(model: dict, m: int) -> float:
    """Row sample rate of round ``m`` (1-based): sine increase."""
    lo, hi = float(model["rho_id_min"]), float(model["rho_id_max"])
    ph = _phase(m, model["rounds"], model["rho_id_speed"])
    if model["rounds"] <= 1 or ph is None:
        return hi
    return lo + (hi - lo) * math.sin(ph)


def round_plan(model: dict, n: int) -> list:
    """[(trees, sampled rows per tree)] for every round."""
    return [(n_trees(model, m), max(1, int(round(n * rho_id(model, m)))))
            for m in range(1, model["rounds"] + 1)]


# --------------------------------------------------------------------------
# binning and sampling
# --------------------------------------------------------------------------
def quantile_edges(x: np.ndarray, num_bins: int) -> np.ndarray:
    """(d, B-1) float32 interior quantile edges, linear interpolation in
    float32, NaN ignored (an all-NaN column gives edges of 0)."""
    qs = np.linspace(0.0, 1.0, num_bins + 1, dtype=np.float32)[1:-1]
    edges = np.zeros((x.shape[1], num_bins - 1), np.float32)
    for j in range(x.shape[1]):
        col = np.sort(x[:, j][~np.isnan(x[:, j])]).astype(np.float32)
        if col.size == 0:
            continue
        q = qs * (np.float32(col.size) - np.float32(1))
        lo, hi = np.floor(q), np.ceil(q)
        w_hi = q - lo
        w_lo = np.float32(1) - w_hi
        edges[j] = col[lo.astype(np.int64)] * w_lo + col[hi.astype(np.int64)] * w_hi
    return edges


def bin_values(x: np.ndarray, edges: np.ndarray) -> np.ndarray:
    """Bin id = number of edges strictly below the value; NaN -> bin 0."""
    out = np.empty(x.shape, np.int32)
    for j in range(x.shape[1]):
        out[:, j] = np.searchsorted(edges[j], x[:, j], side="left")
        out[np.isnan(x[:, j]), j] = 0
    return out


def tree_masks(key, model: dict, n: int, d: int) -> list:
    """Per round, the (trees, n) bool row masks and (trees, d) bool feature
    masks of exactly n_keep rows and d_keep features, drawn without
    replacement: round keys by successive splits of ``key``, one key per
    tree by folding in its slot, then one split into (rows, features)."""
    plan = tuple(round_plan(model, n))
    rows, cols = _draw_masks(key, plan, n, d)
    d_keep = max(1, int(round(d * model["rho_feat"])))
    out, s = [], 0
    for trees, n_keep in plan:
        out.append((np.asarray(rows[s:s + trees]) < n_keep,
                    np.asarray(cols[s:s + trees]) < d_keep))
        s += trees
    return out


def _draw_masks(key, plan, n, d):
    """All trees' row and column permutations in one compiled call."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def draw(key):
        keys, rng = [], key
        for trees, _ in plan:
            rng, k_round = jax.random.split(rng)
            keys.append(jax.vmap(lambda t: jax.random.fold_in(k_round, t))(
                jnp.arange(trees)))
        pair = jax.vmap(jax.random.split)(jnp.concatenate(keys))
        return (jax.vmap(lambda k: jax.random.permutation(k, n))(pair[:, 0]),
                jax.vmap(lambda k: jax.random.permutation(k, d))(pair[:, 1]))

    return draw(key)


# --------------------------------------------------------------------------
# one tree
# --------------------------------------------------------------------------
def _bf16(a) -> np.ndarray:
    return np.asarray(a, np.float32).astype(bfloat16).astype(np.float32)


def _node_stats(b, g, h, assign, width, num_bins) -> np.ndarray:
    """(width, d, B, 3) float64 sums of (g, h, 1) by node, feature, bin."""
    d = b.shape[1]
    ids = ((assign[:, None] * d + np.arange(d)[None, :]) * num_bins + b).ravel()
    size = width * d * num_bins
    stats = [np.bincount(ids, weights=np.repeat(v, d), minlength=size)
             for v in (g, h)]
    stats.append(np.bincount(ids, minlength=size).astype(np.float64))
    return np.stack(stats, -1).reshape(width, d, num_bins, 3)


def _gains(stats, fmask, model, precision) -> np.ndarray:
    """(width, d, B) split gains, -inf where the split is not allowed."""
    lam, gamma, mcw = model["lambda"], model["gamma"], model["min_child_weight"]
    if precision == "float64":
        cum = np.cumsum(stats, axis=2)
    elif precision == "bfloat16":
        cum = _bf16(np.cumsum(_bf16(stats), axis=2, dtype=np.float32))
    else:
        cum = np.cumsum(stats.astype(np.float32), axis=2, dtype=np.float32)
    gl, hl = cum[..., 0], cum[..., 1]
    gt, ht = cum[:, :, -1:, 0], cum[:, :, -1:, 1]
    gr, hr = gt - gl, ht - hl
    with np.errstate(divide="ignore", invalid="ignore"):
        gain = 0.5 * (gl ** 2 / (hl + lam) + gr ** 2 / (hr + lam)
                      - gt ** 2 / (ht + lam)) - gamma
    if precision == "bfloat16":
        gain = _bf16(gain)
    valid = (hl >= mcw) & (hr >= mcw) & fmask[None, :, None]
    valid[:, :, -1] = False  # threshold B-1 sends every row left
    return np.where(valid, gain, -np.inf)


def _leaf_weights(g, h, leaf_of_row, model, precision) -> tuple:
    leaves = 2 ** model["max_depth"]
    G = np.bincount(leaf_of_row, weights=g, minlength=leaves)
    H = np.bincount(leaf_of_row, weights=h, minlength=leaves)
    C = np.bincount(leaf_of_row, minlength=leaves)
    if precision == "bfloat16":
        G, H = _bf16(G), _bf16(H)
    elif precision != "float64":
        G, H = G.astype(np.float32), H.astype(np.float32)
    w = np.where(C > 0, -G / (H + model["lambda"]), 0.0)
    if precision == "bfloat16":
        w = _bf16(w)
    elif precision != "float64":
        w = w.astype(np.float32)
    return w, C


def _grow(b, g, h, fmask, model, precision="float64", given=None):
    """Grow one tree level by level on its sampled rows ``b`` (rows, d).

    With ``given`` = (feature, threshold, leaf) of a tree to check, follow
    its splits instead of choosing, and return how far each falls short.
    Returns (feature, threshold, leaf, split_regret, leaf_gap).
    """
    depth, num_bins = model["max_depth"], model["num_bins"]
    feature = np.full(2 ** depth - 1, -1, np.int32)
    threshold = np.full(2 ** depth - 1, num_bins, np.int32)
    assign = np.zeros(b.shape[0], np.int64)
    rows = np.arange(b.shape[0])
    regret, scale = 0.0, None
    for level in range(depth):
        width, off = 2 ** level, 2 ** level - 1
        gains = _gains(_node_stats(b, g, h, assign, width, num_bins), fmask,
                       model, precision)
        flat = gains.reshape(width, -1)
        best = flat.max(axis=1)
        if given is None:
            arg = flat.argmax(axis=1)  # first index on ties, like argmax
            split = best > 0.0
            feature[off:off + width] = np.where(split, arg // num_bins, -1)
            threshold[off:off + width] = np.where(split, arg % num_bins,
                                                  num_bins)
        else:
            feature[off:off + width] = given[0][off:off + width]
            threshold[off:off + width] = given[1][off:off + width]
            if level == 0:
                scale = best[0] if best[0] > 0 else 1.0
            for k in range(width):
                f, t = int(feature[off + k]), int(threshold[off + k])
                if f >= 0:
                    ok = f < b.shape[1] and 0 <= t < num_bins
                    chosen = gains[k, f, t] if ok else -np.inf
                    short = best[k] - chosen if np.isfinite(chosen) else BIG
                else:
                    short = max(best[k], 0.0)
                regret = max(regret, min(short / scale, BIG))
        f = feature[off:off + width][assign]
        t = threshold[off:off + width][assign]
        go_right = (f >= 0) & (b[rows, np.clip(f, 0, None)] > t)
        assign = assign * 2 + go_right
    w, count = _leaf_weights(g, h, assign, model, precision)
    if given is None:
        return feature, threshold, w, 0.0, 0.0
    leaf = np.asarray(given[2], np.float64)
    filled = np.abs(w[count > 0])
    median = float(np.median(filled)) if filled.size else 0.0
    denom = np.maximum(np.abs(w), median)
    with np.errstate(divide="ignore", invalid="ignore"):
        gap = np.where(denom > 0, np.abs(leaf - w) / denom, np.abs(leaf - w))
    return feature, threshold, w, regret, float(gap.max())


def leaf_index(binned, feature, threshold, depth) -> np.ndarray:
    """Leaf of every row: right iff the split feature's bin exceeds the
    threshold; an unsplit node (feature -1) sends every row left."""
    idx = np.zeros(binned.shape[0], np.int64)
    rows = np.arange(binned.shape[0])
    for level in range(depth):
        off = 2 ** level - 1
        f = feature[off + idx]
        t = threshold[off + idx]
        idx = idx * 2 + ((f >= 0) & (binned[rows, np.clip(f, 0, None)] > t))
    return idx


def _sigmoid(v):
    return 1.0 / (1.0 + np.exp(-v))


# --------------------------------------------------------------------------
# training: check and trainer
# --------------------------------------------------------------------------
def check_training(x, y, key, model: dict, prog: dict) -> dict:
    """The four readings for a trained ensemble ``prog`` = {"edges" (d, B-1),
    "forests": [(feature (T, I), threshold (T, I), leaf (T, L))] per round,
    "margin" (n,)}, trained from ``key`` on rows ``x`` with labels ``y``."""
    n, d = x.shape
    depth, lr = model["max_depth"], model["learning_rate"]
    edges_ref = quantile_edges(x, model["num_bins"])
    edges = np.asarray(prog["edges"], np.float32)
    out = {"edge_gap": float(np.max(np.abs(edges.astype(np.float64) - edges_ref)
                                    / np.maximum(np.abs(edges_ref), 1.0)))}
    masks = tree_masks(key, model, n, d)
    forests = prog["forests"]
    if len(forests) != len(masks) or any(
            np.shape(f[0])[0] != s.shape[0] for f, (s, _) in zip(forests, masks)):
        out.update(split_regret=BIG, leaf_gap=BIG, margin_gap=BIG)
        return out
    binned = bin_values(x, edges)
    y64 = np.asarray(y, np.float64)
    margin = np.full(n, model["base_score"], np.float64)
    regret = leaf_gap = 0.0
    for (feat, thr, leaf), (smask, fmask) in zip(forests, masks):
        p = _sigmoid(margin)
        g, h = p - y64, p * (1.0 - p)
        preds = np.zeros((smask.shape[0], n))
        for t in range(smask.shape[0]):
            rows = np.flatnonzero(smask[t])
            tree = (np.asarray(feat[t]), np.asarray(thr[t]), np.asarray(leaf[t]))
            _, _, _, r, lg = _grow(binned[rows], g[rows], h[rows], fmask[t],
                                   model, "float64", given=tree)
            regret, leaf_gap = max(regret, r), max(leaf_gap, lg)
            preds[t] = tree[2][leaf_index(binned, tree[0], tree[1], depth)]
        margin = margin + lr * preds.mean(axis=0)
    out.update(split_regret=float(regret), leaf_gap=float(leaf_gap),
               margin_gap=float(np.max(np.abs(np.asarray(prog["margin"],
                                                          np.float64) - margin))))
    return out


FAULTS = ("stale_state", "half_batch", "no_exchange", "altered_answer")


def train(x, y, key, model: dict, precision: str = "float32",
          fault: str | None = None) -> dict:
    """The algorithm as a trainer; returns what ``check_training`` reads.

    ``precision``: "float32" (as the configuration states), "bfloat16" (the
    control: edges, gradients, sums, gains, leaves and margin rounded to
    bfloat16) or "mxu_default" (gradients rounded to bfloat16 before float32
    sums: a one-pass bfloat16 matrix unit).  ``fault`` plants one of
    ``FAULTS``: round 2 leaves the margin unchanged; each tree sees only the
    first half of its sampled rows; each tree sees only the rows of the first
    of two row shards; one leaf of the first tree is 1 % off.
    """
    n, d = x.shape
    depth, lr = model["max_depth"], model["learning_rate"]
    edges = quantile_edges(x, model["num_bins"])
    if precision == "bfloat16":
        edges = _bf16(edges)
    binned = bin_values(x, edges)
    grow_prec = "float32" if precision == "mxu_default" else precision
    margin = np.full(n, model["base_score"], np.float32)
    forests = []
    for m, (smask, fmask) in enumerate(tree_masks(key, model, n, d), 1):
        p = _sigmoid(margin).astype(np.float32)
        g, h = p - y.astype(np.float32), p * (np.float32(1) - p)
        if precision in ("bfloat16", "mxu_default"):
            g, h = _bf16(g), _bf16(h)
        trees, preds = [], []
        for t in range(smask.shape[0]):
            rows = np.flatnonzero(smask[t])
            if fault == "half_batch":
                rows = rows[: rows.size // 2]
            elif fault == "no_exchange":
                rows = rows[rows < n // 2]
            f, thr, w, _, _ = _grow(binned[rows], g[rows].astype(np.float64),
                                    h[rows].astype(np.float64), fmask[t],
                                    model, grow_prec)
            preds.append(w[leaf_index(binned, f, thr, depth)])
            trees.append((f, thr, np.asarray(w, np.float32)))
        update = (lr * np.mean(preds, axis=0)).astype(np.float32)
        if fault == "altered_answer" and m == 1:
            k = int(np.argmax(np.abs(trees[0][2])))
            trees[0][2][k] *= np.float32(1.01)
        if not (fault == "stale_state" and m == 2):
            margin = margin + update
        if precision == "bfloat16":
            margin = _bf16(margin)
        forests.append(tuple(np.stack(a) for a in zip(*trees)))
    return {"edges": edges, "forests": forests, "margin": margin}


# --------------------------------------------------------------------------
# serving
# --------------------------------------------------------------------------
def scores(ens: dict, x: np.ndarray, precision: str = "float64") -> np.ndarray:
    """Served probabilities sigmoid(base + sum_t scale_t * leaf_t(x)) of the
    benchmark-made ensemble ``ens`` (bin-space thresholds against its
    edges), in float64 or, as the control, in bfloat16 throughout."""
    edges, leaf, scale = ens["edges"], ens["leaf"], ens["scale"]
    if precision == "bfloat16":
        x, edges, leaf, scale = _bf16(x), _bf16(edges), _bf16(leaf), _bf16(scale)
    binned = bin_values(np.asarray(x, np.float32), edges)
    margin = np.full(x.shape[0], ens["base"], np.float64)
    for t in range(leaf.shape[0]):
        idx = leaf_index(binned, ens["feature"][t], ens["threshold"][t],
                         ens["depth"])
        margin = margin + np.float64(scale[t]) * leaf[t][idx]
        if precision == "bfloat16":
            margin = _bf16(margin)
    out = _sigmoid(margin)
    return _bf16(out) if precision == "bfloat16" else out
