"""Jitted wrappers for the Pallas histogram kernel (``train_histogram.py``).

Drop-in replacements for the ``core.histogram`` providers:

* ``compute_histogram_pallas`` — per-tree provider
  (``core.histogram.compute_histogram`` contract; what the ``local-pallas``
  backend's ``histogram_fn`` runs, ``histogram_dispatch("pallas")``);
* ``compute_histogram_pallas_child`` — its child-only variant for the
  sibling-subtraction pipeline (DESIGN.md §6): left-mask and parent ids are
  formed in-kernel and the one-hot contraction runs at half-frontier width;
* ``compute_round_histogram_pallas[_child]`` — the round-native providers
  (DESIGN.md §9): the tree axis is a kernel grid dimension, so ONE launch
  accumulates the whole round's (T, nodes, d, B, 2K+1) histogram with the
  tree-invariant operands (binned, g, h) shared across the tree grid.

The per-tree providers are the round kernel at T = 1, so the round and
per-tree paths are bit-identical by construction.  The wrappers do the
layout (samples on lanes, tile padding) and undo it on the result.  The
kernel runs compiled on TPU and in interpret mode on every other backend.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.histogram.train_histogram import histogram_pallas_call


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _num_stats(g: jnp.ndarray) -> int:
    """Stats-row count for the derivative layout: 3 for scalar (n,) g/h,
    2K+1 for K-channel (n, K) objectives (count stays the last row)."""
    return 3 if g.ndim == 1 else 2 * g.shape[-1] + 1


def _chan_rows(v: jnp.ndarray, pad_n: int) -> jnp.ndarray:
    """Per-sample derivatives as lane-major rows: (n,) -> (1, n_pad);
    (n, K) -> (K, n_pad)."""
    v = v.astype(jnp.float32)
    v = v[None, :] if v.ndim == 1 else v.T
    return jnp.pad(v, ((0, 0), (0, pad_n)))


@partial(
    jax.jit,
    static_argnames=(
        "num_nodes", "num_bins", "tile_n", "feat_block", "child",
        "root_delta_rows", "level",
    ),
)
def compute_round_histogram_pallas(
    binned: jnp.ndarray,
    g: jnp.ndarray,
    h: jnp.ndarray,
    weight: jnp.ndarray,
    assign: jnp.ndarray,
    num_nodes: int,
    num_bins: int,
    *,
    tile_n: int = 512,
    feat_block: int = 8,
    child: bool = False,
    root_delta_rows: int = 0,
    level: int = 0,
) -> jnp.ndarray:
    """Round-native provider (``core.histogram.compute_round_histogram``
    contract) served by the tree-grid kernel: ONE launch accumulates all T
    trees' histograms.  Only tile-boundary zero padding happens in XLA
    (padded samples carry weight 0, so they accumulate nothing).

    With ``child=True`` it is the subtraction pipeline's round child
    provider (``assign`` is the current level's assignment, ``num_nodes``
    the PARENT count); with ``root_delta_rows > 0`` (level 0) the
    shared-root derivation routes through
    ``histogram.root_histogram_via_delta`` with the per-tree provider as
    the delta accumulator.

    Args:
      binned: (n, d) int32; g / h: (n,) or (n, K); weight / assign: (T, n).
    Returns:
      (T, num_nodes, d, num_bins, 2K+1) float32 (3 for scalar g/h).
    """
    if root_delta_rows:
        from repro.core.histogram import root_histogram_via_delta

        return root_histogram_via_delta(
            binned, g, h, weight, num_bins, root_delta_rows,
            base_tree_fn=compute_histogram_pallas,
        )
    n, d = binned.shape
    t = weight.shape[0]
    nb = num_nodes * num_bins
    nb_pad = _round_up(nb, 128)  # one-hot width on lanes
    stats = _num_stats(g)

    n_pad = _round_up(n, tile_n)
    d_pad = _round_up(d, feat_block)
    pad_n = n_pad - n
    binned_t = jnp.pad(binned.T, ((0, d_pad - d), (0, pad_n)))
    tree_rows = lambda v: jnp.pad(v, ((0, 0), (0, pad_n)))[:, None, :]

    hist = histogram_pallas_call(
        binned_t, tree_rows(assign), _chan_rows(g, pad_n), _chan_rows(h, pad_n),
        tree_rows(weight.astype(jnp.float32)), nb_pad, num_bins,
        tile_n=tile_n, feat_block=feat_block, interpret=interpret_mode(),
        child_mode=child,
    )  # (T, d_pad, stats_pad, nb_pad)

    hist = hist[:, :d, :stats, :nb]
    return hist.reshape(t, d, stats, num_nodes, num_bins).transpose(
        0, 3, 1, 4, 2
    )


def compute_round_histogram_pallas_child(
    binned, g, h, weight, assign, num_parents, num_bins, **kw,
) -> jnp.ndarray:
    """Round child provider for ``TreeBackend.round_child_histogram_fn``:
    the whole round's left-child histograms in one tree-grid launch."""
    return compute_round_histogram_pallas(
        binned, g, h, weight, assign, num_parents, num_bins, child=True, **kw
    )


def compute_histogram_pallas(
    binned, g, h, weight, assign, num_nodes, num_bins, **kw,
) -> jnp.ndarray:
    """Same contract as ``core.histogram.compute_histogram``: the round
    kernel at T = 1.  Returns (num_nodes, d, num_bins, 2K+1) float32."""
    return compute_round_histogram_pallas(
        binned, g, h, weight[None], assign[None], num_nodes, num_bins, **kw
    )[0]


def compute_histogram_pallas_child(
    binned, g, h, weight, assign, num_parents, num_bins, **kw,
) -> jnp.ndarray:
    """Child-only provider for ``TreeBackend.child_histogram_fn``: left-child
    histograms at half-frontier width, all staging fused in-kernel."""
    return compute_histogram_pallas(
        binned, g, h, weight, assign, num_parents, num_bins, child=True, **kw
    )
