"""Jitted wrappers for the Pallas histogram kernels (``train_histogram.py``).

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

Two kernel forms serve them, chosen by the level's static width
(``histogram_form``): the flat kernel (node x bin ids on lanes) for narrow
frontiers, the wide-frontier form (nodes on the MXU's rows, a node-block
grid axis, trees stacked on the rows, VMEM bounded whatever the width)
beyond ``FLAT_MAX_LANES``.

The per-tree providers are the round kernels at T = 1, so the round and
per-tree paths are bit-identical by construction.  The wrappers do the
layout (samples on lanes, tile padding) and undo it on the result.  The
kernel runs compiled on TPU and in interpret mode on every other backend.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.histogram.train_histogram import (
    histogram_nodes_pallas_call,
    histogram_pallas_call,
    node_block,
)
from repro.obs import compiles as compiles_mod

#: widest fused node x bin width the flat kernel serves: the credit
#: configurations' widest level (4 nodes x B 32), which stays on it; wider
#: levels take the wide-frontier form (``benchmarks/hist_form_probe.py`` has
#: times by width)
FLAT_MAX_LANES = 128


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


def histogram_form(num_nodes: int, num_bins: int) -> str:
    """The kernel form for a level: ``"flat"`` (node x bin ids on lanes)
    while the fused width ``num_nodes * num_bins`` is at most
    ``FLAT_MAX_LANES``, else ``"nodes"`` (nodes on the MXU's rows, a
    node-block grid axis)."""
    return "flat" if num_nodes * num_bins <= FLAT_MAX_LANES else "nodes"


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
    contract) served by the tree-grid kernels: ONE launch accumulates all T
    trees' histograms.  Only tile-boundary zero padding happens in XLA
    (padded samples carry weight 0, so they accumulate nothing).

    The form follows the static width (``histogram_form``); each call is
    counted in ``fedgbf_hist_levels_total{form=...}`` at trace time while
    the compile counter is installed.

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
    form = histogram_form(num_nodes, num_bins)
    counter = compiles_mod.installed()
    if counter is not None:
        counter.hist_levels[form].inc()
    return _round_histogram(
        binned, g, h, weight, assign, num_nodes, num_bins, form=form,
        tile_n=tile_n, feat_block=feat_block, child=child,
    )


@partial(
    jax.jit,
    static_argnames=(
        "num_nodes", "num_bins", "form", "tile_n", "feat_block", "child",
    ),
)
def _round_histogram(binned, g, h, weight, assign, num_nodes, num_bins, *,
                     form, tile_n, feat_block, child):
    """Layout in, one kernel launch, layout out."""
    n, d = binned.shape
    t = weight.shape[0]
    stats = _num_stats(g)

    n_pad = _round_up(n, tile_n)
    d_pad = _round_up(d, feat_block)
    pad_n = n_pad - n
    binned_t = jnp.pad(binned.T, ((0, d_pad - d), (0, pad_n)))
    tree_rows = lambda v: jnp.pad(v, ((0, 0), (0, pad_n)))[:, None, :]
    operands = (binned_t, tree_rows(assign), _chan_rows(g, pad_n),
                _chan_rows(h, pad_n), tree_rows(weight.astype(jnp.float32)))
    kw = dict(tile_n=tile_n, feat_block=feat_block,
              interpret=interpret_mode(), child_mode=child)

    if form == "flat":
        nb = num_nodes * num_bins
        hist = histogram_pallas_call(
            *operands, _round_up(nb, 128), num_bins, **kw
        )  # (T, d_pad, stats_pad, nb_pad)
        hist = hist[:, :d, :stats, :nb]
        return hist.reshape(t, d, stats, num_nodes, num_bins).transpose(
            0, 3, 1, 4, 2
        )

    hist = histogram_nodes_pallas_call(
        *operands, num_nodes, _round_up(num_bins, 128), **kw
    )  # (n_blocks, d_pad, rows, b_pad)
    n_blocks = hist.shape[0]
    block = node_block(num_nodes, t * stats)
    hist = hist[:, :d, :t * stats * block, :num_bins]
    hist = hist.reshape(n_blocks, d, t, stats, block, num_bins).transpose(
        2, 0, 4, 1, 5, 3
    )  # (T, n_blocks, block, d, B, stats)
    return hist.reshape(t, n_blocks * block, d, num_bins, stats)[:, :num_nodes]


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
