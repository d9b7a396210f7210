"""Pallas TPU kernel: gradient-histogram accumulation as one-hot MXU matmuls.

TPU adaptation (DESIGN.md §2, §4). GPU GBDTs accumulate histograms with
atomic scatter-adds into shared memory; TPUs have neither atomics nor
arbitrary scatter. Instead the histogram is a dense contraction

    hist[f, :, :] = [g*w, h*w, w, 0...]  @  onehot(node * B + bin[f, :])^T
                    (stats_pad x T)          (T x NB)

which the MXU executes as an ordinary matmul.  The kernel reads the raw
level inputs (``binned``, ``assign``, ``g``, ``h``, ``w``) and forms both
the fused node×bin ids and the stats rows in VMEM/VREGs, so the only HBM
traffic is the inputs once and the histogram out.

Layout (samples on lanes, the TPU-native orientation):

* ``binned`` arrives transposed, (d_pad, n_pad): a (8, tile_n) block puts
  eight features on sublanes and ``tile_n`` (a multiple of 128) samples on
  lanes, so every block is (8, 128)-aligned.  Per-sample vectors are
  (1, n_pad) rows; g/h are (K, n_pad).
* The output is (T, d_pad, stats_pad, NB): ``NB = nodes * B`` padded to a
  multiple of 128 lanes, stats (2K+1 rounded up to 8) on sublanes.
* Grid is (tree, feature block, sample tile) with the sample-tile axis —
  the reduction — innermost, so each output block is revisited on
  consecutive steps and stays resident in VMEM while it accumulates
  (initialised at tile 0).  Tree and feature-block axes are parallel.
* The contraction runs at ``Precision.HIGHEST``: the one-hot is exact in
  any precision, but the default single bf16 pass would round g and h.

VMEM per step (tile_n=512, NB=128, f32): binned 16 KiB, one-hot 256 KiB,
out block 32 KiB — far inside the 16 MiB scoped default.  The one-hot grows
with NB (depth 8, B 32: NB 4096 -> 8 MiB).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _stats_pad(k: int) -> int:
    """Sublane-aligned stats width for K gradient channels: round_up(2K+1, 8)."""
    return ((2 * k + 1 + 7) // 8) * 8


def _histogram_kernel(
    binned_ref, assign_ref, g_ref, h_ref, w_ref, out_ref,
    *, num_bins: int, feat_block: int, child_mode: bool,
):
    """One grid step: accumulate ``feat_block`` features of one sample tile
    into one tree's histogram block.

    binned_ref: (feat_block, tile_n) int32 raw bin ids (tree-invariant);
    assign_ref / w_ref: (1, 1, tile_n) — this tree's node ids / sample mask
        (padded rows carry w == 0, so they contribute nothing);
    g_ref / h_ref: (K, tile_n) float32 shared derivatives (K = 1 scalar);
    out_ref: (1, feat_block, stats_pad, NB) float32.

    ``child_mode`` is the subtraction pipeline's left-child-only variant
    (DESIGN.md §6): samples routed right (odd ``assign``) are weight-masked
    to zero and the node id halves to the parent index, both formed in
    VREGs, so the half-width pass adds no HBM traffic.
    """

    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    k, tile_n = g_ref.shape
    stats_pad, nb = out_ref.shape[2], out_ref.shape[3]
    wv = w_ref[0]          # (1, T)
    assign = assign_ref[0]
    if child_mode:
        wv = wv * (assign % 2 == 0).astype(jnp.float32)
        assign = assign // 2
    gw = g_ref[...] * wv   # (K, T)
    hw = h_ref[...] * wv
    # Stats rows [g_1..g_K, h_1..h_K, w, 0...] built by sublane selects
    # (count stays the LAST live row).
    row = jax.lax.broadcasted_iota(jnp.int32, (stats_pad, tile_n), 0)
    data = jnp.where(row == 2 * k, wv, 0.0)
    for c in range(k):
        data = jnp.where(row == c, gw[c:c + 1], data)
        data = jnp.where(row == k + c, hw[c:c + 1], data)
    node = assign * num_bins  # (1, T)
    iota = jax.lax.broadcasted_iota(jnp.int32, (nb, tile_n), 0)
    for f in range(feat_block):
        ids = node + binned_ref[f:f + 1, :]               # (1, T)
        onehot = (ids == iota).astype(jnp.float32)        # (NB, T)
        out_ref[0, f] += jax.lax.dot_general(
            data, onehot,
            dimension_numbers=(((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )  # (stats_pad, NB) on the MXU


def histogram_pallas_call(
    binned_t: jnp.ndarray,
    assign: jnp.ndarray,
    g: jnp.ndarray,
    h: jnp.ndarray,
    w: jnp.ndarray,
    nb: int,
    num_bins: int,
    *,
    tile_n: int = 512,
    feat_block: int = 8,
    interpret: bool = False,
    child_mode: bool = False,
) -> jnp.ndarray:
    """Raw pallas_call. Caller guarantees the padding invariants (ops.py):

    binned_t (d_pad, n_pad) int32 shared by all trees, d_pad % feat_block
             == 0, n_pad % tile_n == 0, values in [0, num_bins);
    assign / w (n_trees, 1, n_pad) int32 / float32 per tree — ``assign``
             in [0, nb // num_bins), or with ``child_mode`` the current-level
             assignment in [0, 2 * nb // num_bins); padded samples carry
             w == 0;
    g / h (K, n_pad) float32 shared (K = 1 scalar objectives).

    Returns (n_trees, d_pad, round_up(2K+1, 8), nb) float32.
    """
    n_trees = assign.shape[0]
    d_pad, n_pad = binned_t.shape
    k = g.shape[0]
    stats_pad = _stats_pad(k)
    grid = (n_trees, d_pad // feat_block, n_pad // tile_n)
    tree_row = pl.BlockSpec((1, 1, tile_n), lambda t, j, i: (t, 0, i))
    chan_row = pl.BlockSpec((k, tile_n), lambda t, j, i: (0, i))
    return pl.pallas_call(
        functools.partial(
            _histogram_kernel, num_bins=num_bins, feat_block=feat_block,
            child_mode=child_mode,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((feat_block, tile_n), lambda t, j, i: (j, i)),
            tree_row,   # assign
            chan_row,   # g
            chan_row,   # h
            tree_row,   # w
        ],
        out_specs=pl.BlockSpec(
            (1, feat_block, stats_pad, nb), lambda t, j, i: (t, j, 0, 0)
        ),
        out_shape=jax.ShapeDtypeStruct(
            (n_trees, d_pad, stats_pad, nb), jnp.float32
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name="fedgbf_histogram",
    )(binned_t, assign, g, h, w)
