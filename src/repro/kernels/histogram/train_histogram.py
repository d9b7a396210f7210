"""Pallas TPU kernels: gradient-histogram accumulation as one-hot MXU matmuls.

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

VMEM per step (tile_n=512, f32): binned 16 KiB, then the one-hot and its
iota at NB x tile_n x 4 B each and the out block at 8 x stats_pad x NB x 4 B.
At NB 128 that is 256 KiB, 256 KiB and 32 KiB, inside the 16 MiB scoped
default; all three grow with NB, and at NB 16,384 (64 parents at B 256) the
one-hot and iota alone take 32 MiB each and the compile for a v5e fails
(``RESOURCE_EXHAUSTED`` in vmem).

Wide frontiers therefore take the second form, ``histogram_nodes_pallas_call``
(launched as ``fedgbf_histogram_nodes``): nodes and trees go on the MXU's
row dimension instead of the lanes.  Per sample tile it builds one stats
operand for every tree of the round

    data[(t * S + s) * P_blk + k, :] = stat_s * w_t * (node_t == base + k)
                                       (round_up(T * S * P_blk, 16) x T)

shared by every feature of the block, and contracts it with each feature's
bin one-hot (B_pad x T, built once for all trees) into (rows, B_pad).  The
operand is split into three bfloat16 parts (``split_bf16``) whose
one-pass products with the exact one-hot sum to the float32 products: three
MXU passes where ``Precision.HIGHEST`` makes six.  A node-block grid axis
holds P_blk nodes, at most ``256 // (T * S)`` (a power of two), so VMEM is
bounded whatever the frontier width: at T 5, S 3, P_blk 16, B 256, tile_n
512 and eight features, the operand, its row iota and its parts take 1.6
MiB, the one-hot and its iota 1.25 MiB, the out block 1.9 MiB.  The per-row vector work is B
compares per feature and T * S * P_blk selects per block, not nodes x B.
Per level on a TPU v5e at T 5, n 700,000, d 28, B 256
(``benchmarks/hist_form_probe.py``): 13-15 ms at 1-4 parents, 186 ms at
64, against 117-3,878 ms for the flat kernel at 1-32 parents.
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


#: stats rows of one node block in the wide-frontier form (two MXU tiles)
NODE_BLOCK_ROWS = 256


def node_block(num_nodes: int, rows_per_node: int) -> int:
    """Nodes per block of the wide-frontier form: the largest power of two
    whose ``rows_per_node`` stats rows each (T x (2K+1)) fit
    ``NODE_BLOCK_ROWS``, at least 1, and no more than the frontier."""
    cap = 1
    while 2 * cap * rows_per_node <= NODE_BLOCK_ROWS:
        cap *= 2
    return min(num_nodes, cap)


def split_bf16(x: jnp.ndarray) -> tuple:
    """``x`` (float32) as three bfloat16 parts whose float32 sum is ``x``
    exactly: each part takes the next 8 significant bits of the residue.
    A one-pass bf16 product of each part with an exact 0/1 operand is then
    exact, so three passes give float32 products (``Precision.HIGHEST``
    makes six, splitting the one-hot too)."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def _nodes_kernel(
    binned_ref, assign_ref, g_ref, h_ref, w_ref, out_ref,
    *, block: int, feat_block: int, child_mode: bool,
):
    """One grid step of the wide-frontier form: accumulate ``feat_block``
    features of one sample tile into one node block's output, all trees.

    binned_ref: (feat_block, tile_n) int32 raw bin ids (tree-invariant);
    assign_ref / w_ref: (T, 1, tile_n) every tree's node ids / sample mask;
    g_ref / h_ref: (K, tile_n) float32 shared derivatives;
    out_ref: (1, feat_block, rows, B_pad) float32, row
        ``(t * S + s) * block + k`` for stat s of tree t's node k.

    A sample whose node lies outside this block is weight-masked to zero
    and aimed past the last row, so it selects nothing.  ``child_mode`` is
    the flat kernel's left-child variant: odd ``assign`` masked, ids halved.
    """

    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    k, tile_n = g_ref.shape
    n_trees = assign_ref.shape[0]
    stats = 2 * k + 1
    rows, b_pad = out_ref.shape[2], out_ref.shape[3]
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, tile_n), 0)
    data = jnp.zeros((rows, tile_n), jnp.float32)
    g, h = g_ref[...], h_ref[...]
    for t in range(n_trees):
        wv = w_ref[t]          # (1, tile_n)
        assign = assign_ref[t]
        if child_mode:
            wv = wv * (assign % 2 == 0).astype(jnp.float32)
            assign = assign // 2
        target = assign - pl.program_id(0) * block
        inside = (target >= 0) & (target < block)
        wv = jnp.where(inside, wv, 0.0)
        target = jnp.where(inside, target, rows)
        gw, hw = g * wv, h * wv
        vals = ([gw[c:c + 1] for c in range(k)]
                + [hw[c:c + 1] for c in range(k)] + [wv])
        for s, v in enumerate(vals):
            data = jnp.where(row == target + (t * stats + s) * block, v, data)
    parts = split_bf16(data)
    bins = jax.lax.broadcasted_iota(jnp.int32, (b_pad, tile_n), 0)
    for f in range(feat_block):
        onehot = (binned_ref[f:f + 1, :] == bins).astype(
            jnp.float32).astype(jnp.bfloat16)      # exact in bf16
        acc = out_ref[0, f]
        for part in parts:
            acc += jax.lax.dot_general(
                part, onehot, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # (rows, B_pad) on the MXU, one bf16 pass
        out_ref[0, f] = acc


def histogram_nodes_pallas_call(
    binned_t: jnp.ndarray,
    assign: jnp.ndarray,
    g: jnp.ndarray,
    h: jnp.ndarray,
    w: jnp.ndarray,
    num_nodes: int,
    b_pad: int,
    *,
    tile_n: int = 512,
    feat_block: int = 8,
    interpret: bool = False,
    child_mode: bool = False,
) -> jnp.ndarray:
    """Raw pallas_call of the wide-frontier form; the operands are those of
    ``histogram_pallas_call``.  ``num_nodes`` is the node (or, with
    ``child_mode``, parent) count and ``b_pad`` the bin count padded to
    128 lanes.

    Returns (n_blocks, d_pad, rows, b_pad) float32 with S = 2K+1 and
    P_blk = ``node_block(num_nodes, T * S)``: block j's row
    ``(t * S + s) * P_blk + k`` is stat s of tree t's node
    ``j * P_blk + k``; rows = round_up(T * S * P_blk, 16).
    """
    n_trees = assign.shape[0]
    d_pad, n_pad = binned_t.shape
    k = g.shape[0]
    stats = 2 * k + 1
    block = node_block(num_nodes, n_trees * stats)
    n_blocks = -(-num_nodes // block)
    rows = ((n_trees * stats * block + 15) // 16) * 16  # bf16 sublane tile
    grid = (n_blocks, d_pad // feat_block, n_pad // tile_n)
    tree_rows = pl.BlockSpec((n_trees, 1, tile_n), lambda b, j, i: (0, 0, i))
    chan_row = pl.BlockSpec((k, tile_n), lambda b, j, i: (0, i))
    return pl.pallas_call(
        functools.partial(
            _nodes_kernel, block=block, feat_block=feat_block,
            child_mode=child_mode,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((feat_block, tile_n), lambda b, j, i: (j, i)),
            tree_rows,  # assign
            chan_row,   # g
            chan_row,   # h
            tree_rows,  # w
        ],
        out_specs=pl.BlockSpec(
            (1, feat_block, rows, b_pad), lambda b, j, i: (b, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (n_blocks, d_pad, rows, b_pad), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="fedgbf_histogram_nodes",
    )(binned_t, assign, g, h, w)
