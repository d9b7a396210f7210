"""Operations and HBM bytes the FedGBF algorithm needs, from shapes alone.

Every count is a lower bound on the work of any correct implementation, so
no honest measurement of time can give a share above 100 %.  The counts
never depend on how the work is done: a one-hot matmul, a scatter, uint8
bins, histogram subtraction or shared-root caching change the measured
time, never these numbers.

* Each input is counted at its minimal encoding: one byte per bin id at
  B <= 256 (two above), four bytes per float32 gradient statistic.
* The histogram of a tree needs at least one accumulate per sampled
  (row, feature, statistic) at its root; deeper levels are left out, since
  subtraction or a partitioned row layout can shrink them.  Its bytes are
  one pass over the rows of a tree's sample (shared by all of a round's
  trees, so counted once per round at the largest sample) and the root
  histograms written out.
* A boosting round adds the split scan (at least 6 operations per
  (tree, node, feature, threshold) candidate), the routing of every row
  through every tree (one compare per level), the leaf read and margin
  update (one multiply-add per (tree, row)) and the gradient (4 operations
  per row); its bytes are one read of the binned matrix, the label, and one
  read and one write of the float32 margin.
* Scoring a row reads its float32 features once, compares once per level of
  every tree and accumulates one scaled leaf per tree; the node tables are
  read once per call.
"""

from __future__ import annotations

from typing import NamedTuple

STATS = 3          # gradient, hessian, count
F32 = 4
SPLIT_OPS = 6      # per candidate: right stats, two squares, two quotients
GRAD_OPS = 4       # per row: sigmoid, g = p - y, h = p (1 - p)


class Work(NamedTuple):
    ops: float
    nbytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.ops + other.ops, self.nbytes + other.nbytes)


def bin_bytes(num_bins: int) -> int:
    """Bytes of one bin id at its minimal encoding."""
    return 1 if num_bins <= 256 else 2


def histogram_round(n_keep: int, trees: int, d: int, num_bins: int) -> Work:
    """Histogram work of one round of ``trees`` trees, each over ``n_keep``
    sampled rows of ``d`` features."""
    ops = trees * n_keep * d * STATS
    nbytes = (n_keep * (d * bin_bytes(num_bins) + 2 * F32)
              + trees * d * num_bins * STATS * F32)
    return Work(float(ops), float(nbytes))


def boosting_round(n: int, n_keep: int, trees: int, d: int, num_bins: int,
                   depth: int) -> Work:
    """All of one round: histogram, split scan, routing, leaf and margin
    update, gradient, over ``n`` training rows."""
    internal = 2 ** depth - 1
    ops = (trees * internal * d * (num_bins - 1) * SPLIT_OPS
           + trees * n * depth
           + trees * n * 2
           + n * GRAD_OPS)
    nbytes = n * (d * bin_bytes(num_bins) + F32 + 2 * F32)
    return histogram_round(n_keep, trees, d, num_bins) + Work(float(ops),
                                                              float(nbytes))


def traversal(rows: int, trees: int, depth: int, d: int,
              calls: int = 1) -> Work:
    """Scoring ``rows`` raw float32 rows through ``trees`` trees of
    ``depth`` levels, in ``calls`` separate calls."""
    internal, leaves = 2 ** depth - 1, 2 ** depth
    ops = rows * trees * (depth + 2)
    nbytes = (rows * (d * F32 + F32)
              + calls * trees * (internal * 2 * F32 + leaves * F32 + F32))
    return Work(float(ops), float(nbytes))
