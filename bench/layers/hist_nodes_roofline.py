"""Wide-frontier histogram form: least time of the histograms it must
write (``hist_nodes_least_s``: T x built nodes x d x B x 3 x 4 B over HBM
bandwidth, for the levels it served) over the device time of
``fedgbf_histogram_nodes``.  No row reads are counted: under subtraction no
implementation is bound to read a fixed share of the rows."""

from bench import tracing

KERNEL = "fedgbf_histogram_nodes"


def read(ctx):
    s = tracing.op_seconds(ctx["trace"], lambda n: KERNEL in n)
    if not s or "hist_nodes_least_s" not in ctx:
        return None
    return 100.0 * ctx["hist_nodes_least_s"] / s
