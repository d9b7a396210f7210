"""Histogram kernel: least time of the rounds' histogram work
(``counts.histogram_round``) over the device time of ``fedgbf_histogram``."""

from bench import tracing

KERNEL = "fedgbf_histogram"


def read(ctx):
    s = tracing.op_seconds(ctx["trace"], lambda n: KERNEL in n)
    if not s or "hist_least_s" not in ctx:
        return None
    return 100.0 * ctx["hist_least_s"] / s
