"""Histogram kernel: device milliseconds of ``fedgbf_histogram`` per round."""

from bench import tracing

KERNEL = "fedgbf_histogram"


def read(ctx):
    s = tracing.op_seconds(ctx["trace"], lambda n: KERNEL in n)
    if not s or not ctx.get("rounds"):
        return None
    return s * 1e3 / ctx["rounds"]
