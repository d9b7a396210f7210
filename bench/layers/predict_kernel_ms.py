"""Traversal kernel: device milliseconds of ``fedgbf_ensemble_predict`` per
1,000 request rows scored in the traced stretch."""

from bench import tracing

KERNEL = "fedgbf_ensemble_predict"


def read(ctx):
    s = tracing.op_seconds(ctx["trace"], lambda n: KERNEL in n)
    if not s or not ctx.get("rows"):
        return None
    return s * 1e3 / (ctx["rows"] / 1000.0)
