"""Traversal kernel: least time of scoring the traced stretch's request rows
(``counts.traversal``) over the device time of ``fedgbf_ensemble_predict``."""

from bench import tracing

KERNEL = "fedgbf_ensemble_predict"


def read(ctx):
    s = tracing.op_seconds(ctx["trace"], lambda n: KERNEL in n)
    if not s or "predict_least_s" not in ctx:
        return None
    return 100.0 * ctx["predict_least_s"] / s
