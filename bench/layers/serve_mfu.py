"""Whole scoring step: least time of scoring the traced stretch's request
rows (``counts.traversal``) over the device's busy time in that stretch."""

from bench import tracing


def read(ctx):
    if "predict_least_s" not in ctx:
        return None
    busy = tracing.busy_s(ctx["trace"])
    if not busy:
        return None
    return 100.0 * ctx["predict_least_s"] / busy
