"""Device under the training engine: share of the traced stretch in which no
operation ran, averaged over the devices."""

from bench import tracing


def read(ctx):
    tr = ctx["trace"]
    if "rounds" not in ctx or not tr.devices:
        return None
    return 100.0 * (1.0 - tracing.busy_s(tr) / tracing.window_s(tr))
