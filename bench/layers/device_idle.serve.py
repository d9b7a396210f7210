"""Device under the serving loop: share of the traced stretch in which no
operation ran."""

from bench import tracing


def read(ctx):
    tr = ctx["trace"]
    if "rows" not in ctx or not tr.devices:
        return None
    return 100.0 * (1.0 - tracing.busy_s(tr) / tracing.window_s(tr))
