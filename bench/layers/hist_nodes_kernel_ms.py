"""Wide-frontier histogram form: device milliseconds of
``fedgbf_histogram_nodes`` per round (None where no such op ran)."""

from bench import tracing

KERNEL = "fedgbf_histogram_nodes"


def read(ctx):
    s = tracing.op_seconds(ctx["trace"], lambda n: KERNEL in n)
    if not s or not ctx.get("rounds"):
        return None
    return s * 1e3 / ctx["rounds"]
