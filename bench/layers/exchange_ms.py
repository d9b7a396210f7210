"""Party exchange: device milliseconds of collective operations (all-gather,
all-reduce and kin) per round, averaged over the devices."""

from bench import tracing


def read(ctx):
    s = tracing.op_seconds(ctx["trace"], tracing.is_collective)
    if not s or not ctx.get("rounds"):
        return None
    return s * 1e3 / ctx["rounds"]
