"""Party exchange: milliseconds per round in which a collective runs and no
other operation does, on the same device, averaged over the devices."""

from bench import tracing


def read(ctx):
    if not ctx.get("rounds"):
        return None
    tr = ctx["trace"]
    if not tracing.op_seconds(tr, tracing.is_collective):
        return None
    return tracing.exposed_collective_s(tr) * 1e3 / ctx["rounds"]
