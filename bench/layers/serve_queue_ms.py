"""Serving host path: 99th percentile of the wait from a request's due time
to the call that serves it, over the requests of the traced stretch (the
benchmark's own clock)."""

import numpy as np


def read(ctx):
    q = ctx.get("queue_s")
    if q is None or not len(q):
        return None
    return float(np.percentile(q, 99)) * 1e3
