"""Serving host path: median time of one ``serve_stream`` microbatch (pad,
copy, dispatch, fetch), each call's time over its microbatches, over the
calls of the traced stretch (the benchmark's own clock)."""

import numpy as np


def read(ctx):
    b = ctx.get("batch_s")
    if b is None or not len(b):
        return None
    return float(np.median(b)) * 1e3
