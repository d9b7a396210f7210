"""Serving host path: 99th percentile of every request's latency, due time to
scores on the host, over the requests due in the stretch before the
profiler starts (the benchmark's own clock).  The whole window's tail is
held by no bound: one host stall of ~0.1 s moves it tenfold."""

import numpy as np


def read(ctx):
    lat = ctx.get("tail_s")
    if lat is None or not len(lat):
        return None
    return float(np.percentile(lat, 99)) * 1e3
