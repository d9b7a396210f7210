#!/usr/bin/env python3
"""Find the knee of an open-loop serving cell: the highest offered rate the
system sustains without a growing backlog.

    python3 bench/knee.py --workload gmsc.serve --seed 7 --seconds 4 \
        --rates 5000,10000,20000,40000,80000,160000

One process sets the cell up once, then offers each rate for ``--seconds``
through the cell's own open loop.  A rate is sustained when the last
request due finishes within 50 ms of the close and no fifth of the
requests, in order of arrival, has a median latency above twice that of
the calmest fifth: a backlog that grows, or that forms and drains, fails.
Prints one JSON line per rate and, last, ``{"knee_rps": ...}``; the cell's
traffic file then fixes its rate as a number (``rate_rps``).
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

DRAIN_S = 0.05
TREND = 2.0


def sustained(due, done, seconds) -> dict:
    import numpy as np

    fifths = [float(np.median(p)) for p in np.array_split(done - due, 5)]
    drain = float(done[-1] - seconds)
    spread = max(fifths) / min(fifths) if min(fifths) > 0 else float("inf")
    return {"drain_s": drain, "fifths_ms": [f * 1e3 for f in fifths],
            "ok": drain <= DRAIN_S and spread <= TREND}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="gmsc.serve")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    import numpy as np

    from bench import harness
    from bench.drivers import open_loop

    files = harness.cell_files(args.workload)
    harness.enable_cache()
    harness.devices_for(files["cell"]["chips"], True)
    env = types.SimpleNamespace(config=files["config"], traffic=files["traffic"],
                                seed=args.seed, note=harness.note)
    table, _, server = open_loop.setup(env)
    knee = None
    for rate in (float(r) for r in args.rates.split(",")):
        due, offsets, rng = open_loop.schedule(env.traffic, rate, args.seconds,
                                               args.seed)
        rows = table.x_test[rng.integers(0, table.x_test.shape[0], offsets[-1])]
        res = open_loop.open_loop(server, rows, due, offsets)
        lat = (res["done"] - due) * 1e3
        verdict = sustained(due, res["done"], args.seconds)
        print(json.dumps(dict(
            rate_rps=rate, requests=int(due.size), rows=int(offsets[-1]),
            calls=len(res["calls"]), p50_ms=float(np.median(lat)),
            p99_ms=float(np.percentile(lat, 99)), **verdict)), flush=True)
        if not verdict["ok"]:
            break
        knee = rate
    print(json.dumps({"knee_rps": knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
