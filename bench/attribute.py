#!/usr/bin/env python3
"""Run one benchmark cell with the program's own spans on, and say where its
time went by program span and scope.

    python3 bench/attribute.py --workload <cell> --seed <n> --seconds <s>
        [--trace 0|1] [--spans 0|1] [--keep <file.xplane.pb>]

The run is ``bench/run.py``'s (``harness.run``), with two additions: the
program's compile counter (``repro.obs.compiles``) is read at the end of
set-up (at ``env.settle()``, where the traffic module ends it) and at the
end of the run, and with ``--spans 1`` an ``AnnotatingTracer`` is
installed first, so the profiled stretch of a ``--trace 1`` run holds the
program's ``fedgbf.*`` spans.  Prints one JSON object as the last line: the run's result, the
compile counters over set-up and after it, and for a traced run the share
of device busy time under a program scope, device time by scope, the
device's idle time by the innermost host span over it and the median of
each ``fedgbf.*`` host span.  ``--keep`` copies the capture.  Exits 2
without a TPU, as ``run.py`` does.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def counters_delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a.get(k, 0.0) for k in b}


def attribution(sp) -> dict:
    from bench import spans, tracing

    names = sorted({n for n, _, _ in sp.host if n.startswith(spans.SCOPE)})
    busy = sum(tracing.union_ns((s, e) for _, _, s, e in ops)
               for ops in sp.devices.values()) / max(1, len(sp.devices)) * 1e-9
    window = (sp.window[1] - sp.window[0]) * 1e-9
    return {
        "window_s": window, "busy_s": busy,
        "scoped_share_of_busy": spans.scoped_share(sp),
        "device_s_by_phase": spans.by_phase(sp),
        "idle_s_by_span": spans.idle_by_span(sp),
        "host_span_median_ms": {
            n: statistics.median(spans.host_durations(sp, n)) * 1e3
            for n in names if spans.host_durations(sp, n)},
        "host_span_count": {n: len(spans.host_durations(sp, n))
                            for n in names},
        "compile_marks": spans.compile_marks(sp),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--spans", type=int, choices=(0, 1), default=1)
    ap.add_argument("--keep", default=None)
    args = ap.parse_args(argv)

    from bench import harness, spans, tracing
    from repro.obs import compiles, trace

    counter = compiles.install()
    if args.spans:
        trace.set_global_tracer(trace.AnnotatingTracer())
    marks = {}
    settle = harness.settle

    def settle_and_mark():
        marks["setup"] = counter.snapshot()
        settle()

    harness.settle = settle_and_mark
    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), T0)
    except harness.NoChip as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 2
    finally:
        harness.settle = settle
        trace.set_global_tracer(None)
    end = counter.snapshot()
    out = {"workload": args.workload, "seed": args.seed,
           "trace": args.trace, "spans": args.spans, "result": result,
           "compiles_setup": marks.get("setup"),
           "compiles_after_setup": counters_delta(marks.get("setup", {}), end)}
    if args.trace:
        capture = tracing.xplane_file(os.path.join(harness.OUT_DIR, "trace"))
        out["attribution"] = attribution(spans.load(capture))
        if args.keep:
            path = os.path.abspath(args.keep)
            os.makedirs(os.path.dirname(path), exist_ok=True)
            shutil.copyfile(capture, path)
            out["kept"] = path
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
