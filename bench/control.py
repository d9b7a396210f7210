#!/usr/bin/env python3
"""Readings that set a cell's limits: the program on many seeds (the lower
reading), the control and the planted faults on a few (the upper reading).

    python3 bench/control.py --workload gmsc.train --program-seeds 101,102 \
        --control-seeds 201,202,203

For a training cell, each program seed makes the table, runs job 1 of the
cell's traffic through the timed path and checks it; each control seed
runs the plain reference in the program's place in bfloat16 (the control),
with a bfloat16 matrix unit's rounding of the gradients (``mxu_default``),
and with each planted fault of ``reference.FAULTS``, and checks those.  For
the serving cell, each control seed scores a sample of test rows through
the reference in bfloat16.  One JSON line per reading, then the largest
program reading and the smallest reading of each other variant.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

import numpy as np  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

SERVE_ROWS = 200_000


def ints(s: str) -> list:
    return [int(v) for v in s.split(",") if v]


def train_readings(files, program_seeds, control_seeds, faults):
    from bench import datagen, reference
    from bench.drivers import train_jobs
    from repro.core import boosting

    model = files["config"]["model"]
    cfg = train_jobs.fedgbf_config(model)
    for seed in program_seeds:
        table = datagen.credit_table(files["config"]["dataset"], seed)
        xd, yd, backend = train_jobs.place(table.x_train, table.y_train,
                                           files["traffic"], cfg.tree)
        key = train_jobs.job_key(seed, 1)
        m, hist = boosting.train_fedgbf(xd, yd, cfg, key, backend=backend,
                                        eval_every=1)
        prog = {"edges": np.asarray(m.bin_edges), "margin": hist.final_margin,
                "forests": [(np.asarray(f.feature), np.asarray(f.threshold),
                             np.asarray(f.leaf_weight)) for f in m.forests]}
        del xd, yd
        yield seed, "program", reference.check_training(
            table.x_train, table.y_train, key, model, prog)
    for seed in control_seeds:
        table = datagen.credit_table(files["config"]["dataset"], seed)
        key = train_jobs.job_key(seed, 1)
        variants = [("bfloat16", "bfloat16", None),
                    ("mxu_default", "mxu_default", None)]
        variants += [(f, "float32", f) for f in faults]
        for name, precision, fault in variants:
            prog = reference.train(table.x_train, table.y_train, key, model,
                                   precision, fault)
            yield seed, name, reference.check_training(
                table.x_train, table.y_train, key, model, prog)


def serve_readings(files, control_seeds):
    from bench import datagen, reference
    from bench.drivers import open_loop

    for seed in control_seeds:
        table = datagen.credit_table(files["config"]["dataset"], seed)
        ens = open_loop.make_ensemble(files["config"]["model"], table.x_train,
                                      seed)
        rows = table.x_test[np.random.default_rng(seed).integers(
            0, table.x_test.shape[0], SERVE_ROWS)]
        want = reference.scores(ens, rows)
        got = reference.scores(ens, rows, "bfloat16")
        yield seed, "bfloat16", {"score_gap": float(np.max(np.abs(got - want)))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--program-seeds", type=ints, default=[])
    ap.add_argument("--control-seeds", type=ints, default=[])
    ap.add_argument("--faults", default="stale_state,half_batch,altered_answer")
    args = ap.parse_args(argv)

    from bench import harness

    files = harness.cell_files(args.workload)
    harness.enable_cache()
    harness.devices_for(files["cell"]["chips"], True)
    if files["traffic"]["kind"] == "train_jobs":
        gen = train_readings(files, args.program_seeds, args.control_seeds,
                             [f for f in args.faults.split(",") if f])
    else:
        gen = serve_readings(files, args.control_seeds)
    worst, least = {}, {}
    for seed, variant, readings in gen:
        print(json.dumps({"seed": seed, "variant": variant, **readings}),
              flush=True)
        for k, v in readings.items():
            if variant == "program":
                worst[k] = max(worst.get(k, 0.0), v)
            else:
                slot = least.setdefault(variant, {})
                slot[k] = min(slot.get(k, v), v)
    print(json.dumps({"program_largest": worst, "variant_smallest": least}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
