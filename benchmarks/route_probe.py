"""Per-level routing cost on the device, by form and level width.

Times one tree level of round routing (``core.tree.level_go_right``'s
job) in three forms at the Give-Me-Some-Credit round shape (T 5 trees,
n 105,000 rows, d 10 features, B 32 bins) for level widths 1 to 256:

* ``gather`` — per (tree, row) gathers of the node's feature, threshold
  and bin (the element-wise form the library used before the row form;
  kept here only, for comparison);
* ``rows``   — ``level_go_right`` itself: per node, the split column read
  as a contiguous row of the feature-major table and compared over all
  rows;
* ``onehot`` — per node, the split column selected over the d feature
  rows with no gather at all (a variant kept here only).

Each timing is one jitted program that routes ``--reps`` levels in a
``fori_loop`` (each level's indices feed the next, folded back into the
width), so host dispatch is paid once and divided out.  Measured on a
TPU v5e, ms a level (20 levels a program):

    width    1      2      4      8      16     32     64     128    256
    gather   0.037  15.70  15.70  12.22  12.24  12.24  12.24  11.91  13.45
    rows     0.037  0.036  0.047  0.052  0.058  0.093  0.238  0.698  4.490
    onehot   0.036  0.097  0.101  0.123  0.170  0.746  1.314  -      -

The row form wins at every width measured, so the library keeps it alone.
Each time includes the probe's own index update and modulo.

    PYTHONPATH=src python3 benchmarks/route_probe.py [--reps 50] [--out FILE]

Prints one JSON line per (form, width) and ends with a summary line.  On
a CPU the numbers describe XLA's CPU backend, not the chip.
"""

from __future__ import annotations

import argparse
import json
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tree

T, N, D, B = 5, 105_000, 10, 32
WIDTHS = (1, 2, 4, 8, 16, 32, 64, 128, 256)


def gather_form(binned, idx, feature, threshold):
    f = jnp.take_along_axis(feature, idx, axis=1)    # (T, n)
    t = jnp.take_along_axis(threshold, idx, axis=1)  # (T, n)
    rows = jnp.arange(binned.shape[0])
    fv = binned[rows[None, :], jnp.clip(f, 0, None)]
    return (f >= 0) & (fv > t)


def onehot_form(binned, idx, feature, threshold):
    cols = binned.T
    go = jnp.zeros(idx.shape, bool)
    for j in range(feature.shape[1]):
        col = jnp.zeros(idx.shape, cols.dtype)
        for k in range(cols.shape[0]):
            col = jnp.where(feature[:, j, None] == k, cols[k][None, :], col)
        right = (feature[:, j, None] >= 0) & (col > threshold[:, j, None])
        go = go | ((idx == j) & right)
    return go


FORMS = {"gather": gather_form, "rows": tree.level_go_right,
         "onehot": onehot_form}


def make_case(width: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    binned = jnp.asarray(rng.integers(0, B, (N, D)), jnp.int32)
    idx = jnp.asarray(rng.integers(0, width, (T, N)), jnp.int32)
    feature = rng.integers(0, D, (T, width))
    feature[:, ::3] = -1  # some unsplit nodes
    threshold = rng.integers(0, B, (T, width))
    threshold[feature < 0] = B
    return binned, idx, jnp.asarray(feature, jnp.int32), jnp.asarray(
        threshold, jnp.int32)


def timed(form, width: int, reps: int, calls: int) -> dict:
    body = FORMS[form]

    @jax.jit
    def run(binned, idx, feature, threshold):
        def step(_, i):
            nxt = i * 2 + body(binned, i, feature, threshold).astype(jnp.int32)
            return nxt % width
        return jax.lax.fori_loop(0, reps, step, idx)

    args = make_case(width)
    t0 = time.perf_counter()
    compiled = run.lower(*args).compile()
    compile_s = time.perf_counter() - t0
    out = compiled(*args)
    out.block_until_ready()
    walls = []
    for _ in range(calls):
        t0 = time.perf_counter()
        compiled(*args).block_until_ready()
        walls.append(time.perf_counter() - t0)
    level_ms = statistics.median(walls) / reps * 1e3
    return {"form": form, "width": width, "level_ms": level_ms,
            "compile_s": compile_s, "reps": reps,
            "checksum": int(jnp.sum(out))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--reps", type=int, default=50)
    ap.add_argument("--calls", type=int, default=7)
    ap.add_argument("--widths", default=",".join(map(str, WIDTHS)))
    ap.add_argument("--forms", default=",".join(FORMS))
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    rows = []
    for width in map(int, args.widths.split(",")):
        for form in args.forms.split(","):
            r = timed(form, width, args.reps, args.calls)
            r["device"] = dev.device_kind
            rows.append(r)
            print(json.dumps(r), flush=True)
    by = {(r["form"], r["width"]): r for r in rows}
    agree = all(len({by[(f, w)]["checksum"] for f in args.forms.split(",")})
                == 1 for w in {r["width"] for r in rows})
    summary = {"device": dev.device_kind, "platform": dev.platform,
               "forms_agree": agree,
               "level_ms": {f"{f}@{w}": round(r["level_ms"], 5)
                            for (f, w), r in sorted(by.items())}}
    print(json.dumps(summary), flush=True)
    if args.out:
        with open(args.out, "w") as fh:
            fh.write("\n".join(json.dumps(r) for r in rows + [summary]) + "\n")
    return 0 if agree else 1


if __name__ == "__main__":
    raise SystemExit(main())
