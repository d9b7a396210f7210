"""Per-level histogram cost on the device, by kernel form and frontier width.

Times one level's round histogram (``kernels.histogram.ops``' job: T trees,
every row, the left-child pass of the subtraction pipeline) in each Pallas
form, at two shapes:

* ``higgs`` — T 5 trees, n 700,000 rows, d 28 features, B 256 bins (the
  ``higgs.train`` cell's table), 1 to 64 parents;
* ``gmsc``  — T 5, n 105,000, d 10, B 32 (the credit cells), 1 to 16 parents.

Forms: ``flat`` (node x bin ids on lanes, ``fedgbf_histogram``) and
``nodes`` (trees and nodes on the MXU's rows, the operand split into three
bf16 parts, ``fedgbf_histogram_nodes``).  Each timing is the median wall of
``--calls`` calls of one jitted level, ended by ``block_until_ready``;
compile time is reported beside it.  A form that the chip's compiler
refuses is reported with its error and no time.  Measured on a TPU v5e, ms
a level:

    parents          1      2      4      8      16      32      64
    higgs flat       116.5  228.5  452.7  900.9  2044.4  3878.3  (VMEM)
    higgs nodes      13.3   13.7   14.6   26.5   47.6    93.8    186.3
    gmsc  flat       5.16   5.18   5.18   9.46   17.95
    gmsc  nodes      1.69   1.68   1.66   2.39   4.10

Two variants of the nodes form were measured and dropped (higgs, ms at 1 /
16 / 64 parents): one block per tree at ``Precision.HIGHEST``, 116 / 118 /
343; the same in bf16 parts, 60 / 62 / 176; trees stacked at ``HIGHEST``,
24.5 / 87.4 / 346.5 (gmsc's ``nodes`` row is that last variant).  Stacking
the trees wins while one tree's rows leave the MXU idle; the split halves
the passes.

    PYTHONPATH=src python3 benchmarks/hist_form_probe.py [--shapes higgs,gmsc]

Prints one JSON line per (shape, form, parents) and ends with a summary
line.  On a CPU the kernels run in interpret mode and the numbers describe
the interpreter, not the chip.
"""

from __future__ import annotations

import argparse
import json
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.histogram import ops

SHAPES = {
    "higgs": dict(T=5, n=700_000, d=28, B=256, parents=(1, 2, 4, 8, 16, 32, 64)),
    "gmsc": dict(T=5, n=105_000, d=10, B=32, parents=(1, 2, 4, 8, 16)),
}
FORMS = ("flat", "nodes")


def make_case(T, n, d, B, parents, seed=0):
    rng = np.random.default_rng(seed)
    binned = jnp.asarray(rng.integers(0, B, (n, d)), jnp.int32)
    g = jnp.asarray(rng.normal(size=n), jnp.float32)
    h = jnp.asarray(rng.random(n) * 0.25, jnp.float32)
    w = jnp.asarray(rng.random((T, n)) < 0.3, jnp.float32)
    assign = jnp.asarray(rng.integers(0, 2 * parents, (T, n)), jnp.int32)
    return binned, g, h, w, assign


def timed(shape: str, form: str, parents: int, calls: int) -> dict:
    s = SHAPES[shape]
    args = make_case(s["T"], s["n"], s["d"], s["B"], parents)
    out = {"shape": shape, "form": form, "parents": parents,
           "width": parents * s["B"]}
    fn = jax.jit(lambda *a: ops._round_histogram(
        *a, parents, s["B"], form=form, tile_n=512, feat_block=8,
        child=True))
    t0 = time.perf_counter()
    try:
        compiled = fn.lower(*args).compile()
    except Exception as e:  # noqa: BLE001 — the compiler's refusal is the reading
        out.update(error=str(e).splitlines()[0][:300],
                   compile_s=time.perf_counter() - t0)
        return out
    out["compile_s"] = time.perf_counter() - t0
    res = compiled(*args)
    res.block_until_ready()
    walls = []
    for _ in range(calls):
        t0 = time.perf_counter()
        compiled(*args).block_until_ready()
        walls.append(time.perf_counter() - t0)
    out["level_ms"] = statistics.median(walls) * 1e3
    out["checksum"] = float(jnp.sum(res[..., -1]))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--forms", default=",".join(FORMS))
    ap.add_argument("--calls", type=int, default=7)
    ap.add_argument("--skip", default="flat@higgs@64",
                    help="form@shape@parents triples not to run")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    skip = set(args.skip.split(","))
    rows = []
    for shape in args.shapes.split(","):
        for parents in SHAPES[shape]["parents"]:
            for form in args.forms.split(","):
                if f"{form}@{shape}@{parents}" in skip:
                    continue
                r = timed(shape, form, parents, args.calls)
                r["device"] = dev.device_kind
                rows.append(r)
                print(json.dumps(r), flush=True)
    summary = {"device": dev.device_kind, "platform": dev.platform,
               "level_ms": {f"{r['shape']}:{r['form']}@{r['parents']}":
                            round(r["level_ms"], 4)
                            for r in rows if "level_ms" in r}}
    print(json.dumps(summary), flush=True)
    if args.out:
        with open(args.out, "w") as fh:
            fh.write("\n".join(json.dumps(r) for r in rows + [summary]) + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
