import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Dry-run of the paper's own workload on the production mesh: one FedGBF
forest round (5 depth-3 trees, Give-Me-Some-Credit scale) built by the
federated shard_map runtime with parties = the 16-way model axis and samples
sharded over the 16-way data axis.

This is hillclimb pair #3 (most representative of the paper's technique):
the before/after is the aggregation mode — "histogram" (paper-faithful full
per-party histogram exchange, Alg. 2 step 7) vs "argmax" (beyond-paper
candidate-only exchange) — measured in compiled collective bytes.

    PYTHONPATH=src python -m repro.launch.dryrun_fedgbf
"""

import json
import sys

import jax
import jax.numpy as jnp

from repro.core import forest as forest_mod
from repro.core.types import TreeConfig
from repro.federation import vfl
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.obs import perfetto
from repro.obs import trace as obs_trace
from repro.tools import roofline as roofline_mod

REPORT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                          "reports", "dryrun")


def run(aggregation: str, n=150_000, d=16, n_trees=5, multi_pod=False,
        hist_subtraction=False, max_depth=3, max_active_nodes=0,
        data_shards=0, async_exchange=False) -> dict:
    if data_shards:
        # explicit row-shard grid (--data-shards): data_shards x 16 parties
        mesh = make_mesh((data_shards, 16), ("data", "model"))
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    # round the sample count up to the data-sharding granularity (padded
    # rows carry zero sample-mask weight, semantically inert — the backend
    # pads internally either way; pre-rounding keeps the report's n exact)
    shards = 1
    for a in ("pod", "data"):
        if a in mesh.shape:
            shards *= mesh.shape[a]
    n = ((n + shards - 1) // shards) * shards
    cfg = TreeConfig(max_depth=max_depth, num_bins=32,
                     hist_subtraction=hist_subtraction,
                     max_active_nodes=max_active_nodes)
    backend = vfl.make_vfl_backend(
        mesh, cfg, aggregation=aggregation, shard_samples=True,
        async_exchange=async_exchange,
    )

    binned = jax.ShapeDtypeStruct((n, d), jnp.int32)
    g = jax.ShapeDtypeStruct((n,), jnp.float32)
    h = jax.ShapeDtypeStruct((n,), jnp.float32)
    smask = jax.ShapeDtypeStruct((n_trees, n), jnp.float32)
    fmask = jax.ShapeDtypeStruct((n_trees, d), bool)

    tracer = obs_trace.global_tracer()
    with jax.set_mesh(mesh):
        # the backend's forest_builder wraps a jit; lower via a fresh jit
        with tracer.span(f"lower[{aggregation}]", cat="dryrun",
                         args={"chips": chips, "n": n, "d": d}):
            lowered = jax.jit(
                lambda b, gg, hh, sm, fm: backend.build_forest(b, gg, hh, sm, fm)
            ).lower(binned, g, h, smask, fmask)
        with tracer.span(f"compile[{aggregation}]", cat="dryrun",
                         args={"chips": chips}):
            compiled = lowered.compile()

    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    stats = roofline_mod.parse_collectives(compiled.as_text())
    mem = compiled.memory_analysis()
    grid = (f"{data_shards}x16" if data_shards
            else ("2x16x16" if multi_pod else "16x16"))
    report = {
        "tag": f"fedgbf__forest_round__{grid}"
               f"__{aggregation}{'__sub' if hist_subtraction else ''}"
               + ("__async" if async_exchange else "")
               + (f"__d{max_depth}" if max_depth != 3 else "")
               + (f"__a{max_active_nodes}" if max_active_nodes else ""),
        "status": "ok",
        "aggregation": aggregation,
        "hist_subtraction": hist_subtraction,
        "async_exchange": async_exchange,
        "data_shards": data_shards or shards,
        "max_depth": max_depth,
        "max_active_nodes": max_active_nodes,
        "chips": chips,
        "n": n, "d": d, "n_trees": n_trees,
        "flops_per_dev": float(cost.get("flops", 0.0)),
        "bytes_per_dev": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes_per_dev": float(stats.total_bytes),
        "collectives_by_kind": stats.bytes_by_kind,
        "peak_bytes_per_device": getattr(mem, "temp_size_in_bytes", None),
        "compute_s": float(cost.get("flops", 0.0)) / 197e12,
        "memory_s": float(cost.get("bytes accessed", 0.0)) / 819e9,
        "collective_s": float(stats.total_bytes) / 50e9,
    }
    tracer.counter("dryrun_collective_bytes_per_dev",
                   {report["tag"]: report["collective_bytes_per_dev"]})
    os.makedirs(REPORT_DIR, exist_ok=True)
    with open(os.path.join(REPORT_DIR, report["tag"] + ".json"), "w") as f:
        json.dump(report, f, indent=1)
    print(f"[OK] {report['tag']}: flops/dev={report['flops_per_dev']:.3e} "
          f"bytes/dev={report['bytes_per_dev']:.3e} "
          f"coll/dev={report['collective_bytes_per_dev']:.3e} "
          f"(compute {report['compute_s']*1e3:.3f}ms, "
          f"memory {report['memory_s']*1e3:.3f}ms, "
          f"coll {report['collective_s']*1e3:.3f}ms)")
    return report


def main() -> int:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--data-shards", type=int, default=0,
                    help="also dry-run an explicit (data_shards x 16) row-"
                         "sharded grid (DESIGN.md §8) in addition to the "
                         "production meshes")
    ap.add_argument("--trace", nargs="?", const=os.path.join(
                        REPORT_DIR, "dryrun_trace.json"),
                    default=None, metavar="OUT.json",
                    help="export per-phase lower/compile spans of the sweep "
                         "as a Perfetto-loadable Chrome trace (default "
                         "reports/dryrun_trace.json)")
    args = ap.parse_args()

    if args.trace:
        obs_trace.set_global_tracer(obs_trace.Tracer())

    base = None
    for multi_pod in (False, True):
        for agg in ("histogram", "argmax"):
            report = run(agg, multi_pod=multi_pod)
            if agg == "histogram" and not multi_pod:
                base = report
    # Async double-buffered exchange (DESIGN.md §10): same logical payload,
    # two overlapping transfers — collective bytes must NOT grow.
    async_r = run("histogram", multi_pod=False, async_exchange=True)
    if base["collective_bytes_per_dev"]:
        ratio = (async_r["collective_bytes_per_dev"]
                 / base["collective_bytes_per_dev"])
        print(f"[OK] async exchange collective-bytes ratio vs sync: "
              f"{ratio:.3f}x (must stay ~1.0)")
    if args.data_shards:
        run("histogram", data_shards=args.data_shards)
        run("histogram", data_shards=args.data_shards, async_exchange=True)
    # Sibling-subtraction pipeline (DESIGN.md §6) on the paper-faithful
    # histogram exchange: the before/after is the compiled collective-bytes
    # cut of shipping only the left children at levels >= 1.
    sub = run("histogram", multi_pod=False, hist_subtraction=True)
    if sub["collective_bytes_per_dev"]:
        cut = base["collective_bytes_per_dev"] / sub["collective_bytes_per_dev"]
        print(f"[OK] subtraction collective-bytes cut (histogram mode): "
              f"{cut:.2f}x")
    # Round engine (DESIGN.md §9): deep-tree frontier compaction — the
    # before/after is the compiled collective-bytes cut of shipping only the
    # static live-slot budget at depth 5 instead of the 2^L frontier.
    deep = run("histogram", multi_pod=False, hist_subtraction=True,
               max_depth=5)
    comp = run("histogram", multi_pod=False, hist_subtraction=True,
               max_depth=5, max_active_nodes=4)
    if comp["collective_bytes_per_dev"]:
        cut = deep["collective_bytes_per_dev"] / comp["collective_bytes_per_dev"]
        print(f"[OK] depth-5 frontier-compaction collective-bytes cut: "
              f"{cut:.2f}x")
    if args.trace:
        n_events = perfetto.export_chrome_trace(
            args.trace, obs_trace.global_tracer(),
            metadata={"entry": "dryrun_fedgbf"},
        )
        print(f"[OK] dryrun trace: {n_events} events -> {args.trace}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
