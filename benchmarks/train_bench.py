"""Training-engine benchmark: legacy per-round loop vs scanned engine.

Measures the training hot path this PR rebuilds (DESIGN.md §4) on the
paper's Dynamic FedGBF schedule (trees 5 -> 2, rho 0.1 -> 0.3), which is
exactly the case that breaks the legacy loop's compile story: every distinct
(n_trees,) shape compiles a fresh per-round XLA program, while the scanned
engine factors the schedule into constant-width segments scanned inside ONE
compiled program — no recompiles, no per-round host sync.

Reported:
  * ``*_compiles``      — XLA programs compiled per engine (loop: one per
    distinct scheduled tree count, >= 4 for 5 -> 2; scan: exactly 1),
    read from the engines' jit caches;
  * ``*_cold_s``        — first call, includes all compiles;
  * ``*_steady_round_s``— warm second call / rounds (the recompile-free
    per-round cost);
  * ``metric_max_abs_diff`` — max |loop - scan| over all history metrics
    (the 1e-5 equivalence bar of the ISSUE);
  * ``subtraction``      — the sibling-subtraction pipeline (DESIGN.md §6)
    on/off steady-state round time under the scanned engine, its compile
    count (must stay 1), metric drift vs the direct pipeline, and the
    conservative ``speedup_floor`` benchmarks/ci_guard.py enforces;
  * ``telemetry``        — the observability layer (DESIGN.md §12) on vs
    off: traced steady-round time (telemetry=True + live Tracer + segment
    ticks) against the untraced baseline, the overhead ratio ci_guard
    gates at <= 1.05x, and the traced variant's own compile count (the
    telemetry flag is jit-static, so each variant compiles exactly once).

Results land in reports/train_bench.json and the repo-root BENCH_train.json.

The ``sharded`` section (DESIGN.md §8) measures row-sharded multi-host
throughput at >= 1M synthetic rows under ``vfl-histogram-sharded`` on a
(data x model) grid of forced host devices — run in a subprocess so the
parent's jax device state is untouched (same re-exec pattern as
comm_bench).  The recorded ``rows_per_s_floor`` (half the measurement, so
CI machine variance passes but a sharding regression fails) is enforced by
benchmarks/ci_guard.py against the committed BENCH_train.json.

    PYTHONPATH=src python -m benchmarks.train_bench [--smoke]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import cpu_child_env, save_report, scale
from repro.core import boosting
from repro.core import forest as forest_mod
from repro.core.types import TreeConfig
from repro.launch.mesh import make_mesh
from repro.obs import trace as obs_trace

#: sharded-throughput bench shape: >= 1M rows (the ISSUE floor), modest
#: width/rounds so the CI smoke stays minutes, not hours, on one CPU.
SHARDED_N = 1_048_576
SHARDED_D = 8
SHARDED_ROUNDS = 2
SHARDED_GRID = (4, 2)  # (data_shards, parties) -> 8 forced host devices


def _sharded_child() -> None:
    """Child-process body: train vfl-histogram-sharded at >= 1M rows on a
    (4 data x 2 model) grid of forced host devices and print one JSON line
    (the parent parses stdout's last line)."""
    from repro.federation import vfl

    data_shards, parties = SHARDED_GRID
    mesh = make_mesh((data_shards, parties), ("data", "model"))
    tree = TreeConfig(max_depth=3, num_bins=32, hist_subtraction=True)
    cfg = boosting.FedGBFConfig(
        rounds=SHARDED_ROUNDS, tree=tree, n_trees_max=2, n_trees_min=2,
        rho_id_min=0.3, rho_id_max=0.3,
    )
    backend = vfl.make_vfl_backend(
        mesh, tree, aggregation="histogram", shard_samples=True
    )
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(SHARDED_N, SHARDED_D)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 2, SHARDED_N), jnp.float32)

    with jax.set_mesh(mesh):
        t0 = time.perf_counter()
        model, _ = boosting.train_fedgbf(
            x, y, cfg, jax.random.PRNGKey(0), backend=backend,
            eval_every=SHARDED_ROUNDS,
        )
        jax.block_until_ready(model.forests[-1].leaf_weight)
        cold = time.perf_counter() - t0
        warm = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            model, _ = boosting.train_fedgbf(
                x, y, cfg, jax.random.PRNGKey(0), backend=backend,
                eval_every=SHARDED_ROUNDS,
            )
            jax.block_until_ready(model.forests[-1].leaf_weight)
            warm = min(warm, time.perf_counter() - t0)

    print(json.dumps({
        "backend": "vfl-histogram-sharded",
        "n": SHARDED_N, "d": SHARDED_D, "rounds": SHARDED_ROUNDS,
        "data_shards": data_shards, "parties": parties,
        "cold_s": cold, "warm_s": warm,
        "rows_per_s": SHARDED_N * SHARDED_ROUNDS / warm,
    }))


def _sharded_bench() -> dict:
    """Run the >= 1M-row sharded throughput measurement in a CPU subprocess
    with forced host devices (the parent may already hold a 1-device jax)."""
    env = cpu_child_env(SHARDED_GRID[0] * SHARDED_GRID[1])
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.train_bench", "--sharded-child"],
        env=env, check=True, capture_output=True, text=True,
    )
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    # floor at half the measurement: CI machine variance passes, a real
    # sharded-pipeline regression (or a silent fallback to 1 device) fails
    out["rows_per_s_floor"] = round(0.5 * out["rows_per_s"], 1)
    return out


def _train(engine, x, y, cfg, eval_every, tracer=None, telemetry=False):
    t0 = time.perf_counter()
    model, hist = boosting.train_fedgbf(
        x, y, cfg, jax.random.PRNGKey(0), eval_every=eval_every,
        engine=engine, tracer=tracer, telemetry=telemetry,
    )
    jax.block_until_ready(model.forests[-1].leaf_weight)
    return model, hist, time.perf_counter() - t0


def main(smoke: bool = False) -> list:
    quick = smoke or scale() == "quick"
    n, d, rounds = (3_000, 12, 8) if quick else (30_000, 23, 20)
    eval_every = rounds  # isolate the engine: metrics only at the last round

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 2, n), jnp.float32)
    # hist_subtraction now defaults ON; this bench contrasts the pipelines,
    # so the base config pins the direct pass explicitly.
    cfg = boosting.dynamic_fedgbf_config(
        rounds=rounds,
        tree=TreeConfig(max_depth=3, num_bins=32, hist_subtraction=False),
    )

    results = {
        "n": n, "d": d, "rounds": rounds,
        "n_trees_schedule": "5 -> 2 (dynamic decay)",
        "rho_id_schedule": "0.1 -> 0.3 (dynamic increase)",
        "backend": jax.default_backend(),
    }

    warm_repeats = 3  # steady state = best warm run (same policy as predict_bench)

    # -- legacy per-round loop ------------------------------------------------
    jax.clear_caches()
    _, h_loop_cold, cold_loop = _train("loop", x, y, cfg, eval_every)
    results["loop_compiles"] = forest_mod.build_forest._cache_size()
    warm_loop = float("inf")
    for _ in range(warm_repeats):
        _, h_loop, t = _train("loop", x, y, cfg, eval_every)
        warm_loop = min(warm_loop, t)
    results["loop_cold_s"] = cold_loop
    results["loop_steady_round_s"] = warm_loop / rounds

    # -- scanned engine -------------------------------------------------------
    jax.clear_caches()
    _, h_scan_cold, cold_scan = _train("scan", x, y, cfg, eval_every)
    results["scan_compiles"] = boosting._scan_train_program._cache_size()
    warm_scan = float("inf")
    for _ in range(warm_repeats):
        _, h_scan, t = _train("scan", x, y, cfg, eval_every)
        warm_scan = min(warm_scan, t)
    results["scan_cold_s"] = cold_scan
    results["scan_steady_round_s"] = warm_scan / rounds

    results["steady_round_speedup_vs_loop"] = (
        results["loop_steady_round_s"] / results["scan_steady_round_s"]
    )
    results["distinct_n_trees"] = len(set(h_loop.n_trees))
    results["metric_max_abs_diff"] = max(
        abs(a[k] - b[k])
        for a, b in zip(h_loop.train, h_scan.train) for k in a
    )

    # -- sibling-subtraction pipeline (DESIGN.md §6), scanned engine ----------
    # Same schedule with hist_subtraction on: levels >= 1 accumulate only the
    # left children and derive the siblings.  Tracked: steady-state round
    # time on vs off, the compile count (must stay exactly 1 — the switch is
    # jit-static), and the end-metric drift vs the direct pipeline.  The
    # recorded ``speedup_floor`` is a deliberately conservative fraction of
    # the measurement; benchmarks/ci_guard.py fails a future run that drops
    # below the committed floor.
    sub_cfg = dataclasses.replace(
        cfg, tree=dataclasses.replace(cfg.tree, hist_subtraction=True)
    )
    jax.clear_caches()
    _, h_sub_cold, cold_sub = _train("scan", x, y, sub_cfg, eval_every)
    sub_compiles = boosting._scan_train_program._cache_size()
    warm_sub = float("inf")
    for _ in range(warm_repeats):
        _, h_sub, t = _train("scan", x, y, sub_cfg, eval_every)
        warm_sub = min(warm_sub, t)
    on_round = warm_sub / rounds
    speedup = results["scan_steady_round_s"] / on_round
    results["subtraction"] = {
        "scan_compiles": sub_compiles,
        "cold_s": cold_sub,
        "on_steady_round_s": on_round,
        "off_steady_round_s": results["scan_steady_round_s"],
        "on_off_speedup_x": speedup,
        "metric_max_abs_diff_vs_direct": max(
            abs(a[k] - b[k])
            for a, b in zip(h_scan.train, h_sub.train) for k in a
        ),
        # guard floor: 75% of the measured speedup, so normal CI timing noise
        # passes but a real pipeline regression does not
        "speedup_floor": round(0.75 * speedup, 3),
    }
    # -- observability overhead (DESIGN.md §12), scanned engine ---------------
    # Traced = telemetry=True (in-graph liveness block through the scan ys)
    # + a live Tracer + segment-tick callbacks.  Measured with a fresh cache
    # so the traced variant's own compile count is visible: the telemetry
    # flag is jit-STATIC, so the traced program also compiles exactly once.
    # The overhead ratio ci_guard gates at <= 1.05x is taken from
    # INTERLEAVED traced/untraced warm runs (min of each) — alternating the
    # two variants inside one measurement window cancels machine drift that
    # would otherwise swamp a ~1% effect when the baseline was timed in a
    # different section of the bench.
    jax.clear_caches()
    tr = obs_trace.Tracer()
    _, _, cold_tele = _train("scan", x, y, cfg, eval_every,
                             tracer=tr, telemetry=True)
    tele_compiles = boosting._scan_train_program._cache_size()
    warm_tele = warm_plain = float("inf")
    for _ in range(warm_repeats + 2):
        _, h_tele, t = _train("scan", x, y, cfg, eval_every,
                              tracer=obs_trace.Tracer(), telemetry=True)
        warm_tele = min(warm_tele, t)
        _, _, t = _train("scan", x, y, cfg, eval_every)
        warm_plain = min(warm_plain, t)
    traced_round = warm_tele / rounds
    plain_round = warm_plain / rounds
    results["telemetry"] = {
        "scan_compiles": tele_compiles,
        "cold_s": cold_tele,
        "traced_steady_round_s": traced_round,
        "untraced_steady_round_s": plain_round,
        "overhead_x": traced_round / plain_round,
        "liveness_rounds": len(h_tele.telemetry.get("sampled_entries", [])),
        "segments": len(h_tele.segments),
    }

    # -- row-sharded multi-host throughput (DESIGN.md §8), >= 1M rows --------
    results["sharded"] = _sharded_bench()
    sh = results["sharded"]

    results["interpretation"] = (
        "the loop compiles one forest program per distinct scheduled tree "
        "count and host-syncs every round; the scanned engine factors the "
        "schedule into constant-width segments scanned inside ONE compiled "
        "program (masks drawn in one batched vmap, metrics evaluated "
        "in-graph), so it does exactly the scheduled work at the same "
        "vmapped width with zero recompiles and zero per-round "
        "dispatch/sync overhead."
    )

    save_report("train_bench", results)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCH_train.json"), "w") as f:
        json.dump(results, f, indent=1, default=float)

    sub = results["subtraction"]
    print(
        f"  loop: {results['loop_compiles']} compiles, cold {cold_loop:.2f}s, "
        f"steady {results['loop_steady_round_s']*1e3:.1f} ms/round\n"
        f"  scan: {results['scan_compiles']} compile, cold {cold_scan:.2f}s, "
        f"steady {results['scan_steady_round_s']*1e3:.1f} ms/round "
        f"({results['steady_round_speedup_vs_loop']:.2f}x)\n"
        f"  scan+subtraction: {sub['scan_compiles']} compile, "
        f"steady {sub['on_steady_round_s']*1e3:.1f} ms/round "
        f"({sub['on_off_speedup_x']:.2f}x vs direct, "
        f"metric |diff| {sub['metric_max_abs_diff_vs_direct']:.1e})\n"
        f"  scan+telemetry: {results['telemetry']['scan_compiles']} compile, "
        f"steady {results['telemetry']['traced_steady_round_s']*1e3:.1f} "
        f"ms/round ({results['telemetry']['overhead_x']:.3f}x untraced)\n"
        f"  sharded ({sh['data_shards']}x{sh['parties']} grid, "
        f"n={sh['n']:,}): {sh['rows_per_s']/1e3:.0f}k rows/s "
        f"(floor {sh['rows_per_s_floor']/1e3:.0f}k)\n"
        f"  metric max |diff|: {results['metric_max_abs_diff']:.2e}"
    )
    return [
        ("train/loop_round", results["loop_steady_round_s"] * 1e6,
         f"{results['loop_compiles']} programs"),
        ("train/scan_round", results["scan_steady_round_s"] * 1e6,
         f"1 program, {results['steady_round_speedup_vs_loop']:.2f}x vs loop"),
        ("train/scan_round_subtraction", sub["on_steady_round_s"] * 1e6,
         f"1 program, {sub['on_off_speedup_x']:.2f}x vs direct pipeline"),
        ("train/scan_round_traced", results["telemetry"]
         ["traced_steady_round_s"] * 1e6,
         f"{results['telemetry']['overhead_x']:.3f}x untraced "
         f"(gate <= 1.05x)"),
        ("train/sharded_1M_rows", sh["warm_s"] * 1e6,
         f"{sh['rows_per_s']/1e3:.0f}k rows/s on "
         f"{sh['data_shards']}x{sh['parties']} grid"),
    ]


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes for CI (same comparisons; the "
                         "sharded section stays >= 1M rows)")
    ap.add_argument("--sharded-child", action="store_true",
                    help=argparse.SUPPRESS)  # internal: see _sharded_bench
    args = ap.parse_args()
    if args.sharded_child:
        _sharded_child()
    else:
        main(smoke=args.smoke)
