"""Traffic kind ``deep_train_jobs``: whole Dynamic FedGBF jobs on deep,
wide-binned trees over a HIGGS-shaped table, back to back.

The loop is ``train_jobs``' (set-up with a warm job 0, then jobs 1, 2, ...
until ``seconds``; ``train_round_s`` is the window's wall time over its
rounds; jobs 2 .. 1 + ``trace_jobs`` traced; the checked job replayed by
``reference.check_training``), with ``job_key``, ``fedgbf_config`` and
``place`` imported from it.  It differs in three things:

* the table comes from ``bench.datagen_higgs``;
* ``eval_every`` comes from the traffic (the train AUC only at a job's end
  at 20), since a deployment retraining on millions of rows does not sort
  them every round;
* set-up first compiles the widest histogram level on its own
  (``widest_level``), so a program that cannot build it fails fast.

The reference replays the checked job on one core: about 90 s at 700,000
rows, depth 8 and B 256.

Traffic parameters: those of ``train_jobs`` and ``eval_every``.  With
tracing, the ``layer`` keys are ``train_jobs``' and ``hist_nodes_least_s``,
the least time of the histograms the wide-frontier kernel form writes.
"""

from __future__ import annotations

import contextlib
import time

import numpy as np

from bench import counts, datagen_higgs, peaks, reference, tracing
from bench.drivers.train_jobs import fedgbf_config, job_key, place


def nodes_form_writes(model: dict, plan: list, d: int) -> float:
    """HBM bytes of the histograms the wide-frontier kernel form writes in
    the rounds of ``plan``: T x built nodes x d x B x 3 x 4 B for each
    level the program sends to that form (none where the program has no
    such form).  Under subtraction a level builds its parents' left
    children, one per parent, and level 0 its one root."""
    try:
        from repro.kernels.histogram.ops import histogram_form
    except ImportError:
        return 0.0
    b = model["num_bins"]
    built = [1] + [2 ** (lvl - 1) if model["hist_subtraction"] else 2 ** lvl
                   for lvl in range(1, model["max_depth"])]
    per_tree = sum(k * d * b * counts.STATS * counts.F32 for k in built
                   if histogram_form(k, b) == "nodes")
    return float(sum(trees for trees, _ in plan) * per_tree)


def widest_level(x, model: dict, backend) -> None:
    """Compile the backend's histogram provider alone at the round's widest
    level (the most trees, the last level's parents under subtraction), so
    that a program that cannot build that level stops here, in seconds,
    and not deep inside the warm job's compile."""
    import jax
    import jax.numpy as jnp

    n, d = x.shape
    trees, depth = model["trees_max"], model["max_depth"]
    if model["hist_subtraction"]:
        fn, nodes = backend.round_child_histogram_fn, 2 ** max(depth - 2, 0)
    else:
        fn, nodes = backend.round_histogram_fn, 2 ** max(depth - 1, 0)
    shapes = (jax.ShapeDtypeStruct((n, d), jnp.int32),
              jax.ShapeDtypeStruct((n,), jnp.float32),
              jax.ShapeDtypeStruct((n,), jnp.float32),
              jax.ShapeDtypeStruct((trees, n), jnp.float32),
              jax.ShapeDtypeStruct((trees, n), jnp.int32))
    jax.jit(lambda *a: fn(*a, nodes, model["num_bins"])).lower(
        *shapes).compile()


def run(env) -> dict:
    import jax

    from repro.core import boosting

    model = env.config["model"]
    eval_every = int(env.traffic["eval_every"])
    t = time.perf_counter()
    table = datagen_higgs.higgs_table(env.config["dataset"], env.seed)
    x, y = table.x_train, table.y_train
    env.note(f"set-up: table {x.shape} made in {time.perf_counter() - t:.3f} s")

    t = time.perf_counter()
    cfg = fedgbf_config(model)
    xd, yd, backend = place(x, y, env.traffic, cfg.tree)
    jax.block_until_ready((xd, yd))
    env.note(f"set-up: inputs placed, backend {backend.name} built in "
             f"{time.perf_counter() - t:.3f} s")

    t = time.perf_counter()
    widest_level(xd, model, backend)
    env.note(f"set-up: widest histogram level compiled in "
             f"{time.perf_counter() - t:.3f} s")

    def job(j):
        with jax.profiler.TraceAnnotation("bench.job"):
            m, hist = boosting.train_fedgbf(xd, yd, cfg, job_key(env.seed, j),
                                            backend=backend,
                                            eval_every=eval_every)
        return m, hist.final_margin

    t = time.perf_counter()
    job(0)
    env.note(f"set-up: warm job (trace, compile or cache load, run) "
             f"{time.perf_counter() - t:.3f} s")

    env.settle()
    traced_jobs = (range(2, 2 + env.traffic["trace_jobs"]) if env.trace
                   else range(0))
    jobs, traced = [], []
    t_start = time.perf_counter()
    setup_s = t_start - env.t0
    with contextlib.ExitStack() as stack:
        j = 1
        while True:
            if traced_jobs and j == traced_jobs[0]:
                stack.enter_context(tracing.profiler(env.trace_dir))
                stack.enter_context(tracing.window())
            s = time.perf_counter()
            m, margin = job(j)
            e = time.perf_counter()
            jobs.append((j, m, margin))
            if j in traced_jobs:
                traced.append(e - s)
                if j == traced_jobs[-1]:
                    stack.close()
            j += 1
            if e - t_start >= env.seconds and (not traced_jobs
                                               or j > traced_jobs[-1]):
                break
    window = e - t_start
    rounds = len(jobs) * model["rounds"]
    env.note(f"window: {len(jobs)} jobs, {rounds} rounds in {window:.4f} s")
    memory = env.memory_peak()
    del xd, yd

    rng = np.random.default_rng([env.seed, 1])
    picks = rng.choice(len(jobs), size=min(env.traffic["verify_jobs"],
                                           len(jobs)), replace=False)
    readings = {}
    t = time.perf_counter()
    for i in sorted(picks):
        j, m, margin = jobs[i]
        prog = {"edges": np.asarray(m.bin_edges),
                "forests": [(np.asarray(f.feature), np.asarray(f.threshold),
                             np.asarray(f.leaf_weight)) for f in m.forests],
                "margin": margin}
        got = reference.check_training(x, y, job_key(env.seed, j), model,
                                       prog)
        for k, v in got.items():
            readings[k] = max(readings.get(k, 0.0), v)
    env.note(f"reference: jobs {sorted(int(jobs[i][0]) for i in picks)} "
             f"checked in {time.perf_counter() - t:.3f} s")

    out = {"attempted": len(jobs), "failed": 0, "setup_s": setup_s,
           "memory_peak_bytes": memory, "readings": readings,
           "end_to_end": {"train_round_s": window / rounds}}
    if env.trace:
        n, d = x.shape
        b, depth = model["num_bins"], model["max_depth"]
        plan = reference.round_plan(model, n)
        hist = sum(peaks.least_seconds(*counts.histogram_round(k, tr, d, b),
                                       env.device_kind) for tr, k in plan)
        whole = sum(peaks.least_seconds(
            *counts.boosting_round(n, k, tr, d, b, depth), env.device_kind,
            env.chips) for tr, k in plan)
        nodes = peaks.least_seconds(0.0, nodes_form_writes(model, plan, d),
                                    env.device_kind)
        out["layer"] = {"rounds": len(traced) * model["rounds"],
                        "wall_s": sum(traced),
                        "hist_least_s": len(traced) * hist,
                        "round_least_s": len(traced) * whole}
        if nodes:
            out["layer"]["hist_nodes_least_s"] = len(traced) * nodes
    return out
