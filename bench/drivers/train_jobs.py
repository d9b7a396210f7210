"""Traffic kind ``train_jobs``: whole Dynamic FedGBF training jobs, back to
back, through ``boosting.train_fedgbf`` as ``train_fedgbf.main`` calls it.

The table is fixed by the seed; job j draws its sampling key from
(seed, j).  Set-up makes the table, places it, builds the backend and runs
job 0, which compiles (or loads) every program a job uses.  The window then
runs jobs 1, 2, ... and starts no job after ``seconds``; ``train_round_s``
is the window's wall time, from the first job's start to the last job's
end, over the rounds of all its jobs.  With tracing, jobs 2 .. 1 +
``trace_jobs`` run under the profiler and are the stretch read.  After the
window, jobs drawn from the seed are checked against the plain reference.

Traffic parameters: ``backend`` (registry name), ``parties`` and
``data_shards`` (the 2-D mesh of a ``vfl-*-sharded`` backend),
``trace_jobs``, ``verify_jobs``.
"""

from __future__ import annotations

import contextlib
import time

import numpy as np

from bench import counts, datagen, peaks, reference, tracing


def job_key(seed: int, j: int):
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed), j)


def fedgbf_config(model: dict):
    from repro.core.types import FedGBFConfig, TreeConfig

    tree = TreeConfig(max_depth=model["max_depth"], num_bins=model["num_bins"],
                      lambda_=model["lambda"], gamma=model["gamma"],
                      min_child_weight=model["min_child_weight"],
                      hist_subtraction=model["hist_subtraction"])
    return FedGBFConfig(
        rounds=model["rounds"], learning_rate=model["learning_rate"],
        tree=tree, loss=model["loss"], base_score=model["base_score"],
        n_trees_max=model["trees_max"], n_trees_min=model["trees_min"],
        n_trees_speed=model["trees_speed"], rho_id_min=model["rho_id_min"],
        rho_id_max=model["rho_id_max"], rho_id_speed=model["rho_id_speed"],
        rho_feat=model["rho_feat"])


def place(x, y, traffic: dict, tree):
    """Device inputs and the backend the traffic names: one device, or the
    (data x party) mesh of a ``vfl-*`` backend with rows on ``data`` and
    feature columns on ``model``, as ``train_fedgbf.main`` places them."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import backend as backend_mod

    name = traffic["backend"]
    if not name.startswith("vfl"):
        return jnp.asarray(x), jnp.asarray(y), backend_mod.get_backend(name)
    from repro.launch.mesh import make_vfl_mesh

    parties, shards = traffic["parties"], traffic["data_shards"]
    if x.shape[1] % parties or x.shape[0] % shards:
        raise ValueError(f"{x.shape} does not split over {shards} x {parties}")
    mesh = make_vfl_mesh(parties, shards)
    return (jax.device_put(x, NamedSharding(mesh, P("data", "model"))),
            jax.device_put(y, NamedSharding(mesh, P("data"))),
            backend_mod.get_backend(name, mesh=mesh, tree=tree))


def run(env) -> dict:
    import jax

    from repro.core import boosting

    model = env.config["model"]
    t = time.perf_counter()
    table = datagen.credit_table(env.config["dataset"], env.seed)
    x, y = table.x_train, table.y_train
    env.note(f"set-up: table {x.shape} made in {time.perf_counter() - t:.3f} s")

    t = time.perf_counter()
    cfg = fedgbf_config(model)
    xd, yd, backend = place(x, y, env.traffic, cfg.tree)
    jax.block_until_ready((xd, yd))
    env.note(f"set-up: inputs placed, backend {backend.name} built in "
             f"{time.perf_counter() - t:.3f} s")

    def job(j):
        with jax.profiler.TraceAnnotation("bench.job"):
            m, hist = boosting.train_fedgbf(xd, yd, cfg, job_key(env.seed, j),
                                            backend=backend, eval_every=1)
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
        got = reference.check_training(x, y, job_key(env.seed, j), model, prog)
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
        out["layer"] = {"rounds": len(traced) * model["rounds"],
                        "wall_s": sum(traced),
                        "hist_least_s": len(traced) * hist,
                        "round_least_s": len(traced) * whole}
    return out
