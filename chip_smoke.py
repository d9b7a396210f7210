#!/usr/bin/env python3
"""Chip smoke test: Dynamic FedGBF trains and serves on a TPU.

Drives the main path once through the entry points a user calls, at the
paper's Give-Me-Some-Credit shape (150k x 10, 105k training rows, seeded
synthetic data): ``train_fedgbf``'s Dynamic FedGBF configuration (trees
5 -> 2, depth 3, B 32) and ``serve_fedgbf``'s stream path.

Default run (one chip, one process):
  1. trains with ``local-pallas`` (the Pallas histogram kernel) and with
     ``local`` (XLA segment-sum) as the reference, both on the chip;
  2. packs the ensemble and scores 32,768 test rows through
     ``serve_fedgbf.serve_stream`` (``ModelSlot`` + ``_score_batch``) with
     ``fused-pallas``; the scores must be bit-exact against ``fused``.

``--four-chips`` (a 2x2 host) runs only ``vfl-histogram-sharded`` with
2 parties x 2 data shards through ``make_vfl_mesh``, inputs placed across
the mesh, against the same ``local`` reference.

Each training comparison has two bars, the repo's own:
  * one round (both sides see the same gradients, so only the order of
    the histogram sums differs): the same splits, leaves within rtol 1e-5 /
    atol 1e-6 — the CPU tests' bar between these backends and
    ``federation/selftest.py``'s fed-vs-central bar;
  * the whole 5-round run: from round 2 the gradients carry the first
    round's last-bit leaf differences, so a near-tied split may flip; this
    is the selftest's float-reassociation tolerance class (test AUC and
    logloss within 5e-3).  Split and leaf differences are printed.

Timings are printed as informational lines (``info:``); they are not
benchmark numbers.  The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Exits nonzero, printing no result, when JAX finds no TPU.

    python3 chip_smoke.py [--four-chips]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ROUNDS = 5
SERVE_ROWS = 32_768
SERVE_BATCH = 8_192
LEAF_RTOL, LEAF_ATOL = 1e-5, 1e-6  # the CPU tests' local-pallas == local bar
METRIC_BOUND = 5e-3  # selftest's float-reassociation tolerance (AUC, logloss)


def info(msg: str) -> None:
    print(f"info: {msg}", flush=True)


def compare(ref, got) -> dict:
    """Split structure and leaf agreement of two packed ensembles."""
    import numpy as np

    split_diff = ((np.asarray(ref.feature) != np.asarray(got.feature))
                  | (np.asarray(ref.threshold) != np.asarray(got.threshold)))
    leaf_ref = np.asarray(ref.leaf_weight)
    leaf_got = np.asarray(got.leaf_weight)
    trees = np.flatnonzero(split_diff.any(axis=1))
    return {
        "splits_differ": int(split_diff.sum()),
        "first_tree_differ": int(trees[0]) if trees.size else None,
        "max_leaf_diff": float(np.max(np.abs(leaf_ref - leaf_got))),
        "match": bool(not split_diff.any() and np.allclose(
            leaf_got, leaf_ref, rtol=LEAF_RTOL, atol=LEAF_ATOL)),
    }


def train(x, y, cfg, backend, repeat: int = 1):
    """``boosting.train_fedgbf`` as ``train_fedgbf.main`` calls it; with
    ``repeat=2`` the first call compiles and the second is the steady run."""
    import jax

    from repro.core import boosting

    times = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        model, _ = boosting.train_fedgbf(x, y, cfg, jax.random.PRNGKey(0),
                                         backend=backend, verbose=False)
        jax.block_until_ready(model.forests[-1].leaf_weight)
        times.append(time.perf_counter() - t0)
    return model, times


def held_out_metrics(model, ds, loss: str) -> dict:
    import jax.numpy as jnp

    from repro.core import boosting
    from repro.core import objective as objective_mod

    margin = boosting.predict(model, jnp.asarray(ds.x_test))
    return objective_mod.get_objective(loss).evaluate(jnp.asarray(ds.y_test),
                                                      margin)


def check_training(name, ds, cfg, make_inputs, backend):
    """``backend`` against ``local`` on the same chip: the one-round and the
    whole-run bars of the module docstring.  Returns (passed, the whole-run
    model ``backend`` trained)."""
    from repro.core.types import pack_ensemble

    x, y = make_inputs()
    x_ref, y_ref = make_inputs(reference=True)
    one = dataclasses.replace(cfg, rounds=1)
    m_got, _ = train(x, y, one, backend)
    m_ref, _ = train(x_ref, y_ref, one, "local")
    first = compare(pack_ensemble(m_ref), pack_ensemble(m_got))
    print(f"train 1 round {name} vs local: {json.dumps(first)}", flush=True)

    m_got, t_got = train(x, y, cfg, backend, repeat=2)
    m_ref, t_ref = train(x_ref, y_ref, cfg, "local", repeat=2)
    full = compare(pack_ensemble(m_ref), pack_ensemble(m_got))
    got = held_out_metrics(m_got, ds, cfg.loss)
    ref = held_out_metrics(m_ref, ds, cfg.loss)
    deltas = {k: abs(got[k] - ref[k]) for k in ("auc", "loss")}
    print(f"train {cfg.rounds} rounds {name} vs local: {json.dumps(full)} "
          f"test auc {got['auc']:.6f} vs {ref['auc']:.6f}, |d auc| "
          f"{deltas['auc']:.2e}, |d logloss| {deltas['loss']:.2e}",
          flush=True)
    for label, (cold, warm) in ((name, t_got), ("local", t_ref)):
        info(f"{label}: first call {cold:.2f} s (compile included), steady "
             f"{warm / cfg.rounds:.4f} s/round over {cfg.rounds} rounds")
    ok = first["match"] and all(v <= METRIC_BOUND for v in deltas.values())
    return ok, m_got


def check_serving(ds, model) -> bool:
    """Stream-score the test rows with ``fused-pallas`` and ``fused``."""
    import numpy as np

    from repro.core.types import pack_ensemble
    from repro.launch import serve_fedgbf

    packed = pack_ensemble(model)
    requests = np.asarray(ds.x_test[:SERVE_ROWS], np.float32)
    scores = {}
    for impl in ("fused-pallas", "fused"):
        ladder = serve_fedgbf.BatchLadder([SERVE_BATCH])
        ladder.warm(packed, requests.shape[1], impl)
        t0 = time.perf_counter()
        scores[impl], sm = serve_fedgbf.serve_stream(
            serve_fedgbf.ModelSlot(packed, impl), requests, ladder=ladder)
        wall = time.perf_counter() - t0
        q = sm.quantiles_ms()
        info(f"serve {impl}: {SERVE_ROWS / wall:,.0f} rows/s over "
             f"{SERVE_ROWS} rows, batch {SERVE_BATCH}, "
             f"p50 {q[0.5]:.3f} ms p99 {q[0.99]:.3f} ms")
    a, b = scores["fused-pallas"], scores["fused"]
    exact = bool(np.array_equal(a, b, equal_nan=True))
    print(f"serve fused-pallas vs fused over {SERVE_ROWS} rows, "
          f"{packed.total_trees} trees: bit_exact={exact} "
          f"max_abs_diff={float(np.nanmax(np.abs(a - b))):.3e}", flush=True)
    return exact


def one_chip(ds, cfg) -> bool:
    import jax.numpy as jnp

    def inputs(reference=False):
        return jnp.asarray(ds.x_train), jnp.asarray(ds.y_train)

    ok, model = check_training("local-pallas", ds, cfg, inputs,
                               "local-pallas")
    return check_serving(ds, model) and ok


def four_chips(ds, cfg) -> bool:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import backend as backend_mod
    from repro.data import tabular
    from repro.federation import vfl  # noqa: F401  (registers vfl-* backends)
    from repro.launch.mesh import make_vfl_mesh

    parties, shards = 2, 2
    mesh = make_vfl_mesh(parties, shards)
    x_np, _ = tabular.pad_features(ds.x_train, parties)

    def inputs(reference=False):
        if reference:
            return jnp.asarray(x_np), jnp.asarray(ds.y_train)
        x = jax.device_put(x_np, NamedSharding(mesh, P("data", "model")))
        y = jax.device_put(ds.y_train, NamedSharding(mesh, P("data")))
        return x, y

    x, y = inputs()
    info(f"inputs on {len(x.sharding.device_set)} devices: x "
         f"{x.sharding.spec}, y {y.sharding.spec}")
    backend = backend_mod.get_backend("vfl-histogram-sharded", mesh=mesh,
                                      tree=cfg.tree)
    ok, _ = check_training(
        f"vfl-histogram-sharded ({parties} parties x {shards} shards)",
        ds, cfg, inputs, backend)
    return ok


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2 parties x 2 data shards sharded "
                         "path and its local reference (a 2x2 host)")
    args = ap.parse_args()

    from repro.launch import compile_cache

    cache_dir = compile_cache.enable()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              "nothing was run", file=sys.stderr)
        return 1
    if args.four_chips and len(devices) < 4:
        print(f"chip_smoke: --four-chips needs 4 chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    info(f"compile cache {cache_dir}")

    from repro.core.types import TreeConfig
    from repro.data import synthetic
    from repro.launch import train_fedgbf

    ds = synthetic.load("give_me_some_credit")
    tree = TreeConfig(max_depth=3, num_bins=train_fedgbf.NUM_BINS)
    cfg = train_fedgbf.model_config("dynamic_fedgbf", ROUNDS, tree)
    info(f"give_me_some_credit: {ds.x_train.shape[0]} train rows x "
         f"{ds.x_train.shape[1]} features, {ROUNDS} rounds, depth "
         f"{tree.max_depth}, B {tree.num_bins}")
    ok = four_chips(ds, cfg) if args.four_chips else one_chip(ds, cfg)
    print(json.dumps({"ok": ok, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
