"""Traffic kind ``open_loop``: scoring requests that arrive on a schedule.

A request is one client call carrying one row or more.  The schedule is
open loop: ``rate_rps`` x ``seconds`` arrivals with exponential gaps, whose
set of gaps and set of sizes come from ``base_seed`` and whose order comes
from ``--seed``, so every seed offers the same work.  A share
``single_row_share`` of requests carries one row, the rest a log-uniform
count in [``multi_rows_min``, ``multi_rows_max``]; rows are drawn from the
table's test split.

The loop hands every request that is due to ``serve_fedgbf.serve_stream``
(a ``ModelSlot`` and a ``BatchLadder`` of ``ladder_sizes(ladder_max,
ladder_min)``, no p99 budget), with zero rows appended so that the last
microbatch fills a rung.  A request's latency runs from its due time to its
scores being on the host.  Requests due after the window closes are not
offered; those due inside it are all served, after the close if need be.

The model served is made from the seed by the benchmark itself, in the
configuration's shape (trees per round from its schedule, depth, bins),
so the reference traverses tables the program did not make.
"""

from __future__ import annotations

import contextlib
import math
import time

import numpy as np

from bench import counts, datagen, peaks, reference, tracing

UNSPLIT_SHARE = 0.1   # nodes left unsplit in the made ensemble (assumed)
TRACE_SETTLE_S = 0.5  # from the profiler's start to the stretch it reads
LEAF_SCALE = 0.5      # standard deviation of the made leaf weights (assumed)


def make_ensemble(model: dict, x: np.ndarray, seed: int) -> dict:
    """A Dynamic FedGBF ensemble of the configuration's shape, from the seed:
    quantile edges of ``x``, random split features and bin thresholds,
    normal leaf weights, per-tree scale lr / trees of its round."""
    rng = np.random.default_rng([seed, 2])
    depth, num_bins = model["max_depth"], model["num_bins"]
    per_round = [reference.n_trees(model, m) for m in range(1, model["rounds"] + 1)]
    trees, d = sum(per_round), x.shape[1]
    internal = 2 ** depth - 1
    feature = rng.integers(0, d, (trees, internal)).astype(np.int32)
    threshold = rng.integers(0, num_bins - 1, (trees, internal)).astype(np.int32)
    unsplit = rng.random((trees, internal)) < UNSPLIT_SHARE
    feature[unsplit], threshold[unsplit] = -1, num_bins
    return {
        "feature": feature, "threshold": threshold,
        "leaf": rng.normal(0.0, LEAF_SCALE, (trees, 2 ** depth)).astype(np.float32),
        "scale": np.repeat([np.float32(model["learning_rate"] / k)
                            for k in per_round], per_round).astype(np.float32),
        "edges": reference.quantile_edges(x, num_bins),
        "base": float(model["base_score"]), "depth": depth,
        "round_offsets": tuple(int(v) for v in np.cumsum([0] + per_round)),
        "learning_rate": float(model["learning_rate"]), "loss": model["loss"],
    }


def packed(ens: dict):
    import jax.numpy as jnp

    from repro.core.types import PackedEnsemble

    return PackedEnsemble(
        feature=jnp.asarray(ens["feature"]), threshold=jnp.asarray(ens["threshold"]),
        gain=jnp.zeros(ens["feature"].shape, jnp.float32),
        leaf_weight=jnp.asarray(ens["leaf"]), tree_scale=jnp.asarray(ens["scale"]),
        bin_edges=jnp.asarray(ens["edges"]), round_offsets=ens["round_offsets"],
        learning_rate=ens["learning_rate"], base_score=ens["base"],
        loss=ens["loss"], max_depth=ens["depth"])


def schedule(traffic: dict, rate: float, seconds: float, seed: int):
    """Due times (N,) in seconds and row offsets (N+1,) of the requests,
    and the run's generator, which then draws the rows they carry."""
    n = max(1, int(round(rate * seconds)))
    base = np.random.default_rng(traffic["base_seed"])
    gaps = base.exponential(size=n)
    multi = base.random(n) >= traffic["single_row_share"]
    lo, hi = traffic["multi_rows_min"], traffic["multi_rows_max"]
    many = np.floor(np.exp(base.uniform(math.log(lo), math.log(hi + 1), n)))
    sizes = np.where(multi, np.clip(many, lo, hi), 1).astype(np.int64)
    run = np.random.default_rng([seed, 3])
    gaps, sizes = run.permutation(gaps), run.permutation(sizes)
    due = (np.cumsum(gaps) - gaps[0]) / gaps.sum() * seconds
    offsets = np.concatenate([[0], np.cumsum(sizes)])
    return due, offsets, run


class Server:
    """What set-up builds: the slot, the warmed ladder, the stream metrics."""

    def __init__(self, ens: dict, traffic: dict) -> None:
        from repro.launch import serve_fedgbf as sf

        self.sf = sf
        self.model = packed(ens)
        self.ladder = sf.BatchLadder(sf.ladder_sizes(traffic["ladder_max"],
                                                     traffic["ladder_min"]))
        self.slot = sf.ModelSlot(self.model, traffic["impl"])
        self.metrics = sf.StreamMetrics(self.ladder.max_size)
        self.d = ens["edges"].shape[0]
        self.ladder.warm(self.model, self.d, traffic["impl"])
        for s in self.ladder.sizes:
            self.serve(np.zeros((s, self.d), np.float32))

    def serve(self, rows: np.ndarray) -> np.ndarray:
        """Scores of ``rows``, handed over in whole rungs."""
        r, top = rows.shape[0], self.ladder.max_size
        rem = r % top
        fill = 0 if rem == 0 else next(s for s in self.ladder.sizes if s >= rem) - rem
        if fill:
            rows = np.concatenate([rows, np.zeros((fill, self.d), rows.dtype)])
        out, _ = self.sf.serve_stream(self.slot, rows, ladder=self.ladder,
                                      metrics=self.metrics)
        return out[:r]


def open_loop(server: Server, rows: np.ndarray, due: np.ndarray,
              offsets: np.ndarray, trace_from=None, trace_seconds=0.0,
              trace_dir=None) -> dict:
    """Serve every request on its schedule; returns per-request dispatch and
    completion times (s after the start), the scores, the loop's lateness at
    each idle wake-up and per-call (start, end, microbatches)."""
    n = due.shape[0]
    dispatched, done = np.empty(n), np.empty(n)
    scores = np.empty(offsets[-1], np.float32)
    late, calls = [], []
    profiling, traced = False, None   # traced: [start, end] of the stretch read
    with contextlib.ExitStack() as profile, contextlib.ExitStack() as stretch:
        t0 = time.perf_counter()
        i = 0
        while i < n:
            now = time.perf_counter() - t0
            if trace_from is not None and not profiling and now >= trace_from:
                profile.enter_context(tracing.profiler(trace_dir))
                profiling = True
            # the profiler's start stalls the loop; the stretch read begins
            # once the backlog it leaves is served
            if profiling and traced is None and now >= trace_from + TRACE_SETTLE_S:
                stretch.enter_context(tracing.window())
                traced = [now, None]
            if traced and traced[1] is None and now >= traced[0] + trace_seconds:
                stretch.close()
                traced[1] = now
            i = _step(server, rows, due, offsets, t0, i, dispatched, done,
                      scores, late, calls)
        if traced and traced[1] is None:
            stretch.close()
            traced[1] = time.perf_counter() - t0
    return {"dispatched": dispatched, "done": done, "scores": scores,
            "late": np.asarray(late), "calls": calls, "traced": traced}


def _step(server, rows, due, offsets, t0, i, dispatched, done, scores, late,
          calls) -> int:
    """Wait for request ``i`` if it is not due yet, then serve every request
    that is due; returns the index of the first request not served."""
    import jax

    now = time.perf_counter() - t0
    if due[i] > now:
        with jax.profiler.TraceAnnotation("bench.wait"):
            if due[i] - now > 1e-3:
                time.sleep(due[i] - now - 5e-4)
            while time.perf_counter() - t0 < due[i]:
                pass
        now = time.perf_counter() - t0
        late.append(now - due[i])
    j = int(np.searchsorted(due, now, side="right"))
    r0, r1 = offsets[i], offsets[j]
    b0 = server.metrics.batches.value
    with jax.profiler.TraceAnnotation("bench.serve_call"):
        scores[r0:r1] = server.serve(rows[r0:r1])
    end = time.perf_counter() - t0
    dispatched[i:j], done[i:j] = now, end
    calls.append((now, end, server.metrics.batches.value - b0))
    return j


def setup(env):
    """Table, made ensemble, warmed server and the request rows."""
    t = time.perf_counter()
    table = datagen.credit_table(env.config["dataset"], env.seed)
    ens = make_ensemble(env.config["model"], table.x_train, env.seed)
    env.note(f"set-up: table and ensemble ({ens['leaf'].shape[0]} trees) made "
             f"in {time.perf_counter() - t:.3f} s")
    t = time.perf_counter()
    server = Server(ens, env.traffic)
    env.note(f"set-up: {len(server.ladder.sizes)} ladder rungs warmed "
             f"(trace, compile or cache load) in {time.perf_counter() - t:.3f} s")
    return table, ens, server


def run(env) -> dict:
    table, ens, server = setup(env)
    t = time.perf_counter()
    tr = env.traffic
    due, offsets, rng = schedule(tr, tr["rate_rps"], env.seconds, env.seed)
    rows = table.x_test[rng.integers(0, table.x_test.shape[0], offsets[-1])]
    env.note(f"set-up: {due.size} requests, {rows.shape[0]} rows drawn in "
             f"{time.perf_counter() - t:.3f} s")

    env.settle()
    setup_s = time.perf_counter() - env.t0
    res = open_loop(server, rows, due, offsets,
                    trace_from=env.seconds / 2 if env.trace else None,
                    trace_seconds=tr["trace_seconds"], trace_dir=env.trace_dir)
    memory = env.memory_peak()
    latency = res["done"] - due
    late = res["late"]
    env.note(f"window: {due.size} requests, {rows.shape[0]} rows, "
             f"{len(res['calls'])} calls, last done {res['done'][-1]:.4f} s, "
             f"latency p50 {np.median(latency) * 1e3:.4f} ms, "
             f"p99 {np.percentile(latency, 99) * 1e3:.4f} ms")
    slow = sorted(res["calls"], key=lambda c: c[0] - c[1])[:3]
    env.note("slowest calls (start s, seconds, microbatches): " + ", ".join(
        f"({s:.4f}, {e - s:.4f}, {b})" for s, e, b in slow))
    env.note(f"loop lateness at idle wake-ups: {late.size} wake-ups, p50 "
             f"{np.median(late) * 1e3 if late.size else 0:.4f} ms, p99 "
             f"{np.percentile(late, 99) * 1e3 if late.size else 0:.4f} ms, max "
             f"{late.max() * 1e3 if late.size else 0:.4f} ms")

    pick = np.random.default_rng([env.seed, 4]).choice(
        due.size, size=min(tr["verify_requests"], due.size), replace=False)
    pick = np.union1d(pick, [int(np.argmax(np.diff(offsets)))])
    idx = np.concatenate([np.arange(offsets[k], offsets[k + 1]) for k in pick])
    t = time.perf_counter()
    want = reference.scores(ens, rows[idx])
    got = res["scores"][idx].astype(np.float64)
    gap = float(np.max(np.abs(got - want))) if np.isfinite(got).all() else reference.BIG
    env.note(f"reference: {pick.size} requests, {idx.size} rows checked in "
             f"{time.perf_counter() - t:.3f} s")
    bad = np.add.reduceat(~np.isfinite(res["scores"]), offsets[:-1]) > 0

    out = {"attempted": int(due.size), "failed": int(bad.sum()),
           "setup_s": setup_s, "memory_peak_bytes": memory,
           "readings": {"score_gap": gap},
           "end_to_end": {"serve_p50_ms": float(np.median(latency)) * 1e3}}
    if env.trace and res["traced"] is not None:
        lo, hi = res["traced"]
        inside = (res["dispatched"] >= lo) & (res["dispatched"] < hi)
        calls = [c for c in res["calls"] if lo <= c[0] < hi]
        real = int(offsets[np.flatnonzero(inside) + 1].sum()
                   - offsets[np.flatnonzero(inside)].sum())
        model = env.config["model"]
        work = counts.traversal(real, ens["leaf"].shape[0], model["max_depth"],
                                server.d, calls=len(calls))
        # the host path's own numbers come from the stretch before the
        # profiler starts: under it the loop runs slower than it serves
        a, b = env.seconds / 6, env.seconds / 2
        calm = (res["dispatched"] >= a) & (res["dispatched"] < b)
        out["layer"] = {
            "rows": real, "wall_s": hi - lo,
            "queue_s": (res["dispatched"] - due)[calm],
            "tail_s": latency[calm],
            "batch_s": np.array([(e - s) / max(1, k) for s, e, k in res["calls"]
                                 if a <= s < b]),
            "predict_least_s": peaks.least_seconds(*work, env.device_kind),
        }
    return out
