"""(Dynamic) FedGBF training (Algs. 1 & 3) and the SecureBoost baseline.

Two training engines share one contract (DESIGN.md §4):

* ``engine="scan"`` (default) — the static-shape scanned engine: the
  Dynamic FedGBF schedule (5 -> 2 trees, rho 0.1 -> 0.3) is factored into
  constant-width segments whose rounds run under ``lax.scan`` inside ONE
  compiled program, so run-time shapes never change — one XLA program
  total, no per-round recompiles, no per-round host sync (metrics are
  evaluated in-graph, gated by ``eval_every``, and fetched once at the end).
* ``engine="loop"`` — the legacy per-round Python loop, kept as the
  reference baseline: XLA caches one program per distinct (n_trees,) shape
  (the paper's 5 -> 2 schedule compiles at least 4) and every round
  host-syncs.  ``tests/test_train_engine.py`` asserts the scanned engine
  reproduces its history metrics to float tolerance.
"""

from __future__ import annotations

import time
from collections import OrderedDict
from dataclasses import dataclass, field
from functools import partial
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import backend as backend_mod
from repro.core import binning, dynamic
from repro.core import forest as forest_mod
from repro.core import objective as objective_mod
from repro.obs import compiles as compiles_mod
from repro.obs import trace as trace_mod
from repro.core.types import (
    EnsembleModel,
    FedGBFConfig,
    PackedEnsemble,
    forest_size,
    pack_ensemble,
)


@dataclass
class TrainHistory:
    """Per-round training record.

    ``n_trees``, ``rho_id`` and ``wall_time_s`` have one entry for EVERY
    round (length M) regardless of ``eval_every`` — the schedule and the
    spent wall time are facts about training, not about evaluation.  Only
    the metric evals are gated: ``rounds`` lists the (1-based) rounds at
    which metrics were computed and ``train``/``valid`` align with it.

    ``wall_time_s`` granularity: the loop engine times every round on the
    host, so its entries are per-round exact.  The scan engine runs all
    rounds inside ONE compiled program.  Under a tracer that records
    (``Tracer.records``: ``train_fedgbf --trace`` / ``--log-json``) the
    program carries in-program host ticks (``jax.debug.callback`` at the
    segment boundaries), so it measures true PER-SEGMENT walls and smears
    each segment's wall uniformly over its rounds — per-round resolution
    inside a segment is fundamentally unavailable without a per-round host
    sync, which the engine exists to avoid.  Otherwise (the default) the
    program holds no host callback, and the call's whole wall, trace and
    compile included, is smeared uniformly over every round.
    ``segments`` records the boundaries: one dict per segment (``width``,
    ``first_round`` 0-based, ``rounds``, ``root_delta_rows``, ``wall_s``,
    absolute host-clock ``t0``/``t1``; measured with ticks, uniform
    shares without) for the scan engine, one single-round entry per round
    for the loop engine.  ``overhead_s`` is the scan call's wall outside
    the segment ticks (trace + compile + dispatch + history fetch) so
    ``sum(wall_time_s) + overhead_s`` reconstructs the full call; 0 without
    ticks.

    ``telemetry`` (filled when training runs with ``telemetry=True``) holds
    the in-graph per-round stats fetched in the engine's single host sync:
    ``split_nodes_per_level`` ((M, max_depth) — the frontier liveness the
    compaction/shared-root machinery acts on), ``sampled_entries`` (live
    (tree, row) pairs per round) and ``grad_absmean``.
    """

    rounds: list = field(default_factory=list)    # eval rounds (1-based)
    train: list = field(default_factory=list)     # dict of metrics per eval
    valid: list = field(default_factory=list)
    n_trees: list = field(default_factory=list)   # per executed round
    rho_id: list = field(default_factory=list)    # per executed round
    wall_time_s: list = field(default_factory=list)  # per executed round
    engine: str = "loop"
    segments: list = field(default_factory=list)  # measured segment walls
    telemetry: dict = field(default_factory=dict)  # in-graph per-round stats
    overhead_s: float = 0.0                       # scan: wall outside ticks
    #: resume support (DESIGN.md §13): the 0-based round this (possibly
    #: partial) history starts at — per-round lists cover rounds
    #: ``start_round+1 .. start_round+len(n_trees)`` — and the EXACT final
    #: margin carries (float32), which seed ``init_margin`` on resume.
    start_round: int = 0
    final_margin: Optional[np.ndarray] = None
    final_margin_valid: Optional[np.ndarray] = None

    @property
    def total_wall_time_s(self) -> float:
        return float(sum(self.wall_time_s))


def _evaluate(loss: str, y, margin) -> dict:
    """Host-side metric dict — the objective's metric set (DESIGN.md §11)."""
    return objective_mod.get_objective(loss).evaluate(y, margin)


def train_fedgbf(
    x: jnp.ndarray,
    y: jnp.ndarray,
    cfg: FedGBFConfig,
    rng: jax.Array,
    x_valid: Optional[jnp.ndarray] = None,
    y_valid: Optional[jnp.ndarray] = None,
    backend: Union[str, "backend_mod.TreeBackend", None] = None,
    eval_every: int = 1,
    verbose: bool = False,
    engine: str = "scan",
    tracer=None,
    telemetry: bool = False,
    round_feature_mask=None,
    start_round: int = 0,
    stop_round: Optional[int] = None,
    init_margin=None,
    init_margin_valid=None,
) -> tuple[EnsembleModel, TrainHistory]:
    """Train (Dynamic) FedGBF. Set min == max on both schedules for static FedGBF.

    ``backend`` selects the execution layer (DESIGN.md §1): a registry name
    (``"local"``, ``"local-pallas"``; ``"vfl-*"`` names need a constructed
    backend since they bind a mesh) or a ``TreeBackend`` instance from
    ``core.backend.get_backend`` / ``federation.vfl.make_vfl_backend``.
    None means centralized-local execution, which the paper itself argues
    (and SecureBoost's losslessness guarantees) is metric-equivalent (§4.2.1).

    ``engine`` selects the training engine (module docstring): ``"scan"``
    (static-shape scanned engine, the default) or ``"loop"`` (legacy
    per-round reference).  Both drive the same ``TreeBackend``.

    ``tracer`` (an ``obs.trace`` tracer; None falls back to the process
    global, default disabled) takes host-side spans — binning, the
    scan-program call, the history fetch, model assembly; a recording
    ``Tracer`` also gets per-segment/per-round execution spans, for which
    the scan program compiles in its segment ticks.  ``telemetry=True
    additionally threads the in-graph telemetry block through the training
    program (``TrainHistory.telemetry``); it is a jit-STATIC flag, so the
    default path compiles the exact same program as before (the 1-compile
    property and its cost are untouched — gated by benchmarks/ci_guard.py).
    The process's compile counter (``obs.compiles``) is installed on the
    first call.

    Fault tolerance (DESIGN.md §13):

    ``round_feature_mask`` — optional (M, d) bool: round m (1-based row
    m-1) restricts the split search to its True columns, composed (AND)
    with the per-tree sampled feature masks.  This is the party-dropout
    degradation hook: a degraded party's columns go False for the rest of
    the round, and the result is bit-identical to a run whose sampled
    masks never contained those candidates.

    ``start_round``/``stop_round`` — train only rounds ``start_round+1 ..
    stop_round`` (0-based window [start, stop)) of the FULL schedule: the
    rng stream, sampling masks, schedule arithmetic and eval gating all
    replay the full-run derivation, so chunked training stitches to a
    byte-identical ensemble.  ``init_margin``/``init_margin_valid`` seed
    the boosting carry (the previous chunk's ``history.final_margin``);
    every history carries its exact final margins for exactly this.
    """
    if cfg.sampling not in ("uniform", "goss"):
        raise ValueError(
            f"unknown sampling {cfg.sampling!r}; options: 'uniform', 'goss'"
        )
    stop = cfg.rounds if stop_round is None else int(stop_round)
    start = int(start_round)
    if not 0 <= start < stop <= cfg.rounds:
        raise ValueError(
            f"round window [{start}, {stop}) invalid for cfg.rounds="
            f"{cfg.rounds}"
        )
    if (init_margin is None) != (start == 0):
        raise ValueError(
            "init_margin must be given exactly when start_round > 0 "
            "(it is the previous chunk's final_margin)"
        )
    if round_feature_mask is not None:
        round_feature_mask = np.asarray(round_feature_mask, bool)
        if round_feature_mask.shape != (cfg.rounds, x.shape[1]):
            raise ValueError(
                f"round_feature_mask shape {round_feature_mask.shape} != "
                f"(rounds, d) = ({cfg.rounds}, {x.shape[1]})"
            )
    if tracer is None:
        tracer = trace_mod.global_tracer()
    compiles_mod.install()
    if engine == "scan":
        return _train_scanned(
            x, y, cfg, rng, x_valid, y_valid, backend, eval_every, verbose,
            tracer, telemetry, round_feature_mask, start, stop,
            init_margin, init_margin_valid,
        )
    if engine == "loop":
        return _train_loop(
            x, y, cfg, rng, x_valid, y_valid, backend, eval_every, verbose,
            tracer, telemetry, round_feature_mask, start, stop,
            init_margin, init_margin_valid,
        )
    raise ValueError(f"unknown engine {engine!r}; options: 'scan', 'loop'")


def _delta_bucket(rows: int, n: int) -> int:
    """Round a delta-buffer width up to the next power of two (capped at n).

    The buffer width is a jit-STATIC shape: under a dynamic rho schedule
    the raw ``n − n_keep`` differs every round, which would compile one
    forest program per round — exactly the recompile churn the engines
    exist to avoid.  Surplus buffer rows land on kept rows whose delta
    weight ``1 − w`` is 0 (inert), so bucketing costs nothing in accuracy
    and caps the distinct programs at O(log n).
    """
    bucket = 1
    while bucket < rows:
        bucket *= 2
    return min(bucket, n)


def _root_delta_rows(cfg: FedGBFConfig, n: int, rho_id: float) -> int:
    """Static shared-root delta-buffer width for one round (DESIGN.md §9).

    The schedule-driven crossover: the ``shared − delta`` derivation wins
    only when most rows are kept — rho_id >= 0.5, i.e. ``n − n_keep <=
    n // 2`` under the exact host rounding the mask draw uses — and only
    for uniform 0/1 masks (GOSS's amplified weights leave ``1 − w`` nonzero
    on kept rows outside the delta buffer).  Returns 0 (direct level-0
    pass) otherwise; a power-of-two buffer width (``_delta_bucket``) when
    the delta path is selected.
    """
    if not cfg.tree.shared_root or cfg.sampling != "uniform":
        return 0
    n_keep = max(1, int(round(n * rho_id)))
    if n - n_keep > n // 2:
        return 0
    return _delta_bucket(max(1, n - n_keep), n)


def _round_telemetry(trees, smask, g, max_depth) -> list:
    """The in-graph telemetry vector for one round's built forest.

    Per-level live split-node counts over the round's T trees (the frontier
    liveness the compaction/shared-root machinery acts on), the live
    (tree, row) sample-mask entries, and the mean |g| — all O(T·nodes)
    reductions over arrays the round already materialized, so the traced
    cost is noise next to one histogram pass (the <=5% ci_guard gate).
    Returns a list of scalar jnp values, length ``max_depth + 2``.
    """
    tele, off = [], 0
    for level in range(max_depth):
        width = 2 ** level
        tele.append(jnp.sum(
            (trees.feature[:, off:off + width] >= 0).astype(jnp.float32)
        ))
        off += width
    tele.append(jnp.sum((smask > 0).astype(jnp.float32)))
    tele.append(jnp.mean(jnp.abs(g)))
    return tele


#: telemetry slots beyond the per-level liveness counts
_TELE_EXTRA = 2


def _telemetry_dict(tele_np: "np.ndarray", max_depth: int) -> dict:
    """Unpack the fetched (M, max_depth + 2) telemetry matrix."""
    return {
        "split_nodes_per_level":
            tele_np[:, :max_depth].astype(np.int64).tolist(),
        "sampled_entries": tele_np[:, max_depth].astype(np.int64).tolist(),
        "grad_absmean": [float(v) for v in tele_np[:, max_depth + 1]],
    }


def _train_loop(
    x, y, cfg, rng, x_valid, y_valid, backend, eval_every, verbose,
    tracer=trace_mod.NULL_TRACER, telemetry=False,
    round_feature_mask=None, start_round=0, stop_round=None,
    init_margin=None, init_margin_valid=None,
) -> tuple[EnsembleModel, TrainHistory]:
    """Legacy per-round training loop (the reference baseline)."""
    bk = backend_mod.resolve_backend(backend)
    obj = objective_mod.get_objective(cfg.loss)
    n, d = x.shape
    start = int(start_round)
    stop = cfg.rounds if stop_round is None else int(stop_round)
    with tracer.span("binning", cat="train"):
        binned, edges = binning.fit_bin(x, cfg.tree.num_bins)
    y = y.astype(jnp.float32)

    # Resume (DESIGN.md §13): replay the rng stream through the skipped
    # rounds — one split per round, exactly what the loop below draws — so
    # round m's key is identical whether or not rounds before it ran here.
    for _ in range(start):
        rng, _ = jax.random.split(rng)
    y_hat = (obj.init_raw(n, cfg.base_score) if init_margin is None
             else jnp.asarray(init_margin))
    y_hat_valid = None
    binned_valid = None
    if x_valid is not None:
        binned_valid = binning.bin_data(x_valid, edges)
        y_hat_valid = (obj.init_raw(x_valid.shape[0], cfg.base_score)
                       if init_margin_valid is None
                       else jnp.asarray(init_margin_valid))

    forests = []
    history = TrainHistory(engine="loop", start_round=start)

    from repro.core import tree as tree_mod  # local to avoid cycle at import

    for m in range(start + 1, stop + 1):
        t0 = time.perf_counter()
        n_trees = dynamic.n_trees_schedule(cfg, m)
        rho_id = dynamic.rho_id_schedule(cfg, m)

        rng, k_sample = jax.random.split(rng)
        g, h = obj.grad_hess(y, y_hat)
        if cfg.sampling == "goss":
            n_top, n_rand = forest_mod.goss_counts(n, rho_id, cfg.goss_top_share)
            smask, fmask = forest_mod.goss_masks(
                k_sample, g, d, n_trees, n_top, n_rand,
                forest_mod.feature_keep_count(d, cfg.rho_feat)
            )
        else:
            smask, fmask = forest_mod.sample_masks(
                k_sample, n, d, n_trees, rho_id, cfg.rho_feat
            )
        if round_feature_mask is not None:
            # party-dropout degradation: the round's surviving columns,
            # composed with the sampled masks (DESIGN.md §13)
            fmask = fmask & jnp.asarray(round_feature_mask[m - 1])[None, :]
        rdr = _root_delta_rows(cfg, n, rho_id)
        with tracer.span(f"round {m}", cat="train",
                         args={"n_trees": n_trees,
                               "rho_id": round(rho_id, 6)}):
            trees, train_pred = bk.build_forest(
                binned, g, h, smask, fmask, cfg.tree, root_delta_rows=rdr,
            )
            y_hat = y_hat + cfg.learning_rate * train_pred
            forests.append(jax.block_until_ready(trees))
        t1 = time.perf_counter()
        dt = t1 - t0
        history.segments.append({
            "width": n_trees, "first_round": m - 1, "rounds": 1,
            "root_delta_rows": rdr, "wall_s": dt, "t0": t0, "t1": t1,
        })
        if telemetry:
            tele = np.asarray(jnp.stack(
                _round_telemetry(trees, smask, g, cfg.tree.max_depth)
            ))[None]
            for k, v in _telemetry_dict(tele, cfg.tree.max_depth).items():
                history.telemetry.setdefault(k, []).extend(v)

        if x_valid is not None:
            # predict_forest = the shared packed traversal (tree.predict_trees)
            # + per-round mean, applied incrementally to the newest round.
            vpred = tree_mod.predict_forest(trees, binned_valid, cfg.tree.max_depth)
            y_hat_valid = y_hat_valid + cfg.learning_rate * vpred

        # Schedule and timing are recorded for EVERY executed round; only
        # the metric evals are gated by eval_every.  The eval condition is
        # ABSOLUTE (cfg.rounds, not the chunk's stop), so a chunked run
        # evaluates at exactly the rounds the uninterrupted run does.
        history.n_trees.append(n_trees)
        history.rho_id.append(rho_id)
        history.wall_time_s.append(dt)
        if m % eval_every == 0 or m == cfg.rounds:
            tr = _evaluate(cfg.loss, y, y_hat)
            history.rounds.append(m)
            history.train.append(tr)
            if x_valid is not None:
                history.valid.append(_evaluate(cfg.loss, y_valid, y_hat_valid))
            if verbose:
                msg = ", ".join(f"{k}={v:.4f}" for k, v in tr.items())
                print(f"[round {m:3d}] trees={n_trees} rho_id={rho_id:.2f} {msg}")

    history.final_margin = np.asarray(y_hat)
    if y_hat_valid is not None:
        history.final_margin_valid = np.asarray(y_hat_valid)
    model = EnsembleModel(
        forests=tuple(forests),
        learning_rate=cfg.learning_rate,
        base_score=cfg.base_score,
        bin_edges=edges,
        loss=cfg.loss,
        max_depth=cfg.tree.max_depth,
    )
    return model, history


#: host-side segment-boundary timestamps appended by the in-program
#: ``jax.debug.callback`` ticks of the CURRENT scan-engine call: (seg_idx,
#: perf_counter).  Filled only when the program was compiled with ticks (a
#: recording tracer).  Cleared by ``_train_scanned`` before each program
#: call and read back after ``jax.effects_barrier()`` — a probing device
#: like ``MessageMeter``, not re-entrant across concurrent trains in one
#: process.
_SEGMENT_TICKS: list = []


def _segment_tick(seg_idx, _anchor) -> None:
    _SEGMENT_TICKS.append((int(seg_idx), time.perf_counter()))


def _emit_tick(seg_idx: int, anchor) -> None:
    """Stage a host tick anchored on ``anchor`` (a traced array).

    The data dependency on the boosting carry pins the callback to the
    point where the preceding segment's result exists, so the host
    timestamps bracket real segment execution.  Deliberately UNordered:
    ordered effects refuse to run on >1 device, and the vfl backends train
    on a multi-device mesh — sequencing comes from the carry chain instead
    (tick i+1's operand depends on everything tick i's did), and the reader
    dedups per segment index.  One scalar rides per tick — a handful of
    tiny host callbacks per *program execution*.  Their cost is not the
    run time but the program's identity: a host callback embeds a Python
    pointer in the compiled module, so a program with ticks gets a fresh
    persistent-cache key in every process and compiles anew.  Hence ticks
    are compiled in only for a tracer that records them.
    """
    jax.debug.callback(_segment_tick, seg_idx, anchor.ravel()[0])


def _schedule_segments(n_trees: "np.ndarray", split_on=None):
    """Factor a per-round tree-count schedule into constant-width segments:
    [(width, first_round, n_rounds), ...].  Monotone schedules (the paper's
    cosine decay) give at most ``n_trees_max - n_trees_min + 1`` segments.

    ``split_on`` (optional, same length) adds extra segment boundaries
    wherever its value changes — the shared-root engine passes the per-round
    crossover eligibility so every round of a segment makes the SAME
    delta-vs-direct choice the loop engine makes for it (both schedules are
    monotone, so this at most doubles the segment count)."""
    segments = []
    start = 0
    for m in range(1, len(n_trees) + 1):
        if (m == len(n_trees) or n_trees[m] != n_trees[start]
                or (split_on is not None and split_on[m] != split_on[start])):
            segments.append((int(n_trees[start]), start, m - start))
            start = m
    return segments


def _keep_counts(cfg: FedGBFConfig, n: int) -> "np.ndarray":
    """Per-round keep counts via the exact host expression the legacy loop
    evaluates (full float64 rho — schedule_arrays' float32 rho_id could
    round a .5 boundary the other way and break mask equivalence)."""
    return np.array(
        [max(1, int(round(n * dynamic.rho_id_schedule(cfg, m))))
         for m in range(1, cfg.rounds + 1)],
        np.int32,
    )


def _plan_segments(cfg: FedGBFConfig, n: int, start_round: int = 0,
                   stop_round: Optional[int] = None) -> list:
    """The scan engine's segment plan: [(width, first_round, n_rounds,
    root_delta_rows), ...] — ONE host-side derivation shared by the compiled
    program and by the history/trace attribution of the segment ticks, so
    the two can never disagree on segment boundaries.

    Shared-root crossover (DESIGN.md §9): segments additionally split at
    the rho >= 0.5 eligibility boundary, so every round takes EXACTLY the
    delta-vs-direct path the loop engine takes for it (host arithmetic
    identical; engine equivalence must not depend on segment packing).
    Within an eligible segment the static buffer is the bucketed max of
    its rounds' deltas — surplus rows are weight-0 inert, so differing
    buffer widths between the engines cannot change a single bit.

    Resume (DESIGN.md §13): ``start_round``/``stop_round`` clip the FULL
    plan to the 0-based round window [start, stop) — segment widths and the
    per-segment ``root_delta_rows`` are derived from the full schedule
    first, so a clipped segment keeps the buffer width the uninterrupted
    run uses (surplus delta-buffer rows are weight-0 inert, so the shared
    width cannot change a bit; see above).
    """
    sched, _ = dynamic.flat_schedule(cfg)
    n_keep_round = _keep_counts(cfg, n)
    use_shared_root = cfg.tree.shared_root and cfg.sampling != "goss"
    delta_eligible = None
    if use_shared_root:
        delta_eligible = (n - n_keep_round) <= n // 2
    plan = []
    for width, first, n_rounds in _schedule_segments(
        sched.n_trees, split_on=delta_eligible
    ):
        rdr = 0
        if use_shared_root and delta_eligible[first]:
            seg_delta = int(n - n_keep_round[first:first + n_rounds].min())
            rdr = _delta_bucket(max(1, seg_delta), n)
        plan.append((width, first, n_rounds, rdr))
    start = int(start_round)
    stop = cfg.rounds if stop_round is None else int(stop_round)
    if start > 0 or stop < cfg.rounds:
        clipped = []
        for width, first, n_rounds, rdr in plan:
            a, b = max(first, start), min(first + n_rounds, stop)
            if b > a:
                clipped.append((width, a, b - a, rdr))
        plan = clipped
    return plan


@partial(jax.jit, static_argnames=("cfg", "bk", "eval_every", "telemetry",
                                   "start_round", "stop_round", "ticks"))
def _scan_train_program(
    binned, y, binned_valid, y_valid, rng, cfg: FedGBFConfig, bk,
    eval_every: int, telemetry: bool = False, round_mask=None,
    init_margin=None, init_margin_valid=None, start_round: int = 0,
    stop_round: Optional[int] = None, ticks: bool = False,
):
    """The ONE compiled training program of the scanned engine.

    The mask-form schedule (``dynamic.flat_schedule``) factors the dynamic
    tree-count schedule into constant-width segments
    (``_schedule_segments``); each segment runs its rounds under a
    ``lax.scan`` at the segment's natural width (single-round segments are
    inlined), with the boosting state threaded through all segments.  The
    whole schedule therefore compiles to ONE XLA program whose shapes never
    change at run time — no per-round recompiles, no wasted tree slots, and
    the per-round forest build keeps the vmapped multi-tree batching of the
    legacy loop.

    All sampling masks are drawn up front in one batched vmap; the key
    chain replays the loop's split-per-round / fold_in-per-slot derivation
    exactly, so the scan builds mask-for-mask the legacy loop's trees.
    Metrics are evaluated in-graph (``Objective.metric_vector``) under ``lax.cond``,
    gated to eval rounds — no per-round host sync; the caller fetches the
    whole history in one device->host copy.

    Returns (trees per segment — a tuple of (rounds_seg, width, ...) stacked
    TreeArrays — train metric matrix (M, len(keys)), valid metric matrix or
    None, telemetry matrix (M, max_depth + 2) or None); gated-off rounds
    hold NaN metric rows.

    Observability (DESIGN.md §12): with the jit-STATIC ``ticks`` flag (set
    by ``_train_scanned`` only for a recording tracer) an unordered
    ``jax.debug.callback`` tick (``_emit_tick``) fires at every segment
    boundary, anchored on the boosting carry, so the caller recovers TRUE
    per-segment walls from one program execution; the default program
    holds no host callback, so the persistent compilation cache can keep
    it.  With the jit-STATIC ``telemetry`` flag the per-round liveness
    block (``_round_telemetry``) rides the scan ``ys`` and is fetched in
    the same single host sync as the metrics.  Every phase of a round runs
    under a ``jax.named_scope`` (``fedgbf.sample``, ``.grad``,
    ``.histogram``, ``.exchange``, ``.split``, ``.route``, ``.leaf``,
    ``.update``, ``.eval``) inside its segment's ``fedgbf.segment.T<width>``
    scope: op metadata only, which a profiler capture reports per device
    op; the arithmetic is unchanged.

    Top-level + jitted so a) it is the unit the compile-count benchmark
    inspects via ``_cache_size()``, and b) identical shapes/configs across
    calls reuse the cache.

    Fault tolerance (DESIGN.md §13): ``round_mask`` ((M, d) bool or None)
    ANDs into every round's sampled feature masks (party-dropout
    degradation); ``start_round``/``stop_round`` (jit-static) clip the
    executed segment plan to a round window while the rng stream, mask
    draws and eval gating replay the FULL schedule, and
    ``init_margin``/``init_margin_valid`` seed the boosting carry — the
    final carry is returned so chunked runs hand margins forward exactly.
    """
    from repro.core import tree as tree_mod  # local to avoid cycle at import

    start = int(start_round)
    stop = cfg.rounds if stop_round is None else int(stop_round)
    n, d = binned.shape
    d_keep = forest_mod.feature_keep_count(d, cfg.rho_feat)
    obj = objective_mod.get_objective(cfg.loss)
    lr = cfg.learning_rate
    nan_vec = jnp.full((len(obj.metric_keys),), jnp.nan, jnp.float32)
    has_valid = binned_valid is not None
    y32 = y.astype(jnp.float32)

    sched, flat = dynamic.flat_schedule(cfg)
    use_goss = cfg.sampling == "goss"
    n_keep_round = _keep_counts(cfg, n)
    n_keep = n_keep_round[flat.round_of_step]  # (S,)
    if use_goss:
        goss_round = np.array(
            [forest_mod.goss_counts(n, dynamic.rho_id_schedule(cfg, m),
                                    cfg.goss_top_share)
             for m in range(1, cfg.rounds + 1)],
            np.int32,
        )  # (M, 2): per-round (n_top, n_rand), same host arithmetic as loop
    rounds_idx = np.arange(1, cfg.rounds + 1)
    do_eval = (rounds_idx % eval_every == 0) | (rounds_idx == cfg.rounds)

    # -- all mask keys up front ----------------------------------------------
    with jax.named_scope("fedgbf.sample"):
        round_keys = []
        for _ in range(cfg.rounds):  # the loop's exact stream: one split
            rng, k_round = jax.random.split(rng)  # per round
            round_keys.append(k_round)
        round_keys = jnp.stack(round_keys)  # (M, 2)
        step_keys = jax.vmap(jax.random.fold_in)(
            round_keys[jnp.asarray(flat.round_of_step)],
            jnp.asarray(flat.tree_in_round),
        )  # (S, 2) — prefix-stable per-slot keys, identical to the loop's
        if not use_goss:
            # Uniform masks depend only on the keys: one batched draw up
            # front.  GOSS masks depend on the round's gradients, so they
            # are drawn inside round_body from the same per-slot keys.
            smask_all, fmask_all = forest_mod.masks_from_keys(
                step_keys, n, d, jnp.asarray(n_keep), d_keep
            )  # (S, n) float32, (S, d) bool

    def round_body(rdr, carry, xs):
        y_hat, y_hat_valid = carry
        with jax.named_scope("fedgbf.grad"):
            g, h = obj.grad_hess(y32, y_hat)
        with jax.named_scope("fedgbf.sample"):
            if use_goss:
                smask, fmask = forest_mod.goss_masks_from_keys(
                    xs["keys"], g, d, xs["n_top"], xs["n_rand"], d_keep
                )
            else:
                smask, fmask = xs["smask"], xs["fmask"]
            if round_mask is not None:
                # party-dropout degradation (DESIGN.md §13): the round's
                # surviving columns AND into the per-tree sampled masks
                fmask = fmask & xs["rmask"][None, :]
        trees, per_pred = bk.build_forest_per_tree(
            binned, g, h, smask, fmask, cfg.tree, root_delta_rows=rdr
        )
        with jax.named_scope("fedgbf.update"):
            y_hat = y_hat + lr * jnp.mean(per_pred, axis=0)
        with jax.named_scope("fedgbf.eval"):
            tele_vec = (jnp.stack(_round_telemetry(trees, smask, g,
                                                   cfg.tree.max_depth))
                        if telemetry else None)
            tr_vec = jax.lax.cond(
                xs["do_eval"],
                lambda m: obj.metric_vector(y32, m),
                lambda m: nan_vec,
                y_hat,
            )
            va_vec = nan_vec
            if has_valid:
                vp = tree_mod.predict_trees(trees, binned_valid,
                                            cfg.tree.max_depth)
                y_hat_valid = y_hat_valid + lr * jnp.mean(vp, axis=0)
                va_vec = jax.lax.cond(
                    xs["do_eval"],
                    lambda m: obj.metric_vector(y_valid.astype(jnp.float32),
                                                m),
                    lambda m: nan_vec,
                    y_hat_valid,
                )
        ys = ((trees, tr_vec, va_vec, tele_vec) if telemetry
              else (trees, tr_vec, va_vec))
        return (y_hat, y_hat_valid), ys

    y_hat0 = (obj.init_raw(n, cfg.base_score) if init_margin is None
              else init_margin)
    y_hat_valid0 = None
    if has_valid:
        y_hat_valid0 = (
            obj.init_raw(binned_valid.shape[0], cfg.base_score)
            if init_margin_valid is None else init_margin_valid
        )
    carry = (y_hat0, y_hat_valid0)
    offsets = np.concatenate([[0], np.cumsum(sched.n_trees)])
    trees_segs, tr_rows, va_rows, tele_rows = [], [], [], []
    # Segment boundaries + shared-root crossover come from the ONE shared
    # host-side plan (``_plan_segments``) the caller also uses to attribute
    # the segment ticks back to rounds.  Under a resume window the plan is
    # the full schedule's plan clipped to [start, stop) — keys/masks index
    # by ABSOLUTE round, so every executed round replays its full-run draw.
    if ticks:
        _emit_tick(0, y_hat0)
    for seg_idx, (width, first, n_rounds, rdr) in enumerate(
        _plan_segments(cfg, n, start, stop)
    ):
        s, e = int(offsets[first]), int(offsets[first + n_rounds])
        xs = {"do_eval": jnp.asarray(do_eval[first:first + n_rounds])}
        body = partial(round_body, rdr)
        with jax.named_scope(f"fedgbf.segment.T{width}"):
            with jax.named_scope("fedgbf.sample"):
                if use_goss:
                    xs["keys"] = step_keys[s:e].reshape(n_rounds, width, 2)
                    xs["n_top"] = jnp.asarray(
                        goss_round[first:first + n_rounds, 0])
                    xs["n_rand"] = jnp.asarray(
                        goss_round[first:first + n_rounds, 1])
                else:
                    xs["smask"] = smask_all[s:e].reshape(n_rounds, width, n)
                    xs["fmask"] = fmask_all[s:e].reshape(n_rounds, width, d)
                if round_mask is not None:
                    xs["rmask"] = round_mask[first:first + n_rounds]
            if n_rounds == 1:
                carry, ys = body(
                    carry, jax.tree_util.tree_map(lambda a: a[0], xs)
                )
                ys = jax.tree_util.tree_map(lambda a: a[None], ys)
            else:
                carry, ys = jax.lax.scan(body, carry, xs)
        trees_segs.append(ys[0])
        tr_rows.append(ys[1])
        va_rows.append(ys[2])
        if telemetry:
            tele_rows.append(ys[3])
        if ticks:
            _emit_tick(seg_idx + 1, carry[0])
    with jax.named_scope("fedgbf.eval"):
        tr_mat = jnp.concatenate(tr_rows)  # (stop - start, len(keys))
        va_mat = jnp.concatenate(va_rows) if has_valid else None
        tele_mat = jnp.concatenate(tele_rows) if telemetry else None
    return tuple(trees_segs), tr_mat, va_mat, tele_mat, carry


def _train_scanned(
    x, y, cfg, rng, x_valid, y_valid, backend, eval_every, verbose,
    tracer=trace_mod.NULL_TRACER, telemetry=False,
    round_feature_mask=None, start_round=0, stop_round=None,
    init_margin=None, init_margin_valid=None,
) -> tuple[EnsembleModel, TrainHistory]:
    """Static-shape scanned training engine (DESIGN.md §4).

    Mask-for-mask equivalent to ``_train_loop``: per-tree keys are
    prefix-stable (``forest.fold_in_keys``), so every scan step draws
    exactly the mask the legacy loop draws for that (round, slot); the
    sequential round accumulation reproduces the legacy bagging mean up to
    float reassociation (history metrics agree to ~1e-6, asserted in
    tests/test_train_engine.py).
    """
    bk = backend_mod.resolve_backend(backend)
    with tracer.span("binning", cat="train"):
        binned, edges = binning.fit_bin(x, cfg.tree.num_bins)
        binned_valid = (binning.bin_data(x_valid, edges)
                        if x_valid is not None else None)

    sched = dynamic.schedule_arrays(cfg)
    start = int(start_round)
    stop = cfg.rounds if stop_round is None else int(stop_round)
    rounds_idx = np.arange(1, cfg.rounds + 1)
    do_eval = (rounds_idx % eval_every == 0) | (rounds_idx == cfg.rounds)

    ticks = tracer.records  # segment ticks only for a tracer that keeps them
    _SEGMENT_TICKS.clear()
    t0 = time.perf_counter()
    with tracer.span("scan_program", cat="train",
                     args={"rounds": cfg.rounds, "telemetry": telemetry}):
        trees_segs, tr_mat, va_mat, tele_mat, carry = _scan_train_program(
            binned, y, binned_valid,
            None if y_valid is None else jnp.asarray(y_valid),
            rng, cfg, bk, eval_every, telemetry=telemetry,
            round_mask=(None if round_feature_mask is None
                        else jnp.asarray(round_feature_mask)),
            init_margin=(None if init_margin is None
                         else jnp.asarray(init_margin)),
            init_margin_valid=(None if init_margin_valid is None
                               else jnp.asarray(init_margin_valid)),
            start_round=start, stop_round=stop, ticks=ticks,
        )
        jax.block_until_ready(trees_segs)
    if ticks:
        jax.effects_barrier()  # flush the in-program segment ticks
    wall = time.perf_counter() - t0
    with tracer.span("fetch_history", cat="train"):
        # ONE fetch for the whole metric (+ telemetry) history — the
        # engine's only host sync.
        tr_np = np.asarray(tr_mat)
        va_np = np.asarray(va_mat) if va_mat is not None else None
        tele_np = np.asarray(tele_mat) if tele_mat is not None else None

    # Unstack each segment's (rounds_seg, width, ...) trees into the ragged
    # per-round forests — structurally identical to the legacy loop's model.
    with tracer.span("assemble_model", cat="train"):
        forests = []
        for seg_trees in trees_segs:
            rounds_seg = seg_trees.feature.shape[0]
            for r in range(rounds_seg):
                forests.append(
                    jax.tree_util.tree_map(lambda a: a[r], seg_trees)
                )
        forests = tuple(forests)

    history = TrainHistory(engine="scan", start_round=start)
    history.n_trees = [int(v) for v in sched.n_trees[start:stop]]
    history.rho_id = [dynamic.rho_id_schedule(cfg, m)  # full-precision, as loop
                      for m in range(start + 1, stop + 1)]
    if tele_np is not None:
        history.telemetry = _telemetry_dict(tele_np, cfg.tree.max_depth)

    # Per-SEGMENT walls from the in-program ticks: tick i and i+1 bracket
    # segment i's execution, so each segment's wall is real, smeared
    # uniformly only over the rounds INSIDE it (see the TrainHistory
    # docstring for the granularity limit).  Everything the call spent
    # outside the ticks — trace + compile + dispatch — lands in
    # ``overhead_s``, so cold and warm calls stay comparable.
    plan = _plan_segments(cfg, binned.shape[0], start, stop)
    # Unordered callbacks fire once per participating device: dedup to the
    # earliest timestamp per segment index, then clamp to monotone (host
    # callback delivery can jitter by microseconds across devices).
    by_idx: dict = {}
    for i, t in _SEGMENT_TICKS:
        by_idx[i] = min(t, by_idx.get(i, t))
    if set(by_idx) == set(range(len(plan) + 1)):
        ticks = [(i, by_idx[i]) for i in range(len(plan) + 1)]
        for k in range(1, len(ticks)):
            ticks[k] = (k, max(ticks[k][1], ticks[k - 1][1]))
        history.wall_time_s = []
        for (width, first, n_rounds, rdr), (_, ta), (_, tb) in zip(
            plan, ticks, ticks[1:]
        ):
            history.wall_time_s.extend([(tb - ta) / n_rounds] * n_rounds)
            history.segments.append({
                "width": width, "first_round": first, "rounds": n_rounds,
                "root_delta_rows": rdr, "wall_s": tb - ta,
                "t0": ta, "t1": tb,
            })
            tracer.add_span(
                f"segment[T={width}]", ta, tb, cat="train", track="train",
                args={"rounds": n_rounds, "first_round": first + 1,
                      "root_delta_rows": rdr},
            )
        history.overhead_s = max(0.0, wall - (ticks[-1][1] - ticks[0][1]))
        tracer.add_span("trace+compile+dispatch", t0, ticks[0][1],
                        cat="train", track="train")
    else:  # no ticks (the default program, or a backend without host
        # callbacks): the uniform smear, so the total stays true.
        n_exec = stop - start
        history.wall_time_s = [wall / n_exec] * n_exec
        per = wall / n_exec
        for width, first, n_rounds, rdr in plan:
            history.segments.append({
                "width": width, "first_round": first, "rounds": n_rounds,
                "root_delta_rows": rdr, "wall_s": per * n_rounds,
                "t0": t0 + (first - start) * per,
                "t1": t0 + (first - start + n_rounds) * per,
            })
    keys = objective_mod.get_objective(cfg.loss).metric_keys
    for m in np.nonzero(do_eval)[0]:
        m = int(m)
        if not (start <= m < stop):
            continue
        history.rounds.append(m + 1)
        tr = dict(zip(keys, (float(v) for v in tr_np[m - start])))
        history.train.append(tr)
        if va_np is not None:
            history.valid.append(
                dict(zip(keys, (float(v) for v in va_np[m - start])))
            )
        if verbose:
            msg = ", ".join(f"{k}={v:.4f}" for k, v in tr.items())
            print(f"[round {m + 1:3d}] trees={history.n_trees[m - start]} "
                  f"rho_id={history.rho_id[m - start]:.2f} {msg}")

    history.final_margin = np.asarray(carry[0])
    if carry[1] is not None:
        history.final_margin_valid = np.asarray(carry[1])
    model = EnsembleModel(
        forests=forests,
        learning_rate=cfg.learning_rate,
        base_score=cfg.base_score,
        bin_edges=edges,
        loss=cfg.loss,
        max_depth=cfg.tree.max_depth,
    )
    return model, history


def secureboost_config(rounds: int = 20, **kw) -> FedGBFConfig:
    """SecureBoost = FedGBF degenerated to 1 tree/round, full sampling (§2.3).

    This *is* the paper's baseline: sequential single-tree gradient boosting
    with the same histogram/split machinery (alpha_S = 1, beta_S = 1).
    """
    kw.setdefault("learning_rate", 0.1)
    return FedGBFConfig(
        rounds=rounds,
        n_trees_max=1, n_trees_min=1,
        rho_id_min=1.0, rho_id_max=1.0,
        rho_feat=1.0,
        **kw,
    )


def dynamic_fedgbf_config(rounds: int = 20, **kw) -> FedGBFConfig:
    """The paper's §4.2.2 setting: trees 5 -> 2 (k=1), rho_id 0.1 -> 0.3 (k=1)."""
    kw.setdefault("learning_rate", 0.1)
    return FedGBFConfig(
        rounds=rounds,
        n_trees_max=5, n_trees_min=2, n_trees_speed=1.0,
        rho_id_min=0.1, rho_id_max=0.3, rho_id_speed=1.0,
        rho_feat=1.0,
        **kw,
    )


def federated_forest_config(n_trees: int = 20, rho_id: float = 0.6, **kw) -> FedGBFConfig:
    """Federated Forest baseline (§2.1): pure bagging = one boosting round.

    A single round of N subsampled trees fit to the initial residual is
    exactly a random forest on (g, h) at y_hat = base_score.
    """
    return FedGBFConfig(
        rounds=1,
        learning_rate=1.0,
        n_trees_max=n_trees, n_trees_min=n_trees,
        rho_id_min=rho_id, rho_id_max=rho_id,
        **kw,
    )


_PACK_CACHE: "OrderedDict" = OrderedDict()  # id(model) -> (model, packed)


def _packed_for(model: EnsembleModel) -> PackedEnsemble:
    """Memoized pack_ensemble so repeated predict calls on the same model
    (metric sweeps, eval loops) do not re-concatenate the tree stacks.
    Bounded and identity-keyed (keeps the last few models alive — long-lived
    multi-model callers should pre-pack and pass PackedEnsemble directly)."""
    if isinstance(model.bin_edges, jax.core.Tracer):
        return pack_ensemble(model)  # under jit tracing: never cache tracers
    key = id(model)
    hit = _PACK_CACHE.get(key)
    if hit is not None and hit[0] is model:
        return hit[1]
    packed = pack_ensemble(model)
    _PACK_CACHE[key] = (model, packed)
    while len(_PACK_CACHE) > 4:
        _PACK_CACHE.popitem(last=False)
    return packed


def predict(
    model: Union[EnsembleModel, PackedEnsemble],
    x: jnp.ndarray,
    impl: str = "packed",
) -> jnp.ndarray:
    """Raw-margin prediction F(x) = base + lr * sum_m mean_j T_mj(x) (Alg. 1 l.10).

    Routed through the ``PackedEnsemble`` layout (DESIGN.md §3): one
    traversal of all trees instead of an O(rounds) Python loop.  ``impl``:

      ``"packed"``        single vmapped traversal, exact per-round combiner
                          (bit-for-bit equal to the legacy loop) — default;
      ``"weighted"``      single-pass tree_scale combiner;
      ``"pallas"``        the Pallas ``ensemble_predict`` kernel on binned
                          inputs;
      ``"fused"``         serve-time binning fused INTO the traversal
                          (DESIGN.md §14): raw floats compare against
                          value-space thresholds, no separate binning
                          dispatch — leaf-routing-identical to binning +
                          ``"weighted"``;
      ``"fused-pallas"``  the fused path as one Pallas kernel sweep;
      ``"loop"``          the legacy per-round loop (kept for benchmarks).

    A ``QuantizedEnsemble`` (DESIGN.md §14) serves natively on the fused
    impls (leaf table dequantized in-graph); the binned impls widen it to
    the f32 packed layout first.
    """
    from repro.core import tree as tree_mod
    from repro.core.types import QuantizedEnsemble, dequantize_ensemble

    if impl == "loop":
        if isinstance(model, QuantizedEnsemble):
            model = dequantize_ensemble(model)
        return predict_loop(model, x)
    if isinstance(model, (PackedEnsemble, QuantizedEnsemble)):
        packed = model
    else:
        packed = _packed_for(model)
    if impl == "fused":
        return tree_mod.predict_packed_fused(packed, x)
    if impl == "fused-pallas":
        from repro.kernels.ensemble_predict.ops import (
            predict_packed_fused_pallas,
        )

        return predict_packed_fused_pallas(packed, x)
    if isinstance(packed, QuantizedEnsemble):
        packed = dequantize_ensemble(packed)
    binned = binning.bin_data(x, packed.bin_edges)
    if impl == "packed":
        return tree_mod.predict_packed(packed, binned)
    if impl == "weighted":
        return tree_mod.predict_packed_weighted(packed, binned)
    if impl == "pallas":
        from repro.kernels.ensemble_predict.ops import predict_packed_pallas

        return predict_packed_pallas(packed, binned)
    raise ValueError(f"unknown predict impl {impl!r}")


def predict_loop(
    model: Union[EnsembleModel, PackedEnsemble], x: jnp.ndarray
) -> jnp.ndarray:
    """Legacy O(rounds) per-round prediction loop.

    Superseded by the packed path; kept as the reference the packed path is
    asserted bit-for-bit equal to (tests/test_packed.py) and as the baseline
    in benchmarks/predict_bench.py.
    """
    from repro.core import tree as tree_mod
    from repro.core.types import unpack_ensemble

    if isinstance(model, PackedEnsemble):
        model = unpack_ensemble(model)
    binned = binning.bin_data(x, model.bin_edges)
    out = objective_mod.get_objective(model.loss).init_raw(
        x.shape[0], model.base_score
    )
    for trees in model.forests:
        out = out + model.learning_rate * tree_mod.predict_forest(
            trees, binned, model.max_depth
        )
    return out


def predict_proba(
    model: Union[EnsembleModel, PackedEnsemble],
    x: jnp.ndarray,
    impl: str = "packed",
) -> jnp.ndarray:
    """Prediction-space output: the model's objective activation applied to
    the raw margin (sigmoid for logistic, softmax for multiclass, identity
    for regression/quantile) — resolved from the registry, never hard-coded."""
    obj = objective_mod.get_objective(model.loss)
    return obj.activation(predict(model, x, impl=impl))
