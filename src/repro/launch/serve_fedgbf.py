"""Batched FedGBF scoring service — the millions-of-users serving scenario.

The production serving tier (DESIGN.md §14) stacks four layers:

* **Fused bin+traverse** — checkpoints ship their bin edges, requests
  arrive as raw floats, and ONE compiled program does bin + traverse +
  combine (``--impl fused`` vmap scan or ``fused-pallas`` kernel): the
  separate binning dispatch of the two-program serving path is gone.
* **Quantized ensembles** — ``--quantize 8|16`` serves an int8/int16
  ``QuantizedEnsemble`` (stochastically-rounded leaf tables via
  ``federation/compress.py``); routing stays bit-identical to f32 and the
  margin error is bounded by ``types.margin_delta_bound``.
* **Admission control + latency-aware micro-batching** — a pre-compiled
  ``BatchLadder`` of power-of-two batch shapes; each iteration admits the
  largest rung whose observed p99 (read live from the per-rung log-bucket
  histograms) fits ``--p99-budget-ms``, capped at the queue depth so short
  queues never pay full-batch padding.  Adaptation never recompiles: every
  rung was warmed at startup and on every successful hot-swap.
* **Mid-traffic hot-swap** — ``ModelSlot.try_reload`` validates a
  candidate checkpoint (sha256, probe scores, rung pre-compile) and swaps
  it in BETWEEN microbatches (``--reload-at-batch``), timing the swap into
  ``fedgbf_serve_swap_seconds``; a refused candidate leaves the serving
  stream untouched.

Observability (DESIGN.md §12): the stream records into a ``StreamMetrics``
bundle — log-bucketed latency histograms (overall + per rung, p50/p90/p99
from bucket counts so memory stays constant under unbounded streams),
rows/batches/padded-rows/swap counters, occupancy + throughput gauges
segmented per model generation.  ``--metrics-out`` writes the Prometheus
text exposition to a file; ``--metrics-port`` serves it over a localhost
HTTP scrape endpoint.

    # train a small model, save the packed checkpoint, score a request stream
    PYTHONPATH=src python -m repro.launch.serve_fedgbf \
        --dataset default_credit_card --rounds 10 --save /tmp/fedgbf_ckpt

    # serve a checkpoint fused + int8-quantized with a 5 ms p99 budget and
    # a live scrape endpoint
    PYTHONPATH=src python -m repro.launch.serve_fedgbf \
        --checkpoint /tmp/fedgbf_ckpt --impl fused --quantize 8 \
        --requests 200000 --p99-budget-ms 5 --metrics-port 9109

    # hot-swap a retrained checkpoint mid-stream, between microbatches
    PYTHONPATH=src python -m repro.launch.serve_fedgbf \
        --checkpoint /tmp/fedgbf_ckpt --reload /tmp/fedgbf_ckpt_v2 \
        --reload-at-batch 8
"""

from __future__ import annotations

import argparse
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import io as ckpt_io
from repro.core import boosting
from repro.core import objective as objective_mod
from repro.core.types import PackedEnsemble
from repro.data import synthetic
from repro.launch import compile_cache
from repro.obs import compiles as obs_compiles
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace


@partial(jax.jit, static_argnames=("impl",))
def _score_batch(packed, x: jnp.ndarray, impl: str) -> jnp.ndarray:
    """One compiled program per (microbatch shape, impl): bin + traverse,
    via the same dispatch boosting.predict exposes.  ``impl="fused"`` /
    ``"fused-pallas"`` skip the binning pass entirely — raw floats compare
    against value-space thresholds (DESIGN.md §14) — and accept a
    ``QuantizedEnsemble`` natively.

    The activation comes from the objective registry keyed by the
    checkpoint's stored loss name (DESIGN.md §11) — sigmoid for logistic,
    softmax rows for softmax{K}, identity for the regression objectives —
    instead of a hard-coded sigmoid, so a squared- or quantile-loss
    checkpoint serves raw margins and a multiclass one serves (n, K)
    probability rows."""
    margin = boosting.predict(packed, x, impl=impl)
    return objective_mod.get_objective(packed.loss).activation(margin)


class StreamMetrics:
    """Serving instruments for one scoring stream (bounded memory).

    Latency lives ONLY in log-bucketed histograms — the overall
    ``fedgbf_serve_batch_latency_seconds`` plus one
    ``fedgbf_serve_rung_latency_seconds{batch_size="..."}`` series per
    admitted batch rung (the admission controller reads rung p99s live) —
    so p50/p90/p99 come from bucket counts with a ~4.5% relative error
    bound, never from a raw list that grows with the stream.

    Occupancy (real rows / admitted capacity) accumulates PER MODEL
    SEGMENT: ``begin_model_segment()`` (called on every successful
    hot-swap) resets the accumulators and bumps
    ``fedgbf_serve_model_generation``, so a swap never blends two models'
    padding behavior into one gauge.

    The process's compile counters (``obs.compiles``) render with the
    bundle, so a scrape shows any compile during live serving, which the
    warmed ladder should never see.
    """

    def __init__(self, batch_size: int) -> None:
        r = obs_metrics.MetricsRegistry()
        self.registry = r
        self.latency = r.histogram(
            "fedgbf_serve_batch_latency_seconds",
            "Per-microbatch scoring latency (bin + traverse + combine).",
            lo=1e-6, hi=60.0,
        )
        self.rows = r.counter("fedgbf_serve_rows_total",
                              "Real (non-padding) rows scored.")
        self.batches = r.counter("fedgbf_serve_batches_total",
                                 "Microbatches dispatched.")
        self.padded_rows = r.counter(
            "fedgbf_serve_padded_rows_total",
            "Zero-padding rows scored to keep microbatch shapes static.")
        self.batch_size = r.gauge("fedgbf_serve_batch_size",
                                  "Capacity of the last admitted microbatch.")
        self.occupancy = r.gauge(
            "fedgbf_serve_batch_occupancy",
            "Mean real-row fraction per microbatch (1 = no padding), "
            "accumulated over the current model segment only.")
        self.rows_per_s = r.gauge("fedgbf_serve_rows_per_second",
                                  "Stream throughput over the last run.")
        self.rows_rejected = r.counter(
            "fedgbf_serve_rows_rejected_total",
            "Rows rejected for non-finite (inf) features: scored as NaN, "
            "never fed to the ensemble (DESIGN.md §13).")
        self.reloads = r.counter(
            "fedgbf_serve_reloads_total",
            "Hot model reloads that passed validation and were swapped in.")
        self.reload_failures = r.counter(
            "fedgbf_serve_reload_failures_total",
            "Hot reloads refused (corrupt checkpoint / failed probe); the "
            "previous ensemble keeps serving.")
        self.swap_latency = r.histogram(
            "fedgbf_serve_swap_seconds",
            "Validate-before-swap hot reload latency (load + sha256 + probe "
            "+ rung warm), successful swaps only.",
            lo=1e-4, hi=600.0,
        )
        self.model_generation = r.gauge(
            "fedgbf_serve_model_generation",
            "Model segment counter: bumped on every successful hot-swap; "
            "per-segment gauges reset at each bump.")
        self.compiles = obs_compiles.install()
        for m in self.compiles.instruments:
            r.adopt(m)
        self.batch_size.set(batch_size)
        self._capacity = batch_size
        self._rung_hists: dict = {}
        self._seg_rows = 0
        self._seg_slots = 0

    def rung_latency(self, capacity: int) -> obs_metrics.LogBucketHistogram:
        """The labeled per-rung latency histogram (registered lazily)."""
        h = self._rung_hists.get(capacity)
        if h is None:
            h = self.registry.histogram(
                "fedgbf_serve_rung_latency_seconds",
                "Per-microbatch latency by admitted batch capacity; the "
                "admission controller reads each rung's p99 live.",
                lo=1e-6, hi=60.0, labels={"batch_size": str(capacity)},
            )
            self._rung_hists[capacity] = h
        return h

    def observe_batch(self, latency_s: float, real_rows: int,
                      capacity: int | None = None) -> None:
        cap = self._capacity if capacity is None else capacity
        self.latency.observe(latency_s)
        self.rung_latency(cap).observe(latency_s)
        self.rows.inc(real_rows)
        self.batches.inc()
        self.padded_rows.inc(cap - real_rows)
        self.batch_size.set(cap)
        self._seg_rows += real_rows
        self._seg_slots += cap
        self.occupancy.set(
            self._seg_rows / self._seg_slots if self._seg_slots else 0.0)

    def begin_model_segment(self) -> None:
        """Reset per-model gauges at a hot-swap boundary: occupancy starts
        a fresh accumulation and the generation gauge bumps, so the gauges
        never blend two models' serving behavior."""
        self._seg_rows = 0
        self._seg_slots = 0
        self.occupancy.set(0.0)
        self.model_generation.set(self.model_generation.value + 1)

    def finalize(self, wall_s: float) -> None:
        if wall_s > 0:
            self.rows_per_s.set(self.rows.value / wall_s)

    def quantiles_ms(self, qs=(0.5, 0.9, 0.99)) -> dict:
        return {q: self.latency.quantile(q) * 1e3 for q in qs}

    def render(self) -> str:
        """Prometheus text exposition of the whole bundle."""
        return self.registry.render()


def ladder_sizes(max_size: int, min_size: int = 256) -> list:
    """Power-of-two batch rungs up to ``max_size`` (always included)."""
    min_size = max(1, min(min_size, max_size))
    sizes, s = [], 1
    while s < max_size:
        if s >= min_size:
            sizes.append(s)
        s *= 2
    sizes.append(max_size)
    return sizes


class BatchLadder:
    """Pre-compiled ladder of static batch shapes + the admission policy.

    Every rung is compiled once up front (``warm``; ``ModelSlot`` re-warms
    on hot-swap), so ``pick`` may move between rungs every single batch
    without ever triggering a recompile — the no-recompile property is
    asserted via ``_score_batch._cache_size()`` in tests.

    ``pick`` implements the admission policy: cap at the smallest rung
    covering the queue (a larger one only buys padding), then take the
    largest capped rung whose OBSERVED p99 — read live from the per-rung
    log-bucket histogram — fits the latency budget.  Rungs with fewer than
    ``min_obs`` observations are admitted optimistically (they were warmed,
    and a broken budget walks the ladder down within a batch or two); with
    no budget the queue cap alone decides (max throughput).
    """

    def __init__(self, sizes) -> None:
        self.sizes = sorted(set(int(s) for s in sizes))
        if not self.sizes or self.sizes[0] < 1:
            raise ValueError(f"need positive rung sizes, got {sizes!r}")
        self.max_size = self.sizes[-1]

    def warm(self, model, d: int, impl: str) -> None:
        """Compile every (rung, model-structure) serving program."""
        for s in self.sizes:
            jax.block_until_ready(
                _score_batch(model, jnp.zeros((s, d), jnp.float32), impl))

    def pick(self, queued: int, budget_s: float | None,
             metrics: StreamMetrics, min_obs: int = 8) -> int:
        cap = self.max_size
        for s in self.sizes:
            if s >= queued:
                cap = s
                break
        if budget_s is None:
            return cap
        for s in reversed(self.sizes):
            if s > cap:
                continue
            h = metrics.rung_latency(s)
            if h.count < min_obs or h.quantile(0.99) <= budget_s:
                return s
        return self.sizes[0]


class ModelSlot:
    """Hot-reloadable model holder with validate-before-swap (DESIGN.md §13).

    ``try_reload`` loads a candidate checkpoint (sha256-verified by
    ``checkpoint.io``), scores a zero probe batch through the serving
    program, pre-compiles every warm rung shape for the candidate, and only
    THEN swaps it in — so the swap is legal BETWEEN MICROBATCHES of a live
    stream and the first post-swap batch hits a warm program.  Any failure
    — missing file, corrupt/truncated npz, checksum mismatch, non-finite
    probe scores — leaves the previous ensemble serving and increments
    ``fedgbf_serve_reload_failures_total`` without touching any other
    serving metric; a successful swap increments
    ``fedgbf_serve_reloads_total``, records the swap wall into
    ``fedgbf_serve_swap_seconds`` and starts a fresh model segment
    (``StreamMetrics.begin_model_segment``).
    """

    def __init__(self, packed, impl: str = "packed",
                 metrics: StreamMetrics = None, warm_sizes=()) -> None:
        self.packed = packed
        self.impl = impl
        self.metrics = metrics
        self.warm_sizes = tuple(int(s) for s in warm_sizes)

    def _validate(self, packed) -> None:
        d = packed.bin_edges.shape[0]
        probe = jnp.zeros((4, d), jnp.float32)
        scores = np.asarray(_score_batch(packed, probe, self.impl))
        if not np.isfinite(scores).all():
            raise ValueError("probe batch produced non-finite scores")
        for s in self.warm_sizes:
            jax.block_until_ready(
                _score_batch(packed, jnp.zeros((s, d), jnp.float32),
                             self.impl))

    def try_reload(self, path: str) -> bool:
        t0 = time.perf_counter()
        try:
            candidate = ckpt_io.load_ensemble(path)
            self._validate(candidate)
        except (ValueError, OSError) as e:
            if self.metrics is not None:
                self.metrics.reload_failures.inc()
            print(f"reload REFUSED ({path}): {e} — keeping previous model")
            return False
        self.packed = candidate
        if self.metrics is not None:
            self.metrics.reloads.inc()
            self.metrics.swap_latency.observe(time.perf_counter() - t0)
            self.metrics.begin_model_segment()
        print(f"reload OK ({path}): {candidate.total_trees} trees / "
              f"{candidate.rounds} rounds")
        return True


def serve_stream(
    slot: ModelSlot,
    x: np.ndarray,
    *,
    ladder: BatchLadder,
    metrics: StreamMetrics = None,
    p99_budget_s: float | None = None,
    swap_plan: dict | None = None,
) -> tuple[np.ndarray, StreamMetrics]:
    """The production serving loop: admission, scoring, mid-stream swaps.

    Each iteration (1) applies any hot-swap scheduled for this batch index
    (``swap_plan``: batch_idx -> checkpoint path — swaps land BETWEEN
    microbatches, never inside one), (2) asks the ladder for a capacity
    given the queue depth and p99 budget, (3) scores one microbatch on the
    slot's current model.

    Host-copy discipline: a full clean batch goes straight from the caller's
    array into the device transfer — NO host-side staging copy.  A copy is
    made only when the batch needs mutation (inf rows zeroed before the
    compiled program; their scores return NaN and land on
    ``fedgbf_serve_rows_rejected_total``) or zero-padding to the admitted
    capacity.  Plain NaN features are NOT rejected: the fused traversal
    routes them left, the same reserved-NAN_BIN semantics training used.

    Each microbatch opens five spans on the process-global tracer
    (DESIGN.md §12), in order: ``serve.admit`` (the ladder's pick),
    ``serve.stage`` (inf scan, padding copy), ``serve.dispatch`` (host to
    device copy and the program call), ``serve.device`` (waiting for the
    scores) and ``serve.fetch`` (device slice, device to host copy, NaN
    marks, write-back).  Under ``NULL_TRACER`` they cost a method call.
    """
    tracer = obs_trace.global_tracer()
    n = x.shape[0]
    out = None  # allocated after the first batch: (n,) or (n, K) scores
    if metrics is None:
        metrics = StreamMetrics(ladder.max_size)
    pos = 0
    batch_idx = 0
    while pos < n:
        if swap_plan and batch_idx in swap_plan:
            slot.try_reload(swap_plan[batch_idx])
        queued = n - pos
        with tracer.span("serve.admit"):
            cap = ladder.pick(queued, p99_budget_s, metrics)
        real = min(cap, queued)
        with tracer.span("serve.stage"):
            view = x[pos:pos + real]
            bad = np.isinf(view).any(axis=1)
            nbad = int(bad.sum())
            if nbad or real < cap:
                batch = np.zeros((cap,) + x.shape[1:], x.dtype)
                batch[:real] = view
                if nbad:
                    batch[:real][bad] = 0.0
                metrics.rows_rejected.inc(nbad)
            else:
                batch = view
        t0 = time.perf_counter()
        with tracer.span("serve.dispatch"):
            scores = _score_batch(slot.packed, jnp.asarray(batch), slot.impl)
        with tracer.span("serve.device"):
            scores = jax.block_until_ready(scores)
        metrics.observe_batch(time.perf_counter() - t0, real, capacity=cap)
        with tracer.span("serve.fetch"):
            if out is None:
                out = np.empty((n,) + scores.shape[1:], np.float32)
            block = np.asarray(scores[:real])
            if nbad:
                block = block.copy()
                block[bad] = np.nan
            out[pos:pos + real] = block
        pos += real
        batch_idx += 1
    return out, metrics


def score_stream(
    packed,
    x: np.ndarray,
    batch_size: int = 8192,
    impl: str = "packed",
    metrics: StreamMetrics = None,
) -> tuple[np.ndarray, StreamMetrics]:
    """Score ``x`` in fixed-shape microbatches; returns (scores, metrics).

    The single-rung special case of ``serve_stream`` (kept as the simple
    API): the last partial batch is zero-padded to ``batch_size`` so every
    step hits the same compiled program.  Per-batch latency and occupancy
    land in ``metrics`` (a fresh ``StreamMetrics`` unless one is passed in
    to accumulate across calls) — fixed-size state, so an unbounded stream
    cannot grow it.
    """
    slot = ModelSlot(packed, impl)
    return serve_stream(slot, x, ladder=BatchLadder([batch_size]),
                        metrics=metrics if metrics is not None
                        else StreamMetrics(batch_size))


def main() -> None:
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--checkpoint", default=None,
                    help="packed checkpoint path (checkpoint.io.save_ensemble)")
    ap.add_argument("--save", default=None,
                    help="save the (freshly trained) packed model here")
    ap.add_argument("--dataset", choices=list(synthetic.DATASETS),
                    default="default_credit_card")
    ap.add_argument("--rounds", type=int, default=10,
                    help="training rounds when no checkpoint is given")
    ap.add_argument("--requests", type=int, default=100_000,
                    help="size of the synthetic request stream")
    ap.add_argument("--batch-size", type=int, default=8192,
                    help="microbatch capacity (the ladder's top rung)")
    ap.add_argument("--impl",
                    choices=["fused", "fused-pallas", "packed", "weighted",
                             "pallas"],
                    default="fused",
                    help="serving traversal: 'fused'/'fused-pallas' run "
                         "bin+traverse+combine as ONE program on raw floats "
                         "(DESIGN.md §14); the rest bin in a separate "
                         "dispatch first")
    ap.add_argument("--quantize", type=int, choices=[8, 16], default=None,
                    metavar="BITS",
                    help="serve an int8/int16 QuantizedEnsemble (stochastic "
                         "leaf rounding; margin error provably bounded, "
                         "printed at startup)")
    ap.add_argument("--p99-budget-ms", type=float, default=None,
                    help="latency budget: each batch admits the largest "
                         "ladder rung whose observed p99 fits (implies "
                         "--adaptive)")
    ap.add_argument("--adaptive", action="store_true",
                    help="enable the power-of-two batch ladder even without "
                         "a p99 budget (short queues admit smaller rungs "
                         "instead of padding to --batch-size)")
    ap.add_argument("--ladder-min", type=int, default=256,
                    help="smallest ladder rung (adaptive mode)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the Prometheus text exposition of the "
                         "stream metrics here ('-' for stdout)")
    ap.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                    help="serve the exposition on a localhost HTTP scrape "
                         "endpoint (0 = ephemeral port) for the stream's "
                         "duration")
    ap.add_argument("--reload", default=None, metavar="PATH",
                    help="hot-reload this checkpoint (validate-before-swap: "
                         "a corrupt or non-finite candidate is refused and "
                         "the current model keeps serving)")
    ap.add_argument("--reload-at-batch", type=int, default=None, metavar="N",
                    help="apply --reload between microbatches N-1 and N of "
                         "the live stream (default: before the stream)")
    args = ap.parse_args()

    ds = synthetic.load(args.dataset)
    if args.checkpoint:
        packed = ckpt_io.load_ensemble(args.checkpoint)
        print(f"loaded {args.checkpoint}: {packed.total_trees} trees / "
              f"{packed.rounds} rounds, depth {packed.max_depth}")
    else:
        cfg = boosting.dynamic_fedgbf_config(rounds=args.rounds)
        model, _ = boosting.train_fedgbf(
            jnp.asarray(ds.x_train), jnp.asarray(ds.y_train), cfg,
            jax.random.PRNGKey(0),
        )
        from repro.core.types import pack_ensemble

        packed = pack_ensemble(model)
        print(f"trained {packed.total_trees} trees / {packed.rounds} rounds")
    if args.save:
        ckpt_io.save_ensemble(args.save, packed)
        print(f"saved packed checkpoint to {args.save}")

    if args.quantize:
        from repro.core.types import margin_delta_bound, quantize_ensemble

        if isinstance(packed, PackedEnsemble):
            packed = quantize_ensemble(packed, bits=args.quantize,
                                       key=jax.random.PRNGKey(0))
        print(f"serving int{args.quantize} quantized tables: margin error "
              f"bound {margin_delta_bound(packed):.3e}")

    # Synthetic request stream: resample test rows up to --requests users.
    rng = np.random.default_rng(0)
    idx = rng.integers(0, ds.x_test.shape[0], args.requests)
    requests = np.asarray(ds.x_test)[idx]

    # A stream smaller than one microbatch would otherwise pad (and score)
    # mostly zeros — and the warm-up below would already score the whole
    # stream.  Cap the microbatch at the stream size instead.
    batch_size = min(args.batch_size, args.requests)
    if batch_size != args.batch_size:
        print(f"requests < batch-size: shrinking microbatch "
              f"{args.batch_size} -> {batch_size}")

    adaptive = args.adaptive or args.p99_budget_ms is not None
    ladder = BatchLadder(ladder_sizes(batch_size, args.ladder_min)
                         if adaptive else [batch_size])

    sm = StreamMetrics(batch_size)
    server = None
    if args.metrics_port is not None:
        server = obs_metrics.serve_metrics_http(sm.registry,
                                                port=args.metrics_port)
        print(f"metrics scrape endpoint: {server.url}")
    slot = ModelSlot(packed, args.impl, metrics=sm,
                     warm_sizes=ladder.sizes)
    swap_plan = {}
    if args.reload:
        if args.reload_at_batch is not None:
            swap_plan[args.reload_at_batch] = args.reload
        else:
            slot.try_reload(args.reload)

    # Warm-up compiles every ladder rung for the current model (swaps warm
    # their own candidate inside ``try_reload``), so the admission
    # controller can move between rungs with ZERO mid-stream recompiles;
    # warm batches are zero probes and never touch the stream metrics.
    d = slot.packed.bin_edges.shape[0]
    ladder.warm(slot.packed, d, args.impl)

    budget_s = (args.p99_budget_ms * 1e-3
                if args.p99_budget_ms is not None else None)
    t0 = time.perf_counter()
    scores, sm = serve_stream(slot, requests, ladder=ladder, metrics=sm,
                              p99_budget_s=budget_s, swap_plan=swap_plan)
    sm.finalize(time.perf_counter() - t0)
    # Quantiles from the log-bucket counts (geometric-midpoint estimate,
    # error bounded by half the bucket growth) — the raw latency list is
    # gone on purpose: it grew with the stream.
    q = sm.quantiles_ms()
    print(f"impl={args.impl} batch<= {batch_size} "
          f"requests={args.requests}: {sm.rows_per_s.value:,.0f} rows/s, "
          f"batch latency p50={q[0.5]:.2f}ms p90={q[0.9]:.2f}ms "
          f"p99={q[0.99]:.2f}ms "
          f"({int(sm.batches.value)} batches, "
          f"occupancy={sm.occupancy.value:.3f}, "
          f"swaps={int(sm.reloads.value)})")
    if args.metrics_out:
        text = sm.render()
        if args.metrics_out == "-":
            print(text, end="")
        else:
            with open(args.metrics_out, "w") as f:
                f.write(text)
            print(f"metrics exposition -> {args.metrics_out}")
    if server is not None:
        # one self-scrape proves the endpoint served the live registry
        from urllib.request import urlopen

        with urlopen(server.url) as resp:
            lines = resp.read().decode().count("\n")
        print(f"self-scrape {server.url}: {lines} exposition lines")
        server.close()
    print(f"score head: {scores[:5]}")


if __name__ == "__main__":
    main()
