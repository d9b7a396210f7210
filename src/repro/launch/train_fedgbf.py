"""FedGBF training driver — the paper's workload under the real VFL runtime.

Execution is selected by a named ``TreeBackend`` from the registry
(DESIGN.md §1):

    # centralized-local (paper's evaluation mode, §4.2)
    PYTHONPATH=src python -m repro.launch.train_fedgbf --dataset default_credit_card

    # federated on a device mesh (parties = model-axis shards)
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.train_fedgbf \
        --dataset default_credit_card --backend vfl-argmax --parties 4

Fault-tolerant runtime (DESIGN.md §13):

    # chaos transport: seeded drop/corrupt/dup/delay faults at the level
    # exchange; checksum-verified retransmission keeps the model
    # bit-identical and the retried bytes reconcile in the ledger.
    ... --backend vfl-histogram --parties 2 \
        --chaos-drop 0.05 --chaos-corrupt 0.02 --chaos-seed 13

    # party dropout: parties that exhaust --retry-max degrade the round
    # (their feature candidates are masked from split search);
    # --dropout-fallback gradientless adds party-local trees instead.
    ... --party-dropout 0.3 --dropout-seed 0 --retry-max 3 \
        --dropout-fallback gradientless

    # bit-identical segment resume: checkpoint the boosting carry every
    # N rounds (atomic write + sha256 sidecar), kill anywhere, resume to
    # the same bytes as an uninterrupted run.
    ... --checkpoint ckpt/run --checkpoint-every 2 [--stop-after-round 2]
    ... --checkpoint ckpt/run --checkpoint-every 2 --resume
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import io as checkpoint_io
from repro.core import backend as backend_mod
from repro.core import boosting, metrics
from repro.core import objective as objective_mod
from repro.core.types import TreeConfig, unpack_ensemble
from repro.data import synthetic, tabular
from repro.federation import chaos as chaos_mod
from repro.federation import runtime as runtime_mod
from repro.federation import vfl  # noqa: F401  (registers vfl-* backends)
from repro.launch import compile_cache
from repro.launch import mesh as mesh_mod
from repro.obs import log as obs_log
from repro.obs import perfetto
from repro.obs import trace as obs_trace

# All registered backends are launchable, incl. the compressed-transport
# variants (vfl-histogram-q8/q16, vfl-argmax-topk; DESIGN.md §5) and their
# fault-injecting -chaos twins (DESIGN.md §13).
VFL_BACKENDS = tuple(
    n for n in backend_mod.available_backends() if n.startswith("vfl")
)


MODELS = ("dynamic_fedgbf", "fedgbf", "secureboost", "federated_forest")
NUM_BINS = 32


def model_config(model: str, rounds: int,
                 tree: TreeConfig) -> boosting.FedGBFConfig:
    """The training configuration ``--model`` names (paper §4.2)."""
    if model == "dynamic_fedgbf":
        return boosting.dynamic_fedgbf_config(rounds, tree=tree)
    if model == "fedgbf":
        return boosting.FedGBFConfig(
            rounds=rounds, tree=tree, n_trees_max=5, n_trees_min=5,
            rho_id_min=0.3, rho_id_max=0.3)
    if model == "secureboost":
        return boosting.secureboost_config(rounds, tree=tree)
    if model == "federated_forest":
        return boosting.federated_forest_config(n_trees=rounds, tree=tree)
    raise ValueError(f"unknown model {model!r}")


def _merge_histories(hists: list) -> "boosting.TrainHistory":
    """Stitch per-chunk ``TrainHistory`` objects (contiguous round windows)
    into one history covering the union — used by the ``--checkpoint-every``
    chunked training loop so the telemetry outputs see a single run."""
    if len(hists) == 1:
        return hists[0]
    out = boosting.TrainHistory(engine=hists[0].engine,
                                start_round=hists[0].start_round)
    for h in hists:
        out.rounds.extend(h.rounds)
        out.train.extend(h.train)
        out.valid.extend(h.valid)
        out.n_trees.extend(h.n_trees)
        out.rho_id.extend(h.rho_id)
        out.wall_time_s.extend(h.wall_time_s)
        out.segments.extend(h.segments)
        out.overhead_s += h.overhead_s
    if all(h.telemetry is not None for h in hists):
        keys = hists[0].telemetry.keys()
        out.telemetry = {
            k: np.concatenate([np.asarray(h.telemetry[k]) for h in hists])
            for k in keys
        }
    out.final_margin = hists[-1].final_margin
    out.final_margin_valid = hists[-1].final_margin_valid
    return out


def _stitch_models(prefix_model, models: list) -> "boosting.EnsembleModel":
    """Concatenate the resumed prefix (if any) and the chunk models into the
    full ensemble; all pieces share the same deterministic bin edges."""
    pieces = ([prefix_model] if prefix_model is not None else []) + models
    head = pieces[0]
    forests = tuple(f for m in pieces for f in m.forests)
    return boosting.EnsembleModel(
        forests=forests, learning_rate=head.learning_rate,
        base_score=head.base_score, bin_edges=head.bin_edges,
        loss=head.loss, max_depth=head.max_depth,
    )


def main() -> None:
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", choices=list(synthetic.DATASETS),
                    default="default_credit_card")
    ap.add_argument("--model", choices=MODELS, default="dynamic_fedgbf")
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--loss", default="logistic",
                    help="objective registry name (DESIGN.md §11): logistic, "
                         "squared, softmax<K> (e.g. softmax3 for "
                         "--dataset credit_risk_tiers), quantile[@alpha]. "
                         "K-channel objectives widen the histogram stats "
                         "axis to 2K+1 through every backend.")
    ap.add_argument("--n", type=int, default=0, help="subsample dataset")
    ap.add_argument("--max-depth", type=int, default=3)
    ap.add_argument("--backend", default="local",
                    choices=("local", "local-pallas") + VFL_BACKENDS,
                    help="named TreeBackend from the registry")
    ap.add_argument("--parties", type=int, default=2,
                    help="party count for vfl-* backends")
    ap.add_argument("--data-shards", type=int, default=0,
                    help="row shards over the mesh data axis for vfl-*-"
                         "sharded backends (DESIGN.md §8): each host holds "
                         "(n/data_shards, ...) rows and the per-level "
                         "histogram psums over the data axis.  0 = auto "
                         "(spread the remaining devices).  Uneven n pads "
                         "with weight-0 rows inside the backend.")
    ap.add_argument("--engine", default="scan", choices=("scan", "loop"),
                    help="training engine: static-shape scanned (one XLA "
                         "program for all rounds) or the legacy per-round "
                         "loop (DESIGN.md §4)")
    ap.add_argument("--eval-every", type=int, default=1,
                    help="evaluate metrics every k rounds (schedule and "
                         "timing are recorded every round regardless)")
    ap.add_argument("--sampling", default="uniform",
                    choices=("uniform", "goss"),
                    help="rho_id sample policy: uniform (paper eq. 4) or "
                         "GOSS (top-|g| + amplified random rest; DESIGN.md §5)")
    ap.add_argument("--hist-subtraction", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="sibling-subtraction histogram pipeline (DESIGN.md "
                         "§6, ON by default): levels >= 1 compute/exchange "
                         "only left-child histograms and derive the siblings "
                         "— halves the per-level histogram work and, on "
                         "vfl-* backends, the dominant wire message (1.75x "
                         "phase cut at depth 3).  --no-hist-subtraction "
                         "restores the direct reference pass.")
    ap.add_argument("--max-active-nodes", type=int, default=0,
                    help="frontier-compaction budget for deep trees "
                         "(DESIGN.md §9): static cap on live frontier nodes "
                         "per level; dead nodes are masked out of histograms "
                         "and the party exchange.  0 = uncompacted (use "
                         "with --max-depth > 3).")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="write a Chrome-trace/Perfetto JSON timeline of "
                         "the run (DESIGN.md §12): host spans (binning, "
                         "compile, per-segment execution), per-round spans "
                         "with metrics + frontier liveness, and — on vfl-* "
                         "backends — per-phase wire-byte spans whose bytes "
                         "reconcile exactly with ProtocolLedger.breakdown()")
    ap.add_argument("--log-json", action="store_true",
                    help="emit one structured JSON line per round (schedule, "
                         "wall time, metrics, liveness, wire bytes) instead "
                         "of the ad-hoc [round NNN] prints; parsed by "
                         "benchmarks/obs_bench.py")
    ap.add_argument("--shared-root", action="store_true",
                    help="shared-root caching (DESIGN.md §9): the level-0 "
                         "pass computes ONE unmasked histogram per round "
                         "and derives each tree's root as shared - delta "
                         "(masked-out rows); engaged per round when the "
                         "rho_id schedule clears the 0.5 crossover "
                         "(uniform sampling only).")
    # --- fault-tolerant federation runtime (DESIGN.md §13) ------------------
    ap.add_argument("--chaos-drop", type=float, default=0.0,
                    help="chaos transport: probability a level-exchange "
                         "transmission attempt is dropped (recovered by "
                         "checksum-verified retransmission, so results stay "
                         "bit-identical; only wire bytes grow)")
    ap.add_argument("--chaos-corrupt", type=float, default=0.0,
                    help="chaos transport: probability an attempt is "
                         "bit-corrupted in flight (detected by payload "
                         "checksum, recovered by retransmission)")
    ap.add_argument("--chaos-dup", type=float, default=0.0,
                    help="chaos transport: probability the final delivery is "
                         "duplicated (idempotent receive; accounting only)")
    ap.add_argument("--chaos-delay", type=float, default=0.0,
                    help="chaos transport: probability the final delivery is "
                         "delayed one poll (accounting only)")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="seed of the deterministic chaos fault plan")
    ap.add_argument("--chaos-max-retries", type=int, default=3,
                    help="in-graph retransmission budget per exchange slot")
    ap.add_argument("--party-dropout", type=float, default=0.0,
                    help="probability a party misses a coordinator poll; a "
                         "party exhausting --retry-max polls is DEGRADED for "
                         "the round (its feature candidates are masked from "
                         "split search — bit-identical to a run that never "
                         "had them)")
    ap.add_argument("--dropout-seed", type=int, default=0,
                    help="seed of the deterministic party-availability draw")
    ap.add_argument("--retry-max", type=int, default=3,
                    help="coordinator re-polls (with exponential backoff) "
                         "before degrading a silent party for the round")
    ap.add_argument("--dropout-fallback", default="none",
                    choices=("none", "gradientless"),
                    help="gradientless: parties degraded in >=1 round also "
                         "train party-local gradient-less trees (DESIGN.md "
                         "§7) whose margins are ADDED at test evaluation")
    ap.add_argument("--checkpoint", default=None, metavar="PATH",
                    help="train-state checkpoint path (atomic npz + sha256 "
                         "sidecar); segment boundaries write here")
    ap.add_argument("--checkpoint-every", type=int, default=0, metavar="N",
                    help="checkpoint the boosting carry every N rounds "
                         "(0 = only at --stop-after-round / completion)")
    ap.add_argument("--resume", action="store_true",
                    help="resume from --checkpoint: replays the full-run "
                         "RNG schedule so the finished ensemble is "
                         "byte-identical to an uninterrupted run")
    ap.add_argument("--stop-after-round", type=int, default=0, metavar="K",
                    help="stop (and checkpoint) after absolute round K — "
                         "the kill half of the kill-and-resume smoke")
    args = ap.parse_args()

    want_obs = bool(args.trace) or args.log_json
    # a recording tracer compiles the scan engine's segment ticks in, so
    # both outputs carry true per-segment walls (DESIGN.md §12)
    tracer = obs_trace.Tracer() if want_obs else obs_trace.NULL_TRACER
    obs_trace.set_global_tracer(tracer)  # checkpoint I/O etc. hang off this

    ds = synthetic.load(args.dataset, n=args.n or None)
    tree = TreeConfig(max_depth=args.max_depth, num_bins=NUM_BINS,
                      hist_subtraction=args.hist_subtraction,
                      max_active_nodes=args.max_active_nodes,
                      shared_root=args.shared_root)
    cfg = model_config(args.model, args.rounds, tree)
    if args.sampling != "uniform":
        cfg = dataclasses.replace(cfg, sampling=args.sampling)
    if args.loss != cfg.loss:
        cfg = dataclasses.replace(cfg, loss=args.loss)
    obj = objective_mod.get_objective(cfg.loss)

    x_train, y_train = ds.x_train, ds.y_train
    # --- chaos transport (DESIGN.md §13): rates > 0 auto-select the -chaos
    # twin of the requested backend; an explicit -chaos name with no rates
    # runs the zero-fault spec (checksums only — bit-identical results).
    backend_name = args.backend
    chaos_rates = (args.chaos_drop, args.chaos_corrupt,
                   args.chaos_dup, args.chaos_delay)
    if any(r > 0 for r in chaos_rates) and not backend_name.endswith("-chaos"):
        backend_name += "-chaos"
    chaos = None
    if backend_name.endswith("-chaos"):
        if backend_name not in VFL_BACKENDS:
            raise SystemExit(
                f"chaos transport needs a vfl-* backend, got {args.backend!r}"
            )
        chaos = chaos_mod.ChaosSpec(
            drop=args.chaos_drop, corrupt=args.chaos_corrupt,
            dup=args.chaos_dup, delay=args.chaos_delay,
            seed=args.chaos_seed, max_retries=args.chaos_max_retries,
        )
        print(f"chaos transport: {chaos.tag} (faults are injected, detected "
              "by checksum and retransmitted — results stay bit-identical)")
    federated = backend_name in VFL_BACKENDS
    if federated:
        aggregation = "argmax" if "argmax" in backend_name else "histogram"
        n_dev = len(jax.devices())
        if n_dev < args.parties:
            raise SystemExit(
                f"need >= {args.parties} devices (set XLA_FLAGS=--xla_force_"
                f"host_platform_device_count=...), got {n_dev}"
            )
        x_train, d_pad = tabular.pad_features(x_train, args.parties)
        mesh = mesh_mod.make_vfl_mesh(args.parties, args.data_shards)
        shards = mesh.shape["data"]
        sharded = "-sharded" in backend_name
        if sharded and x_train.shape[0] % shards:
            # shard_map needs n divisible by the data-axis extent; the
            # backend pads the remainder with weight-0 rows internally
            # (after the subsampling masks are drawn over the real n, so
            # the exact-count sampling semantics are untouched).
            print(f"sharded backend: n={x_train.shape[0]} pads to "
                  f"{-(-x_train.shape[0] // shards) * shards} inside the "
                  f"backend ({shards} sample shards, weight-0 rows)")
        bk_kw = {"chaos": chaos} if chaos is not None else {}
        backend = backend_mod.get_backend(backend_name, mesh=mesh, tree=tree,
                                          **bk_kw)
        print(f"backend={backend.name}: {args.parties} parties x "
              f"{shards} data shards, aggregation={aggregation}, "
              f"transport={backend.descriptor.transport}"
              + (", async exchange" if backend.descriptor.async_exchange
                 else ""))
        # measured wire bytes reconciled against the wire model, plus the
        # paper-world Paillier estimate — one shared entry (DESIGN.md §5)
        from repro.federation import compress

        ledger = compress.reconciled_ledger(
            mesh, tree, cfg, aggregation=aggregation,
            transport=backend.descriptor.transport_spec,
            n_samples=x_train.shape[0], num_features=d_pad,
            shard_samples=sharded,
            async_exchange=backend.descriptor.async_exchange,
            n_channels=obj.n_classes,
            chaos=chaos,
        )
        cost = ledger.predicted_paillier()
        print(f"paillier-model bytes (ledger): {cost.total/1e6:.1f} MB "
              f"{cost.breakdown()}")
        rec = ledger.reconcile()
        print(f"wire bytes: measured={rec['total']['measured']/1e6:.1f} MB "
              f"predicted={rec['total']['predicted']/1e6:.1f} MB "
              f"(match={rec['total']['match']})")
    else:
        backend = backend_mod.get_backend(backend_name)

    # --- party-dropout degradation (DESIGN.md §13) --------------------------
    dropout_sched = None
    round_mask = None
    if args.party_dropout > 0:
        policy = runtime_mod.RetryPolicy(max_retries=args.retry_max)
        dropout_sched = runtime_mod.dropout_schedule(
            args.party_dropout, cfg.rounds, args.parties,
            seed=args.dropout_seed, policy=policy,
        )
        round_mask = runtime_mod.degradation_masks(
            dropout_sched.degraded, x_train.shape[1], args.parties,
        )
        print(f"party-dropout: {dropout_sched.degraded_rounds}/{cfg.rounds} "
              f"degraded rounds, {int(dropout_sched.retries.sum())} retries, "
              f"simulated backoff {dropout_sched.backoff_s:.2f}s")

    # --- segment checkpoints + bit-identical resume (DESIGN.md §13) ---------
    fingerprint = json.dumps({
        "dataset": args.dataset, "model": args.model, "rounds": cfg.rounds,
        "loss": cfg.loss, "backend": backend_name, "parties": args.parties,
        "engine": args.engine, "sampling": cfg.sampling,
        "max_depth": args.max_depth, "n": args.n,
        "party_dropout": args.party_dropout,
        "dropout_seed": args.dropout_seed, "retry_max": args.retry_max,
    }, sort_keys=True)
    start = 0
    margin_carry = None
    prefix_model = None
    if args.resume:
        if not args.checkpoint:
            raise SystemExit("--resume needs --checkpoint PATH")
        state = checkpoint_io.load_train_state(args.checkpoint)
        if state["config_fingerprint"] != fingerprint:
            raise SystemExit(
                "--resume: checkpoint was written by a different training "
                "configuration (fingerprint mismatch)"
            )
        start = int(state["completed_rounds"])
        margin_carry = state["margin"]
        prefix_model = unpack_ensemble(state["packed"])
        print(f"resume: {start} completed rounds restored "
              f"from {args.checkpoint}")
    stop_limit = args.stop_after_round or cfg.rounds
    if not start < stop_limit <= cfg.rounds:
        raise SystemExit(
            f"--stop-after-round must be in ({start}, {cfg.rounds}]"
        )

    chunk = args.checkpoint_every or (stop_limit - start)
    models, hists = [], []
    a = start
    while a < stop_limit:
        b = min(a + chunk, stop_limit)
        model_c, hist_c = boosting.train_fedgbf(
            jnp.asarray(x_train), jnp.asarray(y_train), cfg,
            jax.random.PRNGKey(0),
            backend=backend, verbose=not args.log_json, engine=args.engine,
            eval_every=args.eval_every, tracer=tracer, telemetry=want_obs,
            round_feature_mask=round_mask, start_round=a, stop_round=b,
            init_margin=margin_carry,
        )
        models.append(model_c)
        hists.append(hist_c)
        margin_carry = hist_c.final_margin
        a = b
        if args.checkpoint:
            checkpoint_io.save_train_state(
                args.checkpoint, _stitch_models(prefix_model, models),
                margin=margin_carry, completed_rounds=a,
                fingerprint=fingerprint,
            )
            print(f"checkpoint: {a} rounds -> {args.checkpoint}")
    model = _stitch_models(prefix_model, models)
    hist = _merge_histories(hists)
    print(f"engine={hist.engine}: total train wall {hist.total_wall_time_s:.2f}s "
          f"over {len(hist.n_trees)} rounds")
    if args.stop_after_round:
        print(f"stopped after round {stop_limit} (checkpointed); "
              "re-run with --resume to continue")

    # --- unified telemetry outputs (DESIGN.md §12) --------------------------
    per_round_bytes = None
    if federated:
        # ledger rows are absolute over the full schedule; clip to the
        # executed window so they line up with the (possibly resumed) history
        rows = ledger.per_round_measured()
        per_round_bytes = rows[start:start + len(hist.n_trees)]
    faults = None
    if want_obs and (chaos is not None or dropout_sched is not None):
        faults = [dict() for _ in range(len(hist.n_trees))]
        if chaos is not None:
            plan = chaos_mod.plan_summary(
                chaos,
                chaos_mod.n_slots_per_tree(aggregation, args.max_depth),
            )
            for r in faults:  # the static plan repeats per traced tree/round
                r["faults_injected"] = plan["faults_injected"]
                r["retries"] = plan["retries"]
                r["dropped"] = plan["dropped"]
                r["corrupted"] = plan["corrupted"]
        if dropout_sched is not None:
            for i, r in enumerate(faults):
                s = dropout_sched.round_summary(start + i)
                r["retries"] = r.get("retries", 0) + s["retries"]
                r["degraded_parties"] = s["degraded_parties"]
    if args.log_json:
        for line in obs_log.render_round_lines(hist, per_round_bytes, faults):
            print(line)
    if args.trace:
        perfetto.add_training_timeline(tracer, hist, per_round_bytes, faults)
        n_events = perfetto.export_chrome_trace(
            args.trace, tracer,
            metadata={"dataset": args.dataset, "backend": backend_name,
                      "engine": hist.engine, "rounds": args.rounds},
        )
        print(f"trace: {n_events} events -> {args.trace} "
              f"(open in ui.perfetto.dev)")
        if federated and start == 0 and stop_limit == cfg.rounds:
            # acceptance contract: the trace's histogram-phase span bytes
            # are the ledger's own per-round rows, so they must sum to
            # breakdown()["measured"] exactly
            span_hist = perfetto.wire_span_phase_totals(tracer)
            led_hist = ledger.breakdown()["measured"]
            match = span_hist.get("histograms", 0) == led_hist["histograms"]
            print(f"trace: histogram-phase span bytes "
                  f"{span_hist.get('histograms', 0)} vs ledger "
                  f"{led_hist['histograms']} (match={match})")
            if not match:
                raise SystemExit("trace/ledger histogram bytes diverged")
    x_test = ds.x_test
    if federated:
        x_test, _ = tabular.pad_features(x_test, args.parties)
    margin = boosting.predict(model, jnp.asarray(x_test))
    if args.dropout_fallback == "gradientless" and dropout_sched is not None:
        # party-local gradient-less trees for every party that lost >= 1
        # round: their tree contributions (margin minus base) add onto the
        # main ensemble's test margin (DESIGN.md §7 composition rule)
        from repro.federation import gradientless

        for p in runtime_mod.degraded_parties(dropout_sched):
            sl = runtime_mod.party_column_slice(
                p, x_train.shape[1], args.parties)
            gl_model, gl_info = gradientless.train_gradientless(
                jnp.asarray(np.asarray(x_train)[:, sl]),
                jnp.asarray(y_train), cfg,
                jax.random.PRNGKey(1000 + p), num_parties=1,
            )
            delta = (boosting.predict(gl_model,
                                      jnp.asarray(np.asarray(x_test)[:, sl]))
                     - gl_model.base_score)
            margin = margin + delta
            print(f"gradientless fallback: party {p} "
                  f"({gl_model.total_trees} local trees) added to margin")
    if obj.n_classes > 1:
        rep = metrics.multiclass_report(jnp.asarray(ds.y_test), margin)
        print(f"TEST: acc={rep['acc']:.4f} macro_f1={rep['macro_f1']:.4f} "
              f"(total trees: {model.total_trees}, K={obj.n_classes})")
    else:
        rep = metrics.classification_report(jnp.asarray(ds.y_test), margin)
        print(f"TEST: auc={rep['auc']:.4f} acc={rep['acc']:.4f} "
              f"f1={rep['f1']:.4f} (total trees: {model.total_trees})")


if __name__ == "__main__":
    main()
