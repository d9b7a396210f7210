"""Observability layer (DESIGN.md §12): spans, trace export, metrics,
MessageMeter reset semantics, and the scan engine's per-segment wall times."""

import json
import tracemalloc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import boosting
from repro.federation.compress import MessageMeter
from repro.obs import log as obs_log
from repro.obs import metrics as obs_metrics
from repro.obs import perfetto, trace


# ---------------------------------------------------------------------------
# MessageMeter: phase_counts / phase_totals / reset
# ---------------------------------------------------------------------------
def test_message_meter_totals_counts_and_reset():
    m = MessageMeter()
    m.record("histograms", np.zeros((4, 2), np.float32))   # 32 B
    m.record("histograms", np.zeros(8, np.int8))           # 8 B
    m.record("grad_broadcast", np.zeros(3, np.float32))    # 12 B
    assert m.phase_totals() == {"histograms": 40, "grad_broadcast": 12}
    assert m.phase_counts() == {"histograms": 2, "grad_broadcast": 1}

    m.reset()
    assert m.entries == []
    assert m.phase_totals() == {} and m.phase_counts() == {}

    # a fresh record after reset starts from zero, not from the old totals
    m.record("histograms", np.zeros(1, np.float32))
    assert m.phase_totals() == {"histograms": 4}
    assert m.phase_counts() == {"histograms": 1}


# ---------------------------------------------------------------------------
# Tracer: nesting, disabled path, global seam
# ---------------------------------------------------------------------------
def test_span_nesting_contains_child():
    tr = trace.Tracer()
    with tr.span("outer", cat="test"):
        with tr.span("inner", cat="test", args={"k": 1}):
            pass
    assert [s.name for s in tr.spans] == ["inner", "outer"]  # exit order
    inner, outer = tr.spans
    assert inner.depth == 1 and outer.depth == 0
    assert outer.t0 <= inner.t0 <= inner.t1 <= outer.t1
    assert inner.args == {"k": 1}
    # depth restored for a sibling span after the nest closes
    with tr.span("sibling"):
        pass
    assert tr.spans[-1].depth == 0


def test_disabled_tracer_is_allocation_free():
    tr = trace.NULL_TRACER
    assert tr.enabled is False
    # span() hands back ONE shared singleton — no per-call object
    assert tr.span("a") is tr.span("b")
    tr.add_span("x", 0.0, 1.0)
    tr.counter("c", {"v": 1})
    # and the hot loop allocates nothing measurable
    with tr.span("warm"):
        pass
    tracemalloc.start()
    before, _ = tracemalloc.get_traced_memory()
    for _ in range(1000):
        with tr.span("hot"):
            pass
    after, _ = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert after - before < 512  # loop-iterator slack only, no per-span cost


def test_global_tracer_seam():
    assert trace.global_tracer() is trace.NULL_TRACER
    t = trace.Tracer()
    try:
        trace.set_global_tracer(t)
        assert trace.global_tracer() is t
    finally:
        trace.set_global_tracer(None)
    assert trace.global_tracer() is trace.NULL_TRACER


# ---------------------------------------------------------------------------
# Perfetto / Chrome-trace export
# ---------------------------------------------------------------------------
def test_chrome_trace_export_schema(tmp_path):
    tr = trace.Tracer()
    with tr.span("compile", cat="host"):
        pass
    tr.add_span("round 1", 10.0, 11.0, cat="round", track="rounds",
                args={"n_trees": 5})
    tr.add_span("histograms", 10.0, 11.0, cat="wire", track="wire/histograms",
                args={"bytes": 1234})
    tr.counter("live_split_nodes", {"nodes": 7}, ts=10.5)

    path = tmp_path / "trace.json"
    n = perfetto.export_chrome_trace(str(path), tr, metadata={"backend": "x"})
    doc = json.loads(path.read_text())
    events = doc["traceEvents"]
    assert n == len(events) and doc["metadata"] == {"backend": "x"}

    xs = [e for e in events if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"compile", "round 1", "histograms"}
    for e in xs:  # complete events need ts/dur/pid/tid to load in Perfetto
        assert {"ts", "dur", "pid", "tid"} <= e.keys() and e["dur"] >= 0
    # tracks surface as thread_name metadata, one tid per track
    names = {e["args"]["name"]: e["tid"] for e in events
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"host", "rounds", "wire/histograms"} <= set(names)
    assert len(set(names.values())) == len(names)
    assert any(e["ph"] == "C" for e in events)
    # the wire-span byte args survive the round trip
    hist = [e for e in xs if e["name"] == "histograms"]
    assert hist[0]["args"]["bytes"] == 1234
    assert perfetto.wire_span_phase_totals(tr) == {"histograms": 1234}


# ---------------------------------------------------------------------------
# Metrics: log-bucket histogram, registry exposition
# ---------------------------------------------------------------------------
def test_log_bucket_histogram_quantiles_from_buckets():
    h = obs_metrics.LogBucketHistogram("lat", lo=1e-5, hi=60.0)
    vals = np.random.default_rng(0).lognormal(-5.0, 1.0, 5000)
    for v in vals:
        h.observe(float(v))
    assert h.count == 5000
    rel_err_bound = (h.growth - 1.0)  # midpoint estimate: half-bucket + slack
    for q in (0.5, 0.9, 0.99):
        exact = float(np.quantile(vals, q))
        assert abs(h.quantile(q) - exact) / exact <= rel_err_bound
    assert h.sum == pytest.approx(vals.sum(), rel=1e-9)


def test_log_bucket_histogram_memory_is_bounded():
    h = obs_metrics.LogBucketHistogram("lat")
    size0 = h.counts.size
    for v in np.random.default_rng(1).exponential(0.01, 20000):
        h.observe(float(v))
    # fixed bucket array, no raw-sample storage anywhere on the instance
    assert h.counts.size == size0
    assert not any(isinstance(v, list) for v in vars(h).values())
    assert np.isnan(obs_metrics.LogBucketHistogram("e").quantile(0.5))


def test_prometheus_exposition_format():
    r = obs_metrics.MetricsRegistry()
    c = r.counter("rows_total", "Rows scored.")
    g = r.gauge("occupancy")
    h = r.histogram("lat_seconds", "Latency.", lo=1e-3, hi=10.0)
    c.inc(5)
    g.set(0.75)
    for v in (0.002, 0.002, 0.5):
        h.observe(v)
    text = r.render()
    assert "# HELP rows_total Rows scored.\n# TYPE rows_total counter" in text
    assert "\nrows_total 5\n" in text
    assert "# TYPE occupancy gauge" in text and "\noccupancy 0.75\n" in text
    assert "# TYPE lat_seconds histogram" in text
    assert 'lat_seconds_bucket{le="+Inf"} 3' in text
    assert "lat_seconds_count 3" in text
    # bucket lines are cumulative and ordered
    cums = [int(line.rsplit(" ", 1)[1]) for line in text.splitlines()
            if line.startswith("lat_seconds_bucket")]
    assert cums == sorted(cums)
    with pytest.raises(ValueError, match="duplicate"):
        r.counter("rows_total")
    with pytest.raises(ValueError):
        c.inc(-1)


# ---------------------------------------------------------------------------
# Structured round log
# ---------------------------------------------------------------------------
def _fake_history():
    h = boosting.TrainHistory(engine="scan")
    h.n_trees = [5, 4]
    h.rho_id = [0.1, 0.2]
    h.wall_time_s = [0.25, 0.125]
    h.rounds = [2]
    h.train = [{"auc": 0.9}]
    h.valid = []
    h.telemetry = {"split_nodes_per_level": [[5, 9, 11], [4, 8, 10]],
                   "sampled_entries": [51, 102],
                   "grad_absmean": [0.5, 0.4]}
    h.segments = [{"width": 5, "first_round": 0, "rounds": 1,
                   "root_delta_rows": 0, "wall_s": 0.25, "t0": 1.0, "t1": 1.25},
                  {"width": 4, "first_round": 1, "rounds": 1,
                   "root_delta_rows": 0, "wall_s": 0.125, "t0": 1.25,
                   "t1": 1.375}]
    return h


def test_round_log_renders_and_parses_back():
    hist = _fake_history()
    bytes_rows = [{"histograms": 100, "grad_broadcast": 8, "id_partition": 0},
                  {"histograms": 80, "grad_broadcast": 8, "id_partition": 0}]
    lines = obs_log.render_round_lines(hist, bytes_rows)
    assert len(lines) == 2
    noisy = "backend=vfl banner\n" + "\n".join(lines) + "\nTEST: auc=0.9\n"
    recs = obs_log.parse_round_log(noisy)
    assert [r["round"] for r in recs] == [1, 2]
    assert recs[0]["metrics"] is None and recs[1]["metrics"] == {"auc": 0.9}
    assert recs[0]["n_trees"] == 5 and recs[0]["wall_s"] == 0.25
    assert recs[0]["liveness"]["split_nodes_per_level"] == [5, 9, 11]
    assert recs[0]["bytes"] == {"histograms": 100, "grad_broadcast": 8}
    # zero-byte phases are dropped from the line, never miscounted
    assert "id_partition" not in recs[0]["bytes"]


def test_training_timeline_merges_rounds_and_wire_bytes():
    hist = _fake_history()
    tr = trace.Tracer()
    rows = [{"histograms": 100}, {"histograms": 80}]
    perfetto.add_training_timeline(tr, hist, rows)
    rounds = [s for s in tr.spans if s.track == "rounds"]
    assert [s.name for s in rounds] == ["round 1", "round 2"]
    assert rounds[0].args["n_trees"] == 5
    assert rounds[1].args["metrics"] == {"auc": 0.9}
    # wire spans carry exactly the ledger rows: totals reconcile by sum
    assert perfetto.wire_span_phase_totals(tr) == {"histograms": 180}
    # counters: liveness + cumulative wire bytes
    names = {c[0] for c in tr.counters}
    assert {"live_split_nodes", "wire_bytes/histograms"} <= names


# ---------------------------------------------------------------------------
# Scan engine: true per-segment wall time + in-graph telemetry
# ---------------------------------------------------------------------------
def _small_problem(n=256, d=6):
    rng = np.random.default_rng(7)
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = (x[:, 0] - x[:, 1] > 0).astype(np.float32)
    return jnp.asarray(x), jnp.asarray(y)


def test_scan_wall_time_is_per_segment_not_smeared():
    x, y = _small_problem()
    cfg = boosting.dynamic_fedgbf_config(rounds=6)
    tr = trace.Tracer()
    _, hist = boosting.train_fedgbf(x, y, cfg, jax.random.PRNGKey(0),
                                    tracer=tr, telemetry=True)
    assert len(hist.wall_time_s) == cfg.rounds
    assert all(v > 0 for v in hist.wall_time_s)
    # segments cover every round exactly once, in order
    assert sum(s["rounds"] for s in hist.segments) == cfg.rounds
    firsts = [s["first_round"] for s in hist.segments]
    assert firsts == sorted(firsts) and firsts[0] == 0
    # per-round wall is the segment wall smeared WITHIN the segment only
    i = 0
    for seg in hist.segments:
        per = seg["wall_s"] / seg["rounds"]
        for _ in range(seg["rounds"]):
            assert hist.wall_time_s[i] == pytest.approx(per)
            i += 1
        assert seg["t1"] >= seg["t0"]
    # the 5->2 schedule has >= 2 distinct segment widths: walls must be able
    # to differ across segments (the old engine forced them all equal)
    assert len({s["width"] for s in hist.segments}) >= 2
    assert hist.overhead_s >= 0.0
    # host spans recorded around the program call
    assert {"binning", "scan_program", "fetch_history"} <= {
        s.name for s in tr.spans}
    assert any(s.name.startswith("segment[T=") for s in tr.spans)

    # telemetry block: fetched per round in the single sync
    tele = hist.telemetry
    assert np.asarray(tele["split_nodes_per_level"]).shape == (6, 3)
    assert len(tele["sampled_entries"]) == 6
    assert all(v >= 0 for v in tele["sampled_entries"])

    # the timeline builder can place every round on the trace
    assert len(perfetto.round_intervals(hist)) == 6


def test_scan_and_loop_telemetry_agree():
    x, y = _small_problem()
    cfg = boosting.dynamic_fedgbf_config(rounds=4)
    _, hs = boosting.train_fedgbf(x, y, cfg, jax.random.PRNGKey(0),
                                  telemetry=True)
    _, hl = boosting.train_fedgbf(x, y, cfg, jax.random.PRNGKey(0),
                                  engine="loop", telemetry=True)
    assert hs.telemetry["split_nodes_per_level"] == \
        hl.telemetry["split_nodes_per_level"]
    assert hs.telemetry["sampled_entries"] == hl.telemetry["sampled_entries"]
    # loop engine records one single-round segment per round
    assert [s["rounds"] for s in hl.segments] == [1] * 4


def test_telemetry_off_leaves_history_clean():
    x, y = _small_problem(n=128)
    cfg = boosting.dynamic_fedgbf_config(rounds=3)
    _, hist = boosting.train_fedgbf(x, y, cfg, jax.random.PRNGKey(0))
    assert hist.telemetry == {}
    assert len(hist.wall_time_s) == 3 and hist.total_wall_time_s > 0


# ---------------------------------------------------------------------------
# Per-round wire rows sum exactly to the run totals (trace/ledger contract)
# ---------------------------------------------------------------------------
def test_per_round_cost_sums_to_assembled_run():
    from repro.core.types import FedGBFConfig
    from repro.federation import protocol

    cfg = FedGBFConfig(rounds=5, n_trees_max=5, n_trees_min=2,
                       rho_id_min=0.1, rho_id_max=0.3)
    per_tree = {"histograms": 1000, "feature_mask": 4, "id_partition": 64,
                "grad_broadcast": 0, "split_candidates": 0}
    rows = protocol.per_round_cost(per_tree, grad_per_round=512,
                                   passive_parties=3, cfg=cfg)
    assert len(rows) == 5
    total = protocol.measured_run_cost(per_tree, 512, 3, cfg)
    for phase in protocol.WIRE_PHASES:
        assert sum(r[phase] for r in rows) == total[phase]
    # ledger round-trip: record_run stores the probe, per_round_measured
    # reproduces self.measured exactly
    spec = protocol.ProtocolSpec(
        n_samples=512, party_dims=(2, 2), num_bins=32, max_depth=3)
    led = protocol.ProtocolLedger(spec=spec, cfg=cfg)
    led.record_run(per_tree, 512)
    rows2 = led.per_round_measured()
    for phase in protocol.WIRE_PHASES:
        assert sum(r[phase] for r in rows2) == led.measured[phase]


# ---------------------------------------------------------------------------
# Profiler-facing tracing: the annotating tracer and the compile counter
# ---------------------------------------------------------------------------
def test_annotating_tracer_keeps_nothing_and_records_no_ticks():
    tr = trace.AnnotatingTracer()
    assert tr.enabled and not tr.records
    assert not trace.NULL_TRACER.records and trace.Tracer().records
    with tr.span("outer"):
        with tr.span("inner"):
            pass
    tr.add_span("derived", 0.0, 1.0)
    tr.counter("c", {"v": 1})
    assert not hasattr(tr, "spans") and not hasattr(tr, "counters")
    # a span is a fresh profiler annotation named into the fedgbf namespace
    assert isinstance(tr.span("x"), jax.profiler.TraceAnnotation)
    assert tr.span("x") is not tr.span("x")


def test_recording_tracer_spans_reach_a_profiler_capture(tmp_path):
    import os

    tr = trace.Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("outer"):
            with tr.span("inner"):
                pass
    finally:
        jax.profiler.stop_trace()
    assert [s.name for s in tr.spans] == ["inner", "outer"]
    (path,) = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
               for f in fs if f.endswith(".xplane.pb")]
    data = jax.profiler.ProfileData.from_file(path)
    names = {e.name for plane in data.planes for line in plane.lines
             for e in line.events}
    assert {"fedgbf.outer", "fedgbf.inner"} <= names


def test_compile_counter_counts_a_fresh_jit_once_and_a_repeat_never():
    from repro.launch import serve_fedgbf
    from repro.obs import compiles

    counter = compiles.install()
    assert compiles.install() is counter and compiles.installed() is counter
    f = jax.jit(lambda a: a * 3.0 + 1.0)  # a new function: a fresh compile
    a = jnp.arange(5.0)
    before = counter.snapshot()
    f(a).block_until_ready()
    first = counter.snapshot()
    f(a).block_until_ready()
    again = counter.snapshot()
    assert first["fedgbf_compiles_total"] - before["fedgbf_compiles_total"] == 1
    assert first["fedgbf_compile_seconds_total"] > \
        before["fedgbf_compile_seconds_total"]
    assert first["fedgbf_trace_seconds_total"] > \
        before["fedgbf_trace_seconds_total"]
    assert again == first  # the repeat call builds nothing
    # the serving bundle renders the process's counters with its own
    text = serve_fedgbf.StreamMetrics(8).render()
    assert "# TYPE fedgbf_compiles_total counter" in text
    assert f"fedgbf_compiles_total {int(again['fedgbf_compiles_total'])}" \
        in text


def test_auc_trace_is_counted_once_and_a_repeat_never():
    """One AUC traced into a program adds 1 to
    ``fedgbf_auc_traces_total{form="sorted_scan"}``; a repeat call of the
    compiled program adds nothing."""
    from repro.core import metrics
    from repro.obs import compiles

    counter = compiles.install()
    y = jnp.asarray([0.0, 1.0, 1.0, 0.0, 1.0])
    s = jnp.asarray([0.1, 0.9, 0.4, 0.4, 0.7])
    before = counter.auc_traces.value
    f = jax.jit(lambda a, b: metrics.auc(a, b))  # a new function: one trace
    first = float(f(y, s))
    assert counter.auc_traces.value - before == 1
    assert float(f(y, s)) == first
    assert counter.auc_traces.value - before == 1
    snap = counter.snapshot()
    assert snap['fedgbf_auc_traces_total{form="sorted_scan"}'] == \
        counter.auc_traces.value
