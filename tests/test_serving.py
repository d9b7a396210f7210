"""Serving-tier tests (DESIGN.md §14): fused bin+traverse, quantized
ensembles, the batch ladder's no-recompile property, mid-stream hot-swap,
and the metrics scrape endpoint."""

import os
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import boosting
from repro.core.types import (
    PackedEnsemble,
    dequantize_ensemble,
    margin_delta_bound,
    pack_ensemble,
    quantize_ensemble,
)
from repro.checkpoint import io as ckpt_io
from repro.data import synthetic
from repro.launch import serve_fedgbf
from repro.obs import metrics as obs_metrics


@pytest.fixture(scope="module")
def model_a():
    ds = synthetic.load("default_credit_card")
    cfg = boosting.dynamic_fedgbf_config(rounds=4)
    m, _ = boosting.train_fedgbf(
        jnp.asarray(ds.x_train[:1500]), jnp.asarray(ds.y_train[:1500]),
        cfg, jax.random.PRNGKey(0),
    )
    return pack_ensemble(m), ds


@pytest.fixture(scope="module")
def model_b(model_a):
    _, ds = model_a
    cfg = boosting.dynamic_fedgbf_config(rounds=3)
    m, _ = boosting.train_fedgbf(
        jnp.asarray(ds.x_train[1500:3000]),
        jnp.asarray(ds.y_train[1500:3000]),
        cfg, jax.random.PRNGKey(7),
    )
    return pack_ensemble(m)


def _hard_rows(ds, n=301):
    """Request rows incl. the non-finite cases the fused path must route
    exactly like binning: NaN (NAN_BIN left), +inf / -inf (extreme bins)."""
    x = np.array(ds.x_test[:n], np.float32)
    x[0, 0] = np.nan
    x[1, 1] = np.inf
    x[2, 2] = -np.inf
    x[3, :] = np.nan
    return x


# ---------------------------------------------------------------------------
# Layer 1: fused bin+traverse
# ---------------------------------------------------------------------------
def test_fused_matches_binned_bit_exact(model_a):
    pe, ds = model_a
    x = jnp.asarray(_hard_rows(ds))
    ref = boosting.predict(pe, x, impl="weighted")
    fused = boosting.predict(pe, x, impl="fused")
    assert bool(jnp.all(ref == fused)), "fused must be bit-exact vs binned"


def test_fused_pallas_matches_binned_pallas_bit_exact(model_a):
    pe, ds = model_a
    x = jnp.asarray(_hard_rows(ds))
    ref = boosting.predict(pe, x, impl="pallas")
    fused = boosting.predict(pe, x, impl="fused-pallas")
    assert bool(jnp.all(ref == fused))


def test_fused_multiclass_channels(model_a):
    _, ds = model_a
    dsm = synthetic.load("credit_risk_tiers")
    cfg = boosting.dynamic_fedgbf_config(rounds=2, loss="softmax3")
    m, _ = boosting.train_fedgbf(
        jnp.asarray(dsm.x_train[:800]), jnp.asarray(dsm.y_train[:800]),
        cfg, jax.random.PRNGKey(0),
    )
    pe = pack_ensemble(m)
    x = jnp.asarray(np.array(dsm.x_test[:67], np.float32))
    ref = boosting.predict(pe, x, impl="weighted")
    fused = boosting.predict(pe, x, impl="fused")
    assert ref.shape == fused.shape == (67, 3)
    assert bool(jnp.all(ref == fused))


# ---------------------------------------------------------------------------
# Layer 2: quantized ensembles
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("bits", [8, 16])
def test_quantized_margin_within_provable_bound(model_a, bits):
    pe, ds = model_a
    q = quantize_ensemble(pe, bits=bits, key=jax.random.PRNGKey(3))
    x = jnp.asarray(_hard_rows(ds))
    oracle = boosting.predict(pe, x, impl="fused")
    got = boosting.predict(q, x, impl="fused")
    bound = margin_delta_bound(q)
    delta = float(jnp.max(jnp.abs(got - oracle)))
    assert delta <= bound, f"int{bits} delta {delta} exceeds bound {bound}"
    # structure is lossless: widening back must reproduce routing tables
    wide = dequantize_ensemble(q)
    assert bool(jnp.all(wide.feature == pe.feature))
    assert bool(jnp.all(wide.threshold == pe.threshold))


def test_quantized_checkpoint_roundtrip(model_a, tmp_path):
    pe, ds = model_a
    q = quantize_ensemble(pe, bits=8, key=jax.random.PRNGKey(3))
    path = str(tmp_path / "q8")
    ckpt_io.save_ensemble(path, q)
    loaded = ckpt_io.load_ensemble(path)
    assert type(loaded).__name__ == "QuantizedEnsemble"
    assert loaded.bits == 8
    assert loaded.leaf_q.dtype == jnp.int8
    x = jnp.asarray(np.array(ds.x_test[:64], np.float32))
    assert bool(jnp.all(boosting.predict(loaded, x, impl="fused")
                        == boosting.predict(q, x, impl="fused")))
    # quantized serves through the pallas fused kernel too, identically
    assert bool(jnp.all(boosting.predict(loaded, x, impl="fused-pallas")
                        == boosting.predict(q, x, impl="fused-pallas")))


# ---------------------------------------------------------------------------
# Layer 3: admission ladder — adaptivity without recompiles
# ---------------------------------------------------------------------------
def test_ladder_pick_respects_budget_and_queue():
    sm = serve_fedgbf.StreamMetrics(1024)
    ladder = serve_fedgbf.BatchLadder([256, 512, 1024])
    # queue cap: a short queue admits the smallest covering rung
    assert ladder.pick(100, None, sm) == 256
    assert ladder.pick(600, None, sm) == 1024
    assert ladder.pick(10_000, None, sm) == 1024
    # unobserved rungs are optimistic under a budget
    assert ladder.pick(10_000, 0.005, sm) == 1024
    # feed the top rung a latency history that breaks a 5 ms budget
    for _ in range(20):
        sm.rung_latency(1024).observe(0.050)
        sm.rung_latency(512).observe(0.002)
    assert ladder.pick(10_000, 0.005, sm) == 512
    # and a budget nothing satisfies falls to the smallest rung
    for _ in range(20):
        sm.rung_latency(256).observe(0.010)
    assert ladder.pick(10_000, 1e-6, sm) == 256


def test_adaptive_stream_never_recompiles(model_a):
    pe, ds = model_a
    x = np.array(ds.x_test[:700], np.float32)
    sizes = [128, 256, 512]
    ladder = serve_fedgbf.BatchLadder(sizes)
    ladder.warm(pe, x.shape[1], "fused")
    compiled = serve_fedgbf._score_batch._cache_size()
    slot = serve_fedgbf.ModelSlot(pe, "fused")
    out, sm = serve_fedgbf.serve_stream(
        slot, x, ladder=ladder, p99_budget_s=10.0)
    # 700 rows on a warm [128,256,512] ladder: adaptation ran (>1 rung) and
    # the jit cache did not grow — no mid-stream recompiles.
    assert serve_fedgbf._score_batch._cache_size() == compiled
    assert len(sm._rung_hists) > 1
    assert int(sm.rows.value) == 700
    ref, _ = serve_fedgbf.score_stream(pe, x, batch_size=512, impl="fused")
    np.testing.assert_array_equal(out, ref)


def test_clean_full_batch_not_copied(model_a):
    """Satellite: full clean batches go straight in — a read-only input
    array must serve fine (no mutation), and inf rows still force the
    copy-and-zero path without touching the caller's buffer."""
    pe, ds = model_a
    x = np.array(ds.x_test[:256], np.float32)
    x[7, 0] = np.inf
    x.setflags(write=False)
    before = x.copy()
    out, sm = serve_fedgbf.score_stream(pe, x, batch_size=128, impl="fused")
    np.testing.assert_array_equal(np.asarray(x), before)
    assert int(sm.rows_rejected.value) == 1
    assert np.isnan(out[7]) and np.isfinite(np.delete(out, 7)).all()


# ---------------------------------------------------------------------------
# Layer 4: mid-stream hot-swap
# ---------------------------------------------------------------------------
def test_mid_stream_swap_scores_match_each_oracle(model_a, model_b, tmp_path):
    pe_a, ds = model_a
    pe_b = model_b
    path_b = str(tmp_path / "model_b")
    ckpt_io.save_ensemble(path_b, pe_b)
    x = np.array(ds.x_test[:512], np.float32)

    sm = serve_fedgbf.StreamMetrics(128)
    ladder = serve_fedgbf.BatchLadder([128])
    slot = serve_fedgbf.ModelSlot(pe_a, "fused", metrics=sm,
                                  warm_sizes=[128])
    out, sm = serve_fedgbf.serve_stream(
        slot, x, ladder=ladder, metrics=sm, swap_plan={2: path_b})

    # batches 0-1 served model A, batches 2-3 model B — each side must be
    # bit-exact against that model's own oracle on the same rows
    oracle_a, _ = serve_fedgbf.score_stream(pe_a, x[:256], 128, "fused")
    oracle_b, _ = serve_fedgbf.score_stream(pe_b, x[256:], 128, "fused")
    np.testing.assert_array_equal(out[:256], oracle_a)
    np.testing.assert_array_equal(out[256:], oracle_b)
    assert int(sm.reloads.value) == 1
    assert int(sm.model_generation.value) == 1
    assert sm.swap_latency.count == 1
    # occupancy was re-segmented at the swap: only model B's two full
    # batches accumulate, so the gauge reads exactly 1.0
    assert sm.occupancy.value == 1.0


def test_occupancy_segments_at_swap(model_a, model_b, tmp_path):
    pe_a, ds = model_a
    path_b = str(tmp_path / "model_b2")
    ckpt_io.save_ensemble(path_b, model_b)
    # 2 full pre-swap batches, then a post-swap segment ending half-full:
    # blended occupancy would read 80/96; segmented must read 16/32 = 0.5
    x = np.array(ds.x_test[:80], np.float32)
    sm = serve_fedgbf.StreamMetrics(32)
    slot = serve_fedgbf.ModelSlot(pe_a, "fused", metrics=sm, warm_sizes=[32])
    _, sm = serve_fedgbf.serve_stream(
        slot, x, ladder=serve_fedgbf.BatchLadder([32]), metrics=sm,
        swap_plan={2: path_b})
    assert sm.occupancy.value == 0.5
    assert int(sm.padded_rows.value) == 16


def test_refused_candidate_never_perturbs_serving_histogram(
        model_a, tmp_path):
    pe, ds = model_a
    good = str(tmp_path / "good")
    ckpt_io.save_ensemble(good, pe)
    bad = str(tmp_path / "bad")
    ckpt_io.save_ensemble(bad, pe)
    # corrupt the npz payload so the sha256 check refuses the candidate
    with open(bad + ".npz", "r+b") as f:
        f.seek(120)
        byte = f.read(1)
        f.seek(120)
        f.write(bytes([byte[0] ^ 0xFF]))

    x = np.array(ds.x_test[:256], np.float32)

    def run(swap_plan):
        sm = serve_fedgbf.StreamMetrics(64)
        slot = serve_fedgbf.ModelSlot(pe, "fused", metrics=sm,
                                      warm_sizes=[64])
        out, sm = serve_fedgbf.serve_stream(
            slot, x, ladder=serve_fedgbf.BatchLadder([64]), metrics=sm,
            swap_plan=swap_plan)
        return out, sm

    base_out, base_sm = run(None)
    out, sm = run({2: bad})
    assert int(sm.reload_failures.value) == 1
    assert int(sm.reloads.value) == 0
    # scores AND every serving series identical to the no-swap run — the
    # refusal shows up ONLY on the failure counter (bucket CONTENTS carry
    # wall-clock noise; the observation counts and gauges must not move)
    np.testing.assert_array_equal(out, base_out)
    assert sm.latency.count == base_sm.latency.count == 4
    for cap, hist in sm._rung_hists.items():
        assert hist.count == base_sm._rung_hists[cap].count
    assert sm.swap_latency.count == 0
    assert int(sm.model_generation.value) == 0
    assert sm.occupancy.value == base_sm.occupancy.value
    assert int(sm.rows.value) == int(base_sm.rows.value)


# ---------------------------------------------------------------------------
# Metrics: labels + the HTTP scrape endpoint
# ---------------------------------------------------------------------------
def test_labeled_series_render_once_per_family():
    r = obs_metrics.MetricsRegistry()
    r.histogram("lat_seconds", "Latency.", labels={"batch_size": "128"})
    r.histogram("lat_seconds", "Latency.", labels={"batch_size": "256"})
    with pytest.raises(ValueError):
        r.histogram("lat_seconds", labels={"batch_size": "128"})
    text = r.render()
    assert text.count("# TYPE lat_seconds histogram") == 1
    assert 'lat_seconds_count{batch_size="128"} 0' in text
    assert 'lat_seconds_count{batch_size="256"} 0' in text


def test_metrics_http_endpoint_serves_live_registry():
    r = obs_metrics.MetricsRegistry()
    c = r.counter("reqs_total", "Requests.")
    server = obs_metrics.serve_metrics_http(r, port=0)
    try:
        c.inc(3)
        with urllib.request.urlopen(server.url) as resp:
            assert resp.status == 200
            assert "text/plain" in resp.headers["Content-Type"]
            body = resp.read().decode()
        assert body == r.render()
        assert "reqs_total 3" in body
        c.inc()  # live registry: the next scrape sees the new count
        with urllib.request.urlopen(server.url) as resp:
            assert "reqs_total 4" in resp.read().decode()
    finally:
        server.close()


# ---------------------------------------------------------------------------
# Observability (DESIGN.md §12): serve_stream's spans on the profiler clock
# ---------------------------------------------------------------------------
SERVE_SPANS = ["fedgbf.serve.admit", "fedgbf.serve.stage",
               "fedgbf.serve.dispatch", "fedgbf.serve.device",
               "fedgbf.serve.fetch"]


def test_serve_stream_spans_land_in_a_profiler_capture(model_a, tmp_path):
    from repro.obs import trace

    pe, ds = model_a
    ladder = serve_fedgbf.BatchLadder([256, 512])
    slot = serve_fedgbf.ModelSlot(pe, "fused")
    ladder.warm(pe, pe.bin_edges.shape[0], "fused")
    x = np.array(ds.x_test[:700], np.float32)  # microbatches of 512, 256
    trace.set_global_tracer(trace.AnnotatingTracer())
    try:
        jax.profiler.start_trace(str(tmp_path))
        try:
            out, _ = serve_fedgbf.serve_stream(slot, x, ladder=ladder)
        finally:
            jax.profiler.stop_trace()
    finally:
        trace.set_global_tracer(None)
    assert out.shape == (700,) and np.isfinite(out).all()

    (path,) = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
               for f in fs if f.endswith(".xplane.pb")]
    data = jax.profiler.ProfileData.from_file(path)
    events = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for plane in data.planes if plane.name.startswith("/host:")
                    for line in plane.lines for e in line.events
                    if e.name.startswith("fedgbf.serve."))
    assert [n for _, _, n in events] == SERVE_SPANS * 2
    for (_, end, _), (start, _, _) in zip(events, events[1:]):
        assert end <= start  # one after another, none nested
