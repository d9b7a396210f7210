"""Program scopes and spans read from profiler captures (``bench/spans.py``),
and the readers built on them."""

import os

import pytest

from bench import harness, spans, tracing

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")
SERVE_OLD = os.path.join(DATA, "serve_v5e.xplane.pb")
NEW_READERS = ["histogram_ms", "split_ms", "route_ms", "update_ms",
               "compiles_in_window.train", "compiles_in_window.serve"]


def test_phase_is_the_innermost_program_scope():
    path = ("jit(_scan_train_program)/fedgbf.segment.T5/while/body/"
            "fedgbf.histogram/fedgbf.exchange/all-gather:")
    assert spans.phase(path) == "exchange"
    assert spans.phase("jit(f)/fedgbf.segment.T2/while/body/concatenate:"
                       ) == "segment"
    assert spans.phase("jit(_score_batch)/jit(_ensemble_pallas)/"
                       "fedgbf_ensemble_predict/pallas_call:") == ""
    assert spans.phase("") == ""


def _spans(ops, host=(), window=(0, 100)):
    return spans.Spans(window, {"/device:TPU:0": ops}, list(host))


def test_idle_is_charged_to_the_innermost_span_over_it():
    sp = _spans([("k", "", 60, 70), ("f", "histogram", 150, 160)], host=[
        ("bench.serve_call", 0, 100), ("fedgbf.serve.admit", 0, 10),
        ("fedgbf.serve.stage", 10, 30), ("fedgbf.serve.dispatch", 30, 50),
        ("fedgbf.serve.device", 50, 80), ("fedgbf.serve.fetch", 85, 95),
        ("bench.wait", 100, 200), ("fedgbf.compile", 120, 120)],
        window=(0, 250))
    idle = spans.idle_by_span(sp)
    ns = {k: round(v * 1e9) for k, v in idle.items()}
    assert ns == {"bench.wait": 90, "host.other": 50,
                  "fedgbf.serve.stage": 20, "fedgbf.serve.dispatch": 20,
                  "fedgbf.serve.device": 20, "fedgbf.serve.admit": 10,
                  "bench.serve_call": 10, "fedgbf.serve.fetch": 10}
    assert sum(ns.values()) == 250 - 20  # the window less the busy time
    assert spans.compile_marks(sp) == 1


def test_scoped_share_and_phase_seconds():
    sp = spans.Spans((0, 100), {
        "/device:TPU:0": [("a", "histogram", 0, 40), ("b", "", 40, 50)],
        "/device:TPU:1": [("a", "histogram", 0, 20), ("c", "split", 20, 30)],
    }, [])
    assert spans.scoped_share(sp) == pytest.approx((0.8 + 1.0) / 2)
    assert spans.phase_seconds(sp, ("histogram",)) == pytest.approx(30e-9)
    assert spans.by_phase(sp) == pytest.approx(
        {"histogram": 30e-9, "split": 5e-9, "": 5e-9})
    assert spans.has_scopes(sp)


@pytest.mark.skipif(not os.path.exists(SERVE_OLD), reason="no recorded trace")
def test_existing_readers_read_the_old_capture_as_before():
    """Every reader the benchmark had, and the reduction under them, give
    the values they gave before this module existed on the capture recorded
    for them (a program without scopes or spans)."""
    tr = tracing.load(SERVE_OLD)
    ctx = {"trace": tr, "rows": 6000, "predict_least_s": 1e-6, "chips": 1,
           "device_kind": "TPU v5 lite"}
    want = {"device_idle.serve": 91.53991705041787,
            "predict_kernel_ms": 0.6886118333333333,
            "predict_roofline": 0.024203282400752625,
            "serve_mfu": 0.02312762773215339}
    for name, value in want.items():
        assert harness.layer_reader(name)(ctx) == pytest.approx(value,
                                                                rel=1e-12)
    assert tracing.busy_s(tr) == pytest.approx(0.004323833, rel=1e-12)
    assert tracing.window_s(tr) == pytest.approx(0.051108636, rel=1e-12)
    b = tracing.breakdown(tr)
    assert b["device_ops"][0] == ["fedgbf_ensemble_predict.1",
                                  pytest.approx(0.004131671, rel=1e-12)]
    assert b["idle_gaps"][0] == ["bench.serve_call",
                                 pytest.approx(0.002140985, rel=1e-12)]
    assert [g[0] for g in b["idle_gaps"]] == ["bench.serve_call"] * 10


@pytest.mark.skipif(not os.path.exists(SERVE_OLD), reason="no recorded trace")
def test_a_capture_without_scopes_reads_as_no_value():
    """The parent program's capture: the same ops and busy time as
    ``tracing`` reads, no scope, no span, so every new reader is None."""
    sp = spans.load(SERVE_OLD)
    tr = tracing.load(SERVE_OLD)
    assert list(sp.devices) == list(tr.devices) and sp.window == tr.window
    ops = sp.devices["/device:TPU:0"]
    assert len(ops) == len(tr.devices["/device:TPU:0"])
    busy = tracing.union_ns((s, e) for _, _, s, e in ops) * 1e-9
    assert busy == pytest.approx(tracing.busy_s(tr), abs=len(ops) * 2e-9)
    assert not spans.has_scopes(sp)
    assert {n for n, _, _ in sp.host} == {"bench.window", "bench.serve_call"}
    ctx = {"trace": tr, "capture": SERVE_OLD, "rounds": 40, "rows": 6000}
    for name in NEW_READERS[:4]:
        assert harness.layer_reader(name)(ctx) is None


TRAIN = os.path.join(DATA, "train_v5e.xplane.pb")
SERVE = os.path.join(DATA, "serve_spans_v5e.xplane.pb")
# Both recorded on a TPU v5e with ``bench/attribute.py --trace 1 --spans 1
# --keep`` and cut with ``data/trim_capture.py``: ``TRAIN`` is 200 ms of
# ``gmsc.train`` around the boundary of jobs 2 and 3 (the end of one scan
# program, the history fetch and model assembly, the next job's binning and
# the start of its program); ``SERVE`` is 50 ms of ``gmsc.serve``.


@pytest.mark.skipif(not os.path.exists(TRAIN), reason="no recorded trace")
def test_training_capture_reads_by_scope():
    sp = spans.load(TRAIN)
    assert spans.has_scopes(sp)
    phases = spans.by_phase(sp)
    assert {"histogram", "split", "route", "leaf", "update", "eval",
            "sample"} <= set(phases)
    ctx = {"trace": tracing.load(TRAIN), "capture": TRAIN, "rounds": 1}
    want = {"histogram_ms": 1.783816, "split_ms": 0.025476,
            "route_ms": 10.73333, "update_ms": 3.687736}
    for name, value in want.items():
        assert harness.layer_reader(name)(ctx) == pytest.approx(value,
                                                                rel=1e-9)
    # the same ops, busy time and kernel as the HLO-name reduction reads
    assert tracing.busy_s(ctx["trace"]) == pytest.approx(
        sum(tracing.union_ns((s, e) for _, _, s, e in ops)
            for ops in sp.devices.values()) * 1e-9, rel=1e-4)
    assert spans.phase_seconds(sp, ("histogram",)) >= tracing.op_seconds(
        ctx["trace"], lambda n: "fedgbf_histogram" in n)
    # outside every scope here: the next job's binning, eager, unjitted
    assert spans.scoped_share(sp) == pytest.approx(0.5596464796578742,
                                                   rel=1e-9)
    # the device idles while the host assembles the model, one eager
    # slice at a time
    idle = spans.idle_by_span(sp)
    assert next(iter(idle)) == "fedgbf.assemble_model"
    assert idle["fedgbf.assemble_model"] == pytest.approx(0.095362915,
                                                          rel=1e-9)
    assert spans.compile_marks(sp) == 0


@pytest.mark.skipif(not os.path.exists(SERVE), reason="no recorded trace")
def test_serving_capture_holds_five_spans_per_microbatch_in_order():
    sp = spans.load(SERVE)
    names = [n for n, _, _ in sp.host if n.startswith("fedgbf.serve.")]
    names = names[names.index("fedgbf.serve.admit"):]
    cycle = ["fedgbf.serve.admit", "fedgbf.serve.stage",
             "fedgbf.serve.dispatch", "fedgbf.serve.device",
             "fedgbf.serve.fetch"]
    whole = len(names) // 5
    assert whole >= 5 and names[:5 * whole] == cycle * whole
    assert not spans.has_scopes(sp)  # scoring is not a training phase
    idle = spans.idle_by_span(sp)
    assert next(iter(idle)) == "fedgbf.serve.device"
    tr = tracing.load(SERVE)  # equal up to the trace viewer's rounding
    assert sum(idle.values()) == pytest.approx(
        tracing.window_s(tr) - tracing.busy_s(tr), rel=1e-5)


@pytest.mark.skipif(not os.path.exists(SERVE), reason="no recorded trace")
def test_compile_readers_count_marks_only_where_the_program_counts():
    from repro.obs import compiles

    ctx = {"capture": SERVE}
    if compiles.installed() is None:
        for name in NEW_READERS[4:]:
            assert harness.layer_reader(name)(ctx) is None
    compiles.install()
    for name in NEW_READERS[4:]:
        assert harness.layer_reader(name)(ctx) == 0
    sp = spans.load(SERVE)
    marked = spans.Spans(sp.window, sp.devices,
                         sp.host + [(spans.COMPILE_MARK, sp.window[0] + 1,
                                     sp.window[0] + 1)])
    assert spans.compile_marks(marked) == 1
