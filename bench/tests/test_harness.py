"""Whole runs of the harness on the CPU at small sizes: a sound run is
correct, a run whose timed path is broken underneath is not, the control
fails the committed limits, and new files are found by name."""

import json
import os
import shutil
import time

import jax
import numpy as np
import pytest

from bench import harness, peaks, reference
from bench.drivers import train_jobs

SEED = 3_000_000_019


@pytest.fixture(autouse=True)
def cpu_peaks(monkeypatch):
    """The CPU has no published peaks; traced runs here borrow the v5e's so
    the readers have a number to divide by (none of it is reported)."""
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])


@pytest.fixture
def fresh_programs():
    """Programs traced with a broken path must not serve the next test."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def tiny(cell: str) -> dict:
    files = harness.cell_files(cell)
    files["config"]["dataset"]["rows"] = 3000
    files["config"]["model"]["rounds"] = 4
    if files["traffic"]["kind"] == "open_loop":
        files["traffic"].update(rate_rps=300.0, ladder_max=512,
                                verify_requests=300, trace_seconds=0.3)
    return files


def run(cell: str, files=None, trace=False, seconds=1.0) -> dict:
    return harness.run(cell, SEED, seconds, trace, time.perf_counter(),
                       require_chip=False, files=files or tiny(cell))


@pytest.mark.parametrize("cell", ["gmsc.train", "gmsc.serve"])
def test_sound_run_is_correct(cell):
    out = run(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"


def test_serving_cell_reports_its_median_end_to_end_and_its_tail_per_layer():
    """The whole window's p99 swings tenfold with one host stall, so it is
    no end-to-end metric; a traced run reads the calm stretch's tail."""
    assert set(run("gmsc.serve")["metrics"]) == {"setup_s", "serve_p50_ms"}
    traced = run("gmsc.serve", trace=True, seconds=3.0)
    assert traced["correct"], traced["checks"]
    tail = traced["metrics"]["serve_tail_p99_ms"]
    assert tail["unit"] == "ms" and tail["value"] > 0


def _break_training(monkeypatch, fault):
    from repro.core import forest

    orig = forest.build_forest_per_tree

    def broken(binned, g, h, smask, fmask, cfg, backend=None,
               root_delta_rows=0):
        if fault == "half_batch":
            smask = smask.at[:, smask.shape[1] // 2:].set(0.0)
        trees, pred = orig(binned, g, h, smask, fmask, cfg, backend,
                           root_delta_rows)
        if fault == "stale_state":
            pred = pred * 0.0
        if fault == "altered_answer":
            trees = trees._replace(leaf_weight=trees.leaf_weight.at[0, 0].add(0.01))
        return trees, pred

    monkeypatch.setattr(forest, "build_forest_per_tree", broken)


def _leave_out_row_exchange(monkeypatch):
    orig = jax.lax.psum

    def psum(x, axis_name, **kw):
        return x if axis_name in ("data", ("data",)) else orig(x, axis_name, **kw)

    monkeypatch.setattr(jax.lax, "psum", psum)


def _break_serving(monkeypatch, fault):
    from repro.launch import serve_fedgbf

    orig = serve_fedgbf._score_batch

    def broken(packed, x, impl):
        out = orig(packed, x, impl)
        if fault == "altered_answer":
            return out.at[0].add(0.01)
        return out.at[out.shape[0] // 2:].set(0.0)

    monkeypatch.setattr(serve_fedgbf, "_score_batch", broken)


@pytest.mark.parametrize("cell,fault", [
    ("gmsc.train", "stale_state"),
    ("gmsc.train", "half_batch"),
    ("gmsc.train", "altered_answer"),
    ("gmsc.train_2x2", "no_exchange"),
    ("gmsc.serve", "half_batch"),
    ("gmsc.serve", "altered_answer"),
])
def test_broken_timed_path_is_not_correct(cell, fault, monkeypatch,
                                          fresh_programs):
    if cell == "gmsc.serve":
        _break_serving(monkeypatch, fault)
    elif fault == "no_exchange":
        _leave_out_row_exchange(monkeypatch)
    else:
        _break_training(monkeypatch, fault)
    out = run(cell)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("precision,fault", [
    ("bfloat16", None),
    ("float32", "stale_state"), ("float32", "half_batch"),
    ("float32", "no_exchange"), ("float32", "altered_answer"),
])
def test_control_and_planted_faults_fail_the_committed_limits(precision, fault):
    """The reference in the program's place, in bfloat16 or with a planted
    fault, reads above a committed limit; in float32 it reads below all."""
    files = tiny("gmsc.train")
    model = files["config"]["model"]
    from bench import datagen

    table = datagen.credit_table(files["config"]["dataset"], SEED)
    key = train_jobs.job_key(SEED, 1)
    limits = files["limits"]
    read = lambda p, f: reference.check_training(
        table.x_train, table.y_train, key, model,
        reference.train(table.x_train, table.y_train, key, model, p, f))
    sound = read("float32", None)
    assert all(sound[k] <= limits[k] for k in limits), sound
    broken = read(precision, fault)
    assert any(broken[k] > limits[k] for k in limits), broken


def test_serving_control_fails_the_committed_limit():
    files = tiny("gmsc.serve")
    from bench import datagen
    from bench.drivers import open_loop

    table = datagen.credit_table(files["config"]["dataset"], SEED)
    ens = open_loop.make_ensemble(files["config"]["model"], table.x_train, SEED)
    rows = table.x_test[:2000]
    gap = np.max(np.abs(reference.scores(ens, rows, "bfloat16")
                        - reference.scores(ens, rows)))
    assert gap > files["limits"]["score_gap"]


def test_new_config_traffic_limits_and_metric_are_found_by_name(tmp_path,
                                                                 monkeypatch):
    """A later change adds files and entries only; the harness finds them."""
    bench_dir = tmp_path / "bench"
    for sub in ("configs", "traffic", "limits", "layers"):
        shutil.copytree(os.path.join(harness.BENCH, sub), bench_dir / sub)
    spec = json.loads((bench_dir / "configs" / "gmsc.json").read_text())
    spec["name"] = "tiny_credit"
    spec["dataset"]["rows"], spec["model"]["rounds"] = 2000, 3
    (bench_dir / "configs" / "tiny_credit.json").write_text(json.dumps(spec))
    (bench_dir / "traffic" / "few_jobs.json").write_text(json.dumps(
        {"kind": "train_jobs", "backend": "local", "trace_jobs": 1,
         "verify_jobs": 1}))
    (bench_dir / "limits" / "tiny_credit.few_jobs.json").write_text(
        (bench_dir / "limits" / "gmsc.train.json").read_text())
    (bench_dir / "layers" / "rounds_traced.py").write_text(
        "def read(ctx):\n    return ctx.get('rounds')\n")
    bench = harness.benchmark()
    bench["configs"].append({"name": "tiny_credit", "source": "test",
                             "file": "bench/configs/tiny_credit.json",
                             "reduced": ["rows"], "why": "test"})
    bench["workloads"].append({"name": "tiny_credit.few_jobs",
                               "config": "tiny_credit", "traffic": "few_jobs",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "rounds_traced", "unit": "rounds",
                               "better": "higher", "source": "host_clock",
                               "layer": "boosting round",
                               "moves": "train_round_s",
                               "workloads": ["tiny_credit.few_jobs"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    monkeypatch.setattr(harness, "BENCH", str(bench_dir))

    out = harness.run("tiny_credit.few_jobs", SEED, 0.5, True,
                      time.perf_counter(), require_chip=False)
    assert out["metrics"]["rounds_traced"] == {"value": 3, "unit": "rounds"}
    assert out["correct"], out["checks"]
