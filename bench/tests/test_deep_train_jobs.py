"""The ``deep_train_jobs`` traffic on the CPU at small sizes: a sound run
of ``higgs.train`` is correct, a run whose timed path is broken is not, and
the wide-frontier form's least bytes count every level it serves."""

import time

import jax
import pytest

from bench import harness, peaks, reference
from bench.drivers import deep_train_jobs

SEED = 3_000_000_019


@pytest.fixture(autouse=True)
def cpu_peaks(monkeypatch):
    """The CPU has no published peaks; borrow the v5e's (none reported)."""
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])


@pytest.fixture
def fresh_programs():
    jax.clear_caches()
    yield
    jax.clear_caches()


def tiny() -> dict:
    """``higgs.train`` at 3,000 rows and 3 rounds; depth 8 and B 256 kept,
    so every level runs the wide-frontier kernel form."""
    files = harness.cell_files("higgs.train")
    files["config"]["dataset"]["rows"] = 3000
    files["config"]["model"]["rounds"] = 3
    return files


def run(files=None, trace=False) -> dict:
    return harness.run("higgs.train", SEED, 0.5, trace, time.perf_counter(),
                       require_chip=False, files=files or tiny())


def test_sound_run_is_correct_and_reports_the_nodes_form():
    out = run(trace=True)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    # the CPU trace has no device ops: the device readers return nothing
    assert "hist_nodes_kernel_ms" not in out["metrics"]


def test_nodes_form_writes_count_every_depth8_level():
    files = tiny()
    model = files["config"]["model"]
    plan = reference.round_plan(model, 2100)
    trees = sum(t for t, _ in plan)
    # level 0 builds 1 root, levels 1..7 their 1..64 left children
    want = trees * 128 * 28 * 256 * 3 * 4
    assert deep_train_jobs.nodes_form_writes(model, plan, 28) == want


def _break_training(monkeypatch, fault):
    from repro.core import forest

    orig = forest.build_forest_per_tree

    def broken(binned, g, h, smask, fmask, cfg, backend=None,
               root_delta_rows=0):
        trees, pred = orig(binned, g, h, smask, fmask, cfg, backend,
                           root_delta_rows)
        if fault == "stale_state":
            pred = pred * 0.0
        if fault == "altered_answer":
            trees = trees._replace(
                leaf_weight=trees.leaf_weight.at[0, 0].add(0.01))
        return trees, pred

    monkeypatch.setattr(forest, "build_forest_per_tree", broken)


@pytest.mark.parametrize("fault", ["altered_answer", "stale_state"])
def test_broken_timed_path_is_not_correct(fault, monkeypatch, fresh_programs):
    """A leaf altered, and separately the margin update zeroed, in the
    program's own path: the run is not correct."""
    _break_training(monkeypatch, fault)
    out = run()
    assert not out["correct"], out["checks"]
