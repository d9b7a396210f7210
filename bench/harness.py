"""The benchmark harness, driven by ``BENCHMARK.json`` and files found by name.

For a cell it loads ``bench/configs/<config>.json``, ``bench/traffic/<traffic>
.json`` (whose ``kind`` names the generator module in ``bench/drivers``) and
``bench/limits/<cell>.json``; for a traced run it loads each per-layer metric
the cell reports from ``bench/layers/<metric>.py``.  A later configuration,
traffic mix, limit set or metric is a new file plus a new entry; nothing here
changes.

A run: set-up (counted into ``setup_s`` from process start), the measured
window, then the checks against the plain reference.  The result is one JSON
object; the numbers compared, each with its limit, come last in it and are
also the last lines on standard error.
"""

from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import os
import sys
import types

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(BENCH, ".cache", "jax")
OUT_DIR = os.path.join(BENCH, ".out")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell needs."""


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT, "BENCHMARK.json")


def cell_files(name: str, bench: dict | None = None) -> dict:
    """The cell entry of ``name`` with its configuration, traffic and limits."""
    bench = bench or benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return {"cell": cell,
            "config": load_json(ROOT, config["file"]),
            "traffic": load_json(BENCH, "traffic", cell["traffic"] + ".json"),
            "limits": load_json(BENCH, "limits", name + ".json")}


def reported(metrics: list, cell: str, e2e: set | None = None) -> list:
    """The metrics a cell reports: those listing it, or listing no cells
    (for a per-layer metric, then every cell that reports what it moves)."""
    out = []
    for m in metrics:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif e2e is None or m["moves"] in e2e:
            out.append(m)
    return out


def layer_reader(metric: str):
    """``read(ctx)`` of ``bench/layers/<metric>.py``."""
    path = os.path.join(BENCH, "layers", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_layer_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def enable_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    for every program however short its compile."""
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def devices_for(chips: int, require_chip: bool) -> list:
    import jax

    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise NoChip(f"JAX found {devs[0].platform}, not a TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def note(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def settle() -> None:
    """End of set-up: collect once, then keep Python's cyclic collector off
    until the window has closed, so that no full collection over the ~135k
    objects set-up leaves (tens of milliseconds) falls in the window;
    reference counting still frees the window's garbage."""
    gc.collect()
    gc.disable()


def run(workload: str, seed: int, seconds: float, trace: bool, t0: float,
        require_chip: bool = True, files: dict | None = None) -> dict:
    """One run of ``workload``; ``files`` replaces what ``cell_files`` would
    load (tests run small sizes through it, on the CPU)."""
    bench = benchmark()
    files = files or cell_files(workload, bench)
    cell, limits = files["cell"], files["limits"]
    enable_cache()
    devs = devices_for(cell["chips"], require_chip)

    def memory_peak() -> int:
        stats = [d.memory_stats() or {} for d in devs]
        return int(max(s.get("peak_bytes_in_use", 0) for s in stats))

    env = types.SimpleNamespace(
        cell=cell, config=files["config"], traffic=files["traffic"],
        seed=int(seed), seconds=float(seconds), trace=bool(trace), t0=t0,
        chips=cell["chips"], device_kind=devs[0].device_kind,
        trace_dir=os.path.join(OUT_DIR, "trace"), note=note, settle=settle,
        memory_peak=memory_peak)
    driver = importlib.import_module("bench.drivers." + env.traffic["kind"])
    try:
        got = driver.run(env)
    finally:
        gc.enable()

    e2e = reported(bench["end_to_end"], cell["name"])
    names = {m["name"] for m in e2e}
    result_metrics = {}
    extra = {}
    if trace:
        from bench import tracing

        tr = tracing.load(env.trace_dir)
        ctx = dict(got.get("layer", {}), trace=tr, chips=cell["chips"],
                   device_kind=env.device_kind)
        for m in reported(bench["per_layer"], cell["name"], names):
            value = layer_reader(m["name"])(ctx)
            if value is not None:
                result_metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        extra = {"busy_s": tracing.busy_s(tr), "window_s": tracing.window_s(tr)}
        breakdown = tracing.breakdown(tr)
    else:
        values = dict(got["end_to_end"], setup_s=got["setup_s"])
        for m in e2e:
            result_metrics[m["name"]] = {"value": values[m["name"]],
                                         "unit": m["unit"]}

    checks = {k: {"value": v, "limit": limits[k]}
              for k, v in sorted(got["readings"].items())}
    correct = (got["failed"] == 0
               and set(checks) == set(limits)
               and all(c["value"] <= c["limit"] for c in checks.values()))
    result = {"correct": correct, "attempted": got["attempted"],
              "failed": got["failed"], "metrics": result_metrics,
              "device": dict({"platform": devs[0].platform,
                              "kind": devs[0].device_kind, "count": len(devs),
                              "memory_peak_bytes": got["memory_peak_bytes"]},
                             **extra)}
    if trace:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for k, c in checks.items():
        note(f"check {k}: {c['value']!r} (limit {c['limit']!r})")
    return result
