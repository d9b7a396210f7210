"""Shared benchmark utilities."""

from __future__ import annotations

import json
import os
import time

REPORT_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                          "reports")


def scale() -> str:
    """REPRO_BENCH_SCALE=full reproduces the paper's exact round counts and
    dataset sizes; the default 'quick' keeps `-m benchmarks.run` under ~10 min
    on one CPU core (same relative comparisons, smaller n / fewer rounds)."""
    return os.environ.get("REPRO_BENCH_SCALE", "quick")


def cpu_child_env(host_devices: int | None = None) -> dict:
    """Environment for a bench child process: pinned to the CPU backend
    (with ``host_devices`` forced host devices when given), so a child never
    competes with its parent for an accelerator — a chip belongs to one
    process at a time."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    if host_devices:
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={host_devices}")
    return env


def save_report(name: str, payload) -> str:
    os.makedirs(REPORT_DIR, exist_ok=True)
    path = os.path.join(REPORT_DIR, f"{name}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, default=float)
    return path


class Timer:
    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *a):
        self.seconds = time.perf_counter() - self.t0


def csv_row(name: str, us_per_call: float, derived: str) -> str:
    return f"{name},{us_per_call:.1f},{derived}"
