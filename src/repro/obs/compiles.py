"""Compile counter: JAX's compile events as serving counters (DESIGN.md §12).

JAX reports every trace, compile and persistent-cache lookup through
``jax.monitoring``.  ``install()`` registers one process-wide listener set
that keeps them in ``MetricsRegistry`` counters:

* ``fedgbf_compiles_total`` / ``fedgbf_compile_seconds_total`` — programs
  built by the backend (``/jax/core/compile/backend_compile_duration``);
  a program loaded from the persistent cache counts too, since JAX times
  the cache lookup inside that event;
* ``fedgbf_trace_seconds_total`` — Python tracing to a jaxpr
  (``/jax/core/compile/jaxpr_trace_duration``);
* ``fedgbf_compile_cache_hits_total`` / ``..._misses_total`` /
  ``fedgbf_compile_cache_load_seconds_total`` — the persistent cache
  (``/jax/compilation_cache/cache_hits``, ``cache_misses``,
  ``cache_retrieval_time_sec``; the load seconds are a part of the compile
  seconds);
* ``fedgbf_route_levels_total{form="rows"}`` — tree levels traced into a
  round-routing program by ``core.tree.level_go_right``'s row compares,
  while the counter is installed.  It counts at trace time, so a program
  loaded from the persistent cache adds nothing, and costs the device
  nothing.
* ``fedgbf_hist_levels_total{form="flat"|"nodes"}`` — histogram passes
  traced into a program by the Pallas round provider
  (``kernels.histogram.ops.compute_round_histogram_pallas``), by the
  kernel form its width chose; counted at trace time like the route
  levels;
* ``fedgbf_auc_traces_total{form="sorted_scan"}`` — AUCs traced into
  a program by ``core.metrics.auc`` (one sort, then prefix scans); counted
  at trace time like the route levels.

A repeat call of a compiled program fires none of these, so a counter that
moves during live serving is a compile the batch ladder claims never
happens.  ``StreamMetrics`` renders the counters with the stream's own.
Each built program is also marked in a ``jax.profiler`` capture by an
empty ``fedgbf.compile`` annotation at its end, so a profiled stretch can
count the programs built inside it.

The first training call and every ``StreamMetrics`` install the listener;
JAX offers no way to ask whether a listener is there, so this module keeps
the one counter and installs it once.
"""

from __future__ import annotations

from repro.obs.metrics import MetricsRegistry, _label_str
from repro.obs.trace import annotation

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

#: the name a built program leaves in a profiler capture
MARK = "compile"
#: the Pallas histogram kernel forms (``kernels.histogram.ops``)
HIST_FORMS = ("flat", "nodes")


class CompileCounter:
    """The counters, in a registry of their own."""

    def __init__(self) -> None:
        r = MetricsRegistry()
        self.registry = r
        self.compiles = r.counter(
            "fedgbf_compiles_total",
            "Programs built by the backend: compiled, or loaded from the "
            "persistent compilation cache.")
        self.compile_seconds = r.counter(
            "fedgbf_compile_seconds_total",
            "Seconds spent building programs, cache loads included.")
        self.trace_seconds = r.counter(
            "fedgbf_trace_seconds_total",
            "Seconds spent tracing Python functions to jaxprs.")
        self.cache_hits = r.counter(
            "fedgbf_compile_cache_hits_total",
            "Programs loaded from the persistent compilation cache.")
        self.cache_misses = r.counter(
            "fedgbf_compile_cache_misses_total",
            "Persistent compilation cache lookups that found nothing.")
        self.cache_load_seconds = r.counter(
            "fedgbf_compile_cache_load_seconds_total",
            "Seconds spent loading programs from the persistent cache.")
        self.route_levels = r.counter(
            "fedgbf_route_levels_total",
            "Tree levels traced into a round-routing program, routed by "
            "whole-row compares.", labels={"form": "rows"})
        self.hist_levels = {
            form: r.counter(
                "fedgbf_hist_levels_total",
                "Histogram passes traced into a program by the Pallas round "
                "provider, by kernel form.", labels={"form": form})
            for form in HIST_FORMS}
        self.auc_traces = r.counter(
            "fedgbf_auc_traces_total",
            "AUC computations traced into a program, ranked by one sort and "
            "prefix scans.", labels={"form": "sorted_scan"})
        self._durations = {TRACE_EVENT: self.trace_seconds,
                           CACHE_LOAD_EVENT: self.cache_load_seconds}
        self._events = {CACHE_HIT_EVENT: self.cache_hits,
                        CACHE_MISS_EVENT: self.cache_misses}

    @property
    def instruments(self) -> tuple:
        return (self.compiles, self.compile_seconds, self.trace_seconds,
                self.cache_hits, self.cache_misses, self.cache_load_seconds,
                self.route_levels, *self.hist_levels.values(),
                self.auc_traces)

    def snapshot(self) -> dict:
        """Every counter's value, by series (name and labels)."""
        return {m.name + _label_str(m.labels): m.value
                for m in self.instruments}

    def on_duration(self, event: str, seconds: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.compiles.inc()
            self.compile_seconds.inc(seconds)
            with annotation(MARK):
                pass
        else:
            counter = self._durations.get(event)
            if counter is not None:
                counter.inc(seconds)

    def on_event(self, event: str, **_kw) -> None:
        counter = self._events.get(event)
        if counter is not None:
            counter.inc()


_COUNTER: CompileCounter | None = None


def install() -> CompileCounter:
    """The process's compile counter, listening from the first call on."""
    global _COUNTER
    if _COUNTER is None:
        from jax import monitoring

        _COUNTER = CompileCounter()
        monitoring.register_event_duration_secs_listener(_COUNTER.on_duration)
        monitoring.register_event_listener(_COUNTER.on_event)
    return _COUNTER


def installed() -> CompileCounter | None:
    """The compile counter if ``install`` has run, else None."""
    return _COUNTER
