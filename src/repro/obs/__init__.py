"""Observability layer: host-side spans, Perfetto trace export, serving
metrics (DESIGN.md §12).

Split by concern so nothing here drags jax into import time:

* ``trace``    — ``Span``/``Tracer`` with a zero-overhead disabled path
                 (``NULL_TRACER``), the profiler-only ``AnnotatingTracer``,
                 plus the process-global tracer seam the launchers flip on
                 with ``--trace``; live spans land in ``jax.profiler``
                 captures as ``fedgbf.<name>`` annotations;
* ``compiles`` — JAX's trace/compile/persistent-cache events as counters
                 (``install()``), rendered with the serving metrics;
* ``perfetto`` — Chrome-trace/Perfetto JSON exporter merging host spans,
                 per-round ``TrainHistory`` timing/telemetry, and the
                 ledger's per-round wire bytes into one timeline;
* ``metrics``  — log-bucketed latency histograms, counters/gauges, and a
                 Prometheus text exposition writer for the serving path;
* ``log``      — structured per-round JSON lines (``--log-json``) and their
                 parser (consumed by benchmarks).
"""

from repro.obs.trace import (  # noqa: F401
    NULL_TRACER,
    AnnotatingTracer,
    Span,
    Tracer,
    global_tracer,
    set_global_tracer,
)
