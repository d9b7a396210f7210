"""Profiler capture and the reduction from a trace to device intervals.

A traced stretch is recorded with ``jax.profiler`` into an ``.xplane.pb``
file.  ``load`` reduces it to what the per-layer readers need:

* per device plane (``/device:TPU:<i>``), the operations of its ``XLA Ops``
  line as (instruction name, start, end) in nanoseconds.  An event's name
  is the whole HLO instruction text; the name before `` = `` is kept.
  Control-flow containers (``while``, ``conditional``, ``call``) span the
  operations of their bodies and are left out, so busy time is the union
  of the operations that do work;
* the benchmark's own host annotations (names starting with ``bench.``),
  which share the profiler's clock with the device planes.

Everything is then clipped to the ``bench.window`` annotation, the stretch
the benchmark chose to trace.
"""

from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil
from typing import NamedTuple

OPS_LINE = "XLA Ops"
WINDOW = "bench.window"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")
CONTAINER = re.compile(r"^(while|conditional|call)(\.\d+)?$")


class Trace(NamedTuple):
    window: tuple                 # (start_ns, end_ns) of the traced stretch
    devices: dict                 # plane name -> [(name, start_ns, end_ns)]
    host: list                    # [(name, start_ns, end_ns)] annotations


@contextlib.contextmanager
def profiler(directory: str):
    """Profile the enclosed code into ``directory`` (emptied first).  Of
    the host only annotations are recorded, no Python calls or runtime
    internals, so the host path keeps most of its speed.  The stretch that
    is read is marked inside it by a ``window()``."""
    import jax

    shutil.rmtree(directory, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(directory, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def window():
    """The annotation that marks the traced stretch."""
    import jax

    return jax.profiler.TraceAnnotation(WINDOW)


def instruction(text: str) -> str:
    """``fusion.12`` of ``%fusion.12 = f32[8]{0} fusion(...)``."""
    return text.split(" = ", 1)[0].lstrip("%")


def xplane_file(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def load(path: str) -> Trace:
    """Reduce an ``.xplane.pb`` file (or a directory holding one)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = xplane_file(path)
    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops = [(instruction(e.name), int(e.start_ns),
                    int(e.start_ns + e.duration_ns))
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            ops = [o for o in ops if not CONTAINER.match(o[0])]
            if ops:
                devices[plane.name] = sorted(ops, key=lambda o: o[1])
        elif plane.name.startswith("/host:"):
            host.extend((e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                        for line in plane.lines for e in line.events
                        if e.name.startswith("bench."))
    spans = [h for h in host if h[0] == WINDOW]
    if not spans:
        raise ValueError(f"no {WINDOW} annotation in {path}")
    lo, hi = spans[0][1], spans[0][2]
    clip = lambda evs: [(n, max(s, lo), min(e, hi)) for n, s, e in evs
                        if e > lo and s < hi]
    return Trace((lo, hi), {k: clip(v) for k, v in devices.items()},
                 clip(host))


def union_ns(intervals) -> int:
    """Length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_s(trace: Trace) -> float:
    """Seconds in which an operation ran, averaged over the device planes."""
    if not trace.devices:
        return 0.0
    return sum(union_ns((s, e) for _, s, e in ops)
               for ops in trace.devices.values()) / len(trace.devices) * 1e-9


def window_s(trace: Trace) -> float:
    return (trace.window[1] - trace.window[0]) * 1e-9


def op_seconds(trace: Trace, match) -> float:
    """Device seconds of the operations whose name ``match`` accepts, summed
    over planes and averaged over the device planes."""
    if not trace.devices:
        return 0.0
    total = sum(e - s for ops in trace.devices.values()
                for n, s, e in ops if match(n))
    return total / len(trace.devices) * 1e-9


def is_collective(name: str) -> bool:
    return bool(COLLECTIVE.match(name))


def exposed_collective_s(trace: Trace) -> float:
    """Seconds of collective operations during which no other operation
    runs on the same device, averaged over the device planes."""
    if not trace.devices:
        return 0.0
    total = 0
    for ops in trace.devices.values():
        coll = [(s, e) for n, s, e in ops if is_collective(n)]
        other = [(s, e) for n, s, e in ops if not is_collective(n)]
        total += union_ns(coll + other) - union_ns(other)
    return total / len(trace.devices) * 1e-9


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The operations that took most device time, and the longest idle gaps
    of the first device named by what the host was doing in them."""
    per = {}
    for ops in trace.devices.values():
        for n, s, e in ops:
            per[n] = per.get(n, 0) + (e - s)
    scale = 1e-9 / max(1, len(trace.devices))
    device_ops = sorted(([k, v * scale] for k, v in per.items()),
                        key=lambda kv: -kv[1])[:top]
    gaps = []
    if trace.devices:
        ops = trace.devices[sorted(trace.devices)[0]]
        edge = trace.window[0]
        for _, s, e in sorted(ops, key=lambda o: o[1]) + [
                ("", trace.window[1], trace.window[1])]:
            if s > edge:
                gaps.append((edge, s))
            edge = max(edge, e)
    labelled = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (s + e) // 2
        inner = [h for h in trace.host if h[1] <= mid < h[2] and h[0] != WINDOW]
        label = min(inner, key=lambda h: h[2] - h[1])[0] if inner else "host.other"
        labelled.append([label, (e - s) * 1e-9])
    return {"device_ops": device_ops, "idle_gaps": labelled}

