"""Program scopes and program spans from a profiler capture.

``tracing.load`` reads device ops by their HLO names through
``jax.profiler.ProfileData``, which drops the op's ``tf_op`` argument.  On
the chip that argument carries the op's ``jax.named_scope`` path, e.g.
``jit(_scan_train_program)/fedgbf.segment.T5/while/body/fedgbf.histogram/
...``.  ``load`` converts the capture with ``xprof``'s ``trace_viewer``
tool, which keeps it, and returns:

* per device plane, the operations of its ``XLA Ops`` line as (name,
  phase, start, end) in nanoseconds, control-flow containers left out as in
  ``tracing``.  ``phase`` is the innermost ``fedgbf.<phase>`` scope of the
  op (``fedgbf.`` taken off; ``segment`` when only a
  ``fedgbf.segment.T<width>`` scope holds it) or ``""`` outside every
  program scope;
* the host annotations whose names start with ``fedgbf.`` (the program's
  spans and compile marks) or ``bench.``, which share the device planes'
  clock.

Everything is clipped to the ``bench.window`` annotation, as in
``tracing``.  A capture of a program without scopes or spans reads as
one whose ops all have phase ``""`` and whose host holds only ``bench.*``
annotations, so the readers return None there.
"""

from __future__ import annotations

import bisect
import functools
import json
import os
import sys
from typing import NamedTuple

from bench import tracing

SCOPE = "fedgbf."
SEGMENT = "segment"
COMPILE_MARK = "fedgbf.compile"


class Spans(NamedTuple):
    window: tuple                 # (start_ns, end_ns) of the traced stretch
    devices: dict                 # plane -> [(name, phase, start_ns, end_ns)]
    host: list                    # [(name, start_ns, end_ns)]


def phase(tf_op: str) -> str:
    """The innermost program phase of a ``tf_op`` scope path."""
    found = ""
    for part in tf_op.split(":", 1)[0].split("/"):
        if part.startswith(SCOPE):
            p = part[len(SCOPE):]
            found = SEGMENT if p.startswith(SEGMENT + ".") else p
    return found


def _ns(us: float) -> int:
    return int(round(us * 1000.0))


def convert(path: str) -> list:
    """The ``trace_viewer`` events of an ``.xplane.pb`` file."""
    from xprof.convert import raw_to_tool_data

    data, _ = raw_to_tool_data.xspace_to_tool_data([path], "trace_viewer", {})
    if not data:
        raise ValueError(f"xprof could not convert {path}")
    return json.loads(data)["traceEvents"]


@functools.lru_cache(maxsize=2)
def _load(path: str, _stamp: tuple) -> Spans:
    events = convert(path)
    planes, lines = {}, {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e["name"] == "process_name":
            planes[e["pid"]] = e["args"]["name"]
        elif e["name"] == "thread_name":
            lines[(e["pid"], e["tid"])] = e["args"]["name"]
    devices, host = {}, []
    for e in events:
        if e.get("ph") != "X":
            continue
        plane = planes.get(e["pid"], "")
        s, d = _ns(e["ts"]), _ns(e.get("dur", 0.0))
        if plane.startswith("/device:"):
            if lines.get((e["pid"], e["tid"])) != tracing.OPS_LINE:
                continue
            name = e["name"]
            if tracing.CONTAINER.match(name):
                continue
            op = (name, phase(e.get("args", {}).get("tf_op", "")), s, s + d)
            devices.setdefault(plane, []).append(op)
        elif plane.startswith("/host:"):
            if e["name"].startswith((SCOPE, "bench.")):
                host.append((e["name"], s, s + d))
    spans = [h for h in host if h[0] == tracing.WINDOW]
    if not spans:
        raise ValueError(f"no {tracing.WINDOW} annotation in {path}")
    lo, hi = spans[0][1], spans[0][2]
    devices = {k: sorted(((n, p, max(s, lo), min(e, hi)) for n, p, s, e in v
                          if e > lo and s < hi), key=lambda o: o[2])
               for k, v in devices.items()}
    host = sorted(((n, max(s, lo), min(e, hi)) for n, s, e in host
                   if e >= lo and s <= hi), key=lambda h: h[1])
    return Spans((lo, hi), {k: v for k, v in devices.items() if v}, host)


def load(path: str) -> Spans:
    """Read an ``.xplane.pb`` file (or a directory holding one)."""
    if os.path.isdir(path):
        path = tracing.xplane_file(path)
    st = os.stat(path)
    return _load(os.path.abspath(path), (st.st_mtime_ns, st.st_size))


def of_run(ctx: dict):
    """The capture of the traced run a per-layer reader is given: the path
    in ``ctx["capture"]``, else the harness's trace directory.  None, with
    a note, where it cannot be read."""
    from bench import harness

    path = ctx.get("capture") or os.path.join(harness.OUT_DIR, "trace")
    try:
        return load(path)
    except Exception as e:  # a reader returns nothing rather than raise
        print(f"bench.spans: no program spans from {path}: {e!r}",
              file=sys.stderr, flush=True)
        return None


def has_scopes(sp: Spans) -> bool:
    return any(p for ops in sp.devices.values() for _, p, _, _ in ops)


def phase_seconds(sp: Spans, phases) -> float:
    """Device seconds of the ops whose phase is in ``phases``, summed over
    each plane and averaged over the device planes (``tracing.op_seconds``
    by scope)."""
    if not sp.devices:
        return 0.0
    total = sum(e - s for ops in sp.devices.values()
                for _, p, s, e in ops if p in phases)
    return total / len(sp.devices) * 1e-9


def phase_ms_per_round(ctx: dict, phases):
    """A training reader: device ms of ``phases`` per traced round, or None
    where the capture holds no program scope."""
    sp = of_run(ctx)
    if sp is None or not ctx.get("rounds") or not has_scopes(sp):
        return None
    return phase_seconds(sp, phases) * 1e3 / ctx["rounds"]


def scoped_share(sp: Spans) -> float:
    """Share of device busy time (union of ops) under a program scope,
    averaged over the device planes."""
    if not sp.devices:
        return 0.0
    shares = []
    for ops in sp.devices.values():
        busy = tracing.union_ns((s, e) for _, _, s, e in ops)
        scoped = tracing.union_ns((s, e) for _, p, s, e in ops if p)
        shares.append(scoped / busy if busy else 0.0)
    return sum(shares) / len(shares)


def by_phase(sp: Spans) -> dict:
    """Device seconds per phase (``""`` for unscoped), averaged over the
    device planes, largest first."""
    per = {}
    for ops in sp.devices.values():
        for _, p, s, e in ops:
            per[p] = per.get(p, 0) + (e - s)
    scale = 1e-9 / max(1, len(sp.devices))
    return dict(sorted(((k, v * scale) for k, v in per.items()),
                       key=lambda kv: -kv[1]))


def host_durations(sp: Spans, name: str) -> list:
    """Seconds of every host annotation called ``name``, in order."""
    return [(e - s) * 1e-9 for n, s, e in sp.host if n == name]


def compile_marks(sp: Spans) -> int:
    """Programs built inside the traced stretch, by the marks the program's
    compile counter leaves (``repro.obs.compiles``)."""
    return sum(1 for n, _, _ in sp.host if n == COMPILE_MARK)


def compiles_in_stretch(ctx: dict):
    """A reader: ``compile_marks`` of the run's capture, or None where the
    program keeps no compile counter (nothing would mark a compile)."""
    try:
        from repro.obs import compiles
    except ImportError:
        return None
    if compiles.installed() is None:
        return None
    sp = of_run(ctx)
    return None if sp is None else compile_marks(sp)


def _innermost(host: list) -> list:
    """The host annotations flattened to disjoint (start, end, label)
    pieces, each labelled by the innermost annotation over it.  The
    annotations of one thread nest, so a stack sweep does."""
    spans = sorted(((s, e, n) for n, s, e in host
                    if n not in (tracing.WINDOW, COMPILE_MARK) and e > s),
                   key=lambda h: (h[0], -h[1]))
    pieces, stack, t = [], [], None   # stack: (end, name), innermost last

    def advance(upto):
        nonlocal t
        while stack:
            end, name = stack[-1]
            stop = min(end, upto)
            if stop > t:
                pieces.append((t, stop, name))
                t = stop
            if end > upto:
                return
            stack.pop()
        t = max(t, upto)

    for s, e, n in spans:
        if t is None:
            t = s
        advance(s)
        stack.append((min(e, stack[-1][0]) if stack else e, n))
    if stack:
        advance(stack[0][0])
    return pieces


def idle_by_span(sp: Spans, device: str | None = None) -> dict:
    """Idle seconds of one device plane (the first by default) in the
    traced stretch, charged piece by piece to the innermost host
    annotation over them (``host.other`` where there is none), largest
    first."""
    if not sp.devices:
        return {}
    ops = sp.devices[device or sorted(sp.devices)[0]]
    gaps, edge = [], sp.window[0]
    for _, _, s, e in ops:
        if s > edge:
            gaps.append((edge, s))
        edge = max(edge, e)
    if sp.window[1] > edge:
        gaps.append((edge, sp.window[1]))
    pieces = _innermost(sp.host)
    starts = [p[0] for p in pieces]
    out = {}
    for gs, ge in gaps:
        covered = 0
        i = max(0, bisect.bisect_right(starts, gs) - 1)
        while i < len(pieces) and pieces[i][0] < ge:
            ps, pe, name = pieces[i]
            lap = min(pe, ge) - max(ps, gs)
            if lap > 0:
                out[name] = out.get(name, 0) + lap
                covered += lap
            i += 1
        if ge - gs > covered:
            out["host.other"] = out.get("host.other", 0) + (ge - gs - covered)
    return dict(sorted(((k, v * 1e-9) for k, v in out.items()),
                       key=lambda kv: -kv[1]))
