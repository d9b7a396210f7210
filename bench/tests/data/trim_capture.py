"""Cut a recorded profiler capture down to a test fixture.

    python3 bench/tests/data/trim_capture.py IN.xplane.pb OUT.xplane.pb \
        --from-ms A --to-ms B

Keeps the events that overlap [A, B] milliseconds after the start of the
capture's ``bench.window`` annotation, moves that annotation to [A, B],
and drops event metadata no kept event refers to (the per-module HLO
protos among it).  Made the committed ``*_v5e.xplane.pb`` captures from
``bench/attribute.py --keep`` on a TPU v5e.  Needs TensorFlow's XPlane
protobuf module, which the profiler's installation brings.
"""

import argparse

from tensorflow.tsl.profiler.protobuf import xplane_pb2

WINDOW = "bench.window"


def _window_ps(space) -> tuple:
    for plane in space.planes:
        names = {k: m.name for k, m in plane.event_metadata.items()}
        for line in plane.lines:
            for e in line.events:
                if names.get(e.metadata_id) == WINDOW:
                    start = line.timestamp_ns * 1000 + e.offset_ps
                    return start, start + e.duration_ps
    raise ValueError(f"no {WINDOW} annotation")


def trim(space, lo_ps: int, hi_ps: int) -> None:
    for plane in space.planes:
        names = {k: m.name for k, m in plane.event_metadata.items()}
        used = set()
        for line in plane.lines:
            base = line.timestamp_ns * 1000
            kept = []
            for e in line.events:
                s = base + e.offset_ps
                if names.get(e.metadata_id) == WINDOW:
                    e.offset_ps, e.duration_ps = lo_ps - base, hi_ps - lo_ps
                elif s + e.duration_ps < lo_ps or s > hi_ps:
                    continue
                kept.append(e)
                used.add(e.metadata_id)
            del line.events[:]
            line.events.extend(kept)
        for k in [k for k in plane.event_metadata if k not in used]:
            del plane.event_metadata[k]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("src")
    ap.add_argument("dst")
    ap.add_argument("--from-ms", type=float, required=True)
    ap.add_argument("--to-ms", type=float, required=True)
    args = ap.parse_args(argv)
    space = xplane_pb2.XSpace()
    with open(args.src, "rb") as f:
        space.ParseFromString(f.read())
    start, _ = _window_ps(space)
    trim(space, start + int(args.from_ms * 1e9), start + int(args.to_ms * 1e9))
    with open(args.dst, "wb") as f:
        f.write(space.SerializeToString())


if __name__ == "__main__":
    main()
