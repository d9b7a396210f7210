"""Serving host path: programs built (compiled or loaded from the
persistent cache) inside the traced stretch of the open loop, counted by
the marks the program's compile counter (``repro.obs.compiles``) leaves in
the capture.  The warmed batch ladder should build none.  None where the
program has no such counter."""

from bench import spans


def read(ctx):
    return spans.compiles_in_stretch(ctx)
