"""Training engine: programs built (compiled or loaded from the persistent
cache) inside the traced stretch, counted by the marks the program's
compile counter (``repro.obs.compiles``) leaves in the capture.  None
where the program has no such counter."""

from bench import spans


def read(ctx):
    return spans.compiles_in_stretch(ctx)
