"""Histogram phase: device milliseconds per round of the ops under the
program's ``fedgbf.histogram`` scope (accumulation, sibling derivation,
compaction), whatever implements them: the Pallas kernel on one chip,
segment sums on the 2x2 mesh."""

from bench import spans


def read(ctx):
    return spans.phase_ms_per_round(ctx, ("histogram",))
