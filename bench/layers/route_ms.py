"""Routing: device milliseconds per round of the ops under the program's
``fedgbf.route`` scope (each level's per-row node gather)."""

from bench import spans


def read(ctx):
    return spans.phase_ms_per_round(ctx, ("route",))
