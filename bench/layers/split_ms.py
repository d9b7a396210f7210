"""Split choice: device milliseconds per round of the ops under the
program's ``fedgbf.split`` scope (gains over every bin, the choice)."""

from bench import spans


def read(ctx):
    return spans.phase_ms_per_round(ctx, ("split",))
