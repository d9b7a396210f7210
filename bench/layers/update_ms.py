"""Leaf and margin update: device milliseconds per round of the ops under
the program's ``fedgbf.leaf`` and ``fedgbf.update`` scopes (leaf
statistics and weights, per-tree predictions, the margin step)."""

from bench import spans


def read(ctx):
    return spans.phase_ms_per_round(ctx, ("leaf", "update"))
