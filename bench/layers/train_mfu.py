"""Whole boosting round: least time of the rounds' algorithmic work
(``counts.boosting_round``, over all the cell's chips) over the wall time of
the traced jobs."""


def read(ctx):
    if not ctx.get("wall_s") or "round_least_s" not in ctx:
        return None
    return 100.0 * ctx["round_least_s"] / ctx["wall_s"]
