"""Conflict-resolution passes of the settle's fixed point a round, summed
over a backend's replays: the mean ``n_passes`` of the window's audit rows
(``IterationLog``)."""


def read(ctx):
    rows = ctx.run.round_log
    if not rows or not hasattr(rows[0], "n_passes"):
        return None
    return sum(r.n_passes for r in rows) / len(rows)
