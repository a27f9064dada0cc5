"""Windows cleared by WIS a round (first sweep plus every re-clear), summed
over a backend's replays: the mean ``n_reclears`` of the window's audit
rows (``IterationLog``)."""


def read(ctx):
    rows = ctx.run.round_log
    if not rows or not hasattr(rows[0], "n_reclears"):
        return None
    return sum(r.n_reclears for r in rows) / len(rows)
