"""Share of the windows cleared by WIS that a batched DP cleared (the
lane-major host DP, or the device kernel) rather than the per-row scalar
DP, summed over a backend's replays: ``100 * sum(n_reclears_vec) /
sum(n_reclears)`` over the window's audit rows (``IterationLog``), in %."""


def read(ctx):
    rows = ctx.run.round_log
    if not rows or not hasattr(rows[0], "n_reclears_vec"):
        return None
    total = sum(r.n_reclears for r in rows)
    if not total:
        return None
    return 100.0 * sum(r.n_reclears_vec for r in rows) / total
