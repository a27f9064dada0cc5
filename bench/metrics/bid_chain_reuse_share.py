"""Share of the (agent, window) bid groups answered by a chunk chain
computed for another window of the same shape, rather than by a chain of
their own: ``100 * (1 - sum(n_bid_chains) / sum(n_bid_groups))`` over the
window's audit rows (``IterationLog``), in %."""


def read(ctx):
    rows = ctx.run.round_log
    if not rows or not hasattr(rows[0], "n_bid_groups"):
        return None
    groups = sum(r.n_bid_groups for r in rows)
    if not groups:
        return None
    return 100.0 * (1.0 - sum(r.n_bid_chains for r in rows) / groups)
