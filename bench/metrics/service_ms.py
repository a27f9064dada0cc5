"""Host ms a round outside the three scheduler spans: the service loop,
admission, events and health policing.  With bids_ms, pack_ms and
settle_ms it sums to the traced run's time per round."""


def read(ctx):
    run = ctx.run
    if not run.rounds:
        return None
    inside = sum(run.spans.seconds.get(k, 0.0)
                 for k in ("bids", "pack", "settle"))
    return 1e3 * (run.window_s - inside) / run.rounds
