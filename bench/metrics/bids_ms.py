"""Host ms a round in bid collection (``JasdaScheduler._collect_bids``)."""


def read(ctx):
    run = ctx.run
    return 1e3 * run.spans.seconds["bids"] / run.rounds if run.rounds else None
