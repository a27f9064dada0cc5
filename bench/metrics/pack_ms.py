"""Host ms a round in pool pack and dispatch
(``JasdaScheduler._finalize_prep``: window assignment, packing, the score
dispatch and the settle predispatch)."""


def read(ctx):
    run = ctx.run
    return 1e3 * run.spans.seconds["pack"] / run.rounds if run.rounds else None
