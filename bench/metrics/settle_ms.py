"""Host ms a round in settle, commit and feedback
(``JasdaScheduler._settle_round``, the block on the scores included)."""


def read(ctx):
    run = ctx.run
    return (1e3 * run.spans.seconds["settle"] / run.rounds if run.rounds
            else None)
