"""Host ms a round blocked on in-flight device scores: the program's
``jasda.score.wait`` spans, over the rounds of the window (rounds scored on
the host have none)."""
import programspans


def read(ctx):
    run = ctx.run
    return programspans.ms_per_round(programspans.for_run(run), "score.wait",
                                     run.rounds)
