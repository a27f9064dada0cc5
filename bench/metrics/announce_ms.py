"""Host ms a round in window announcement: the program's
``jasda.announce`` spans in the trace, over the rounds of the window."""
import programspans


def read(ctx):
    run = ctx.run
    return programspans.ms_per_round(programspans.for_run(run), "announce",
                                     run.rounds)
