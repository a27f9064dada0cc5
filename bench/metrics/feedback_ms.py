"""Host ms a round building and broadcasting the round's feedback to the
bidders: the program's ``jasda.settle.feedback`` spans, over the rounds of
the window."""
import programspans


def read(ctx):
    run = ctx.run
    return programspans.ms_per_round(programspans.for_run(run),
                                     "settle.feedback", run.rounds)
