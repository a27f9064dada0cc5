"""Host ms a round in the clearing policy (per-window WIS and the
cross-window conflict fixed point, with every replay): the program's
``jasda.settle.clear`` spans, over the rounds of the window."""
import programspans


def read(ctx):
    run = ctx.run
    return programspans.ms_per_round(programspans.for_run(run),
                                     "settle.clear", run.rounds)
