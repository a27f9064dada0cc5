"""Host ms a round in the score dispatch (operand packing, device put and
launch; the scoring itself where the pool is scored on the host): the
program's ``jasda.score.dispatch`` spans, over the rounds of the window."""
import programspans


def read(ctx):
    run = ctx.run
    return programspans.ms_per_round(programspans.for_run(run),
                                     "score.dispatch", run.rounds)
