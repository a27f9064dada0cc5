"""Host ms a round in the repartition coordinator's ticks: the program's
``jasda.repartition`` spans in the trace, over the rounds of the window.
Nothing where the trace holds no such span (a program without it)."""
import programspans


def read(ctx):
    run = ctx.run
    spans = programspans.for_run(run)
    if not spans or not any(s.name == programspans.PREFIX + "repartition"
                            for s in spans):
        return None
    return programspans.ms_per_round(spans, "repartition", run.rounds)
