"""Share of pending work that no live slice holds θ-safely, in %: the mean
over the repartition ticks of the window of the coordinator's stranded
share (``frag_trace``).  Nothing where the run has no coordinator."""
import repartition_window


def read(ctx):
    ticks = repartition_window.in_window(ctx.run, "frag_trace")
    if not ticks:
        return None
    return 100.0 * sum(f for _, f in ticks) / len(ticks)
