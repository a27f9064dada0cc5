"""Device ms a round of the ``jasda_score`` kernel: the sum of its events'
durations in the profiler trace over the rounds of the window."""
import roofline


def read(ctx):
    if ctx.trace is None or not ctx.run.rounds:
        return None
    events = [e for e in ctx.trace.events if roofline.is_score_kernel(e)]
    if not events:
        return None
    return 1e3 * sum(e.dur_ns for e in events) / 1e9 / ctx.run.rounds
