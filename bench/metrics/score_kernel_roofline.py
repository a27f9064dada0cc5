"""Share of its roofline that the ``jasda_score`` kernel reached, in %.

For each kernel event of the trace: the least time of the call on the
chip's peaks (``roofline.score_cost`` on the call's own operand shapes, as
the trace's HLO text gives them: job features (M, Fj), system features
(M, Fs), memory profile (M, T)), summed and divided by the events' summed
device time.  Nothing is read where no event gives its shapes.
"""
import roofline


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    events = [e for e in ctx.trace.events if roofline.is_score_kernel(e)]
    least = 0.0
    for ev in events:
        shapes = roofline.custom_call_shapes(ev.name)
        if len(shapes) < 6:
            return None
        (m, fj), (_, fs), (_, t) = shapes[0], shapes[1], shapes[4]
        ops, nbytes = roofline.score_cost(m, fj, fs, t)
        least += roofline.least_seconds(ops, nbytes, ctx.peaks)[0]
    busy = sum(ev.dur_ns for ev in events) / 1e9
    return 100.0 * least / busy if busy > 0 else None
