"""Repartition moves the coordinator executed in the window, per 100
rounds: the records of its layout log that follow a move.  Nothing where
the run has no coordinator or no layout log."""
import repartition_window


def read(ctx):
    run = ctx.run
    records = repartition_window.in_window(run, "layout_log")
    if records is None or not run.rounds:
        return None
    moves = sum(1 for r in records if r.move is not None)
    return 100.0 * moves / run.rounds
