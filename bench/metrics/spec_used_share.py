"""Share of the window's rounds whose preparation came from the round
pipeline's speculation (``IterationLog.prep`` other than ``"serial"``),
in %."""


def read(ctx):
    rows = ctx.run.round_log
    if not rows or not hasattr(rows[0], "prep"):
        return None
    return 100.0 * sum(r.prep != "serial" for r in rows) / len(rows)
