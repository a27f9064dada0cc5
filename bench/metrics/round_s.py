"""Wall seconds per round: the window's length over the rounds it completed."""


def read(ctx):
    run = ctx.run
    return run.window_s / run.rounds if run.rounds else None
