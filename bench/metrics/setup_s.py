"""Wall seconds from process start to the first measured round."""


def read(ctx):
    return ctx.run.setup_s
