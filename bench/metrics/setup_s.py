"""Process start to the window's start: weights, build, warm-up and
compilation."""


def read(ctx):
    return ctx.setup_s
