"""Output tokens of the window over the real window: first submit to the
last token seen."""


def read(ctx):
    return ctx.window.tokens_per_s() or None
