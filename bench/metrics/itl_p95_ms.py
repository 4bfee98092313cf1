"""95th percentile of every gap between consecutive output tokens of
every request of the window, on the host clock (nearest rank)."""


def read(ctx):
    gap = ctx.window.itl_p(95)
    return None if gap is None else gap * 1e3
