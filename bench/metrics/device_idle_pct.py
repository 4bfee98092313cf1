"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals) / (traced window)."""


def read(ctx):
    if not ctx.window_s or not ctx.trace.busy_s:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.window_s)
