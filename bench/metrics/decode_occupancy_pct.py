"""Active decode slots over the decode engine's slots, averaged over the
decode iterations of the window (counted in the serving loop's
on_step)."""


def read(ctx):
    return None if ctx.occupancy is None else 100.0 * ctx.occupancy
