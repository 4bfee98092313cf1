"""Device time of the decode program per decode step in the traced part
of the window."""

PROGRAM = "jit_decode_fn"


def read(ctx):
    n, t = ctx.counts["bench.decode_steps"], ctx.program_s(PROGRAM)
    if not n or not t:
        return None
    return 1e3 * t / n
