"""The decode step's share of the chip's bf16 peak: model flops of the
decode steps in the traced part of the window (work.decode_flops, at
the slots' true lengths) over the decode program's device time."""

from bench import work

PROGRAM = "jit_decode_fn"


def read(ctx):
    f = work.decode_flops(ctx.dims, ctx.decode_lengths)
    t = ctx.program_s(PROGRAM)
    if not f or not t:
        return None
    return 100.0 * f / (t * ctx.peaks["bf16_flops_per_s"])
