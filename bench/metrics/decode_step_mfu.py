"""The decode step's share of the chip's bf16 peak: model flops of the
decode steps in the traced part of the window (the family's
``decode_flops``, at the slots' true lengths) over the decode program's
device time."""

PROGRAM = "jit_decode_fn"


def read(ctx):
    f = ctx.work.decode_flops(ctx.decode_lengths)
    t = ctx.program_s(PROGRAM)
    if not f or not t:
        return None
    return 100.0 * f / (t * ctx.peaks["bf16_flops_per_s"])
