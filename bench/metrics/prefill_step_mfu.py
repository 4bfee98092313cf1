"""The prefill step's share of the chip's bf16 peak: model flops of the
prefill chunks in the traced part of the window (the family's
``prefill_flops``) over the prefill program's device time."""

PROGRAM = "jit_prefill_suffix_fn"


def read(ctx):
    f = ctx.work.prefill_flops(ctx.chunks)
    t = ctx.program_s(PROGRAM)
    if not f or not t:
        return None
    return 100.0 * f / (t * ctx.peaks["bf16_flops_per_s"])
