"""Model flops of the traced part of the window (prompt tokens computed,
output tokens and images projected: the family's ``prefill_flops``,
``decode_flops`` and ``encode_flops``) over the traced window times the
chip's bf16 peak."""


def read(ctx):
    w = ctx.work
    f = w.prefill_flops(ctx.chunks) + w.decode_flops(ctx.decode_lengths)
    if ctx.image_tokens:
        f += w.encode_flops(int(ctx.counts.get("encode_tokens_total", 0)))
    if not f:
        return None
    return 100.0 * f / (ctx.window_s * ctx.peaks["bf16_flops_per_s"])
