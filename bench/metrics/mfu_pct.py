"""Model flops of the traced part of the window (prompt tokens computed,
output tokens and images projected: work.prefill_flops, decode_flops
and encode_flops) over the traced window times the chip's bf16 peak."""

from bench import work


def read(ctx):
    d = ctx.dims
    f = (work.prefill_flops(d, ctx.chunks)
         + work.decode_flops(d, ctx.decode_lengths))
    if ctx.image_tokens:
        f += work.encode_flops(d, int(ctx.counts.get("encode_tokens_total",
                                                     0)))
    if not f:
        return None
    return 100.0 * f / (ctx.window_s * ctx.peaks["bf16_flops_per_s"])
