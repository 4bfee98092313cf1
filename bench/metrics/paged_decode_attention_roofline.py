"""Share of its roofline that the paged_decode_attention kernel reaches:
the least time the chip needs for the kernel's work in the traced part
of the window (the family's ``paged_decode``: every step at the slots'
true lengths, summed over the model's attention layers) over the
kernel's device time: the Pallas custom calls of the decode program,
one per attention layer."""

PROGRAM = "jit_decode_fn"
KIND = "custom-call:tpu_custom_call"


def read(ctx):
    t = ctx.op_s(PROGRAM, KIND)
    flops, nbytes = ctx.work.paged_decode(ctx.decode_lengths)
    if not t or not flops:
        return None
    p = ctx.peaks
    least, _ = ctx.work.roofline_s(flops, nbytes, p["bf16_flops_per_s"],
                                   p["hbm_bytes_per_s"])
    return 100.0 * least / t
