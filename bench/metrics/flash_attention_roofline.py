"""Share of its roofline that the flash_attention kernel reaches: the
least time the chip needs for the kernel's work in the traced part of
the window (the family's ``flash``: every chunk at its true lengths,
summed over the model's attention layers) over the kernel's device
time: the Pallas custom calls of the prefill program, one per attention
layer."""

PROGRAM = "jit_prefill_suffix_fn"
KIND = "custom-call:tpu_custom_call"


def read(ctx):
    t = ctx.op_s(PROGRAM, KIND)
    flops, nbytes = ctx.work.flash(ctx.chunks)
    if not t or not flops:
        return None
    p = ctx.peaks
    least, _ = ctx.work.roofline_s(flops, nbytes, p["bf16_flops_per_s"],
                                   p["hbm_bytes_per_s"])
    return 100.0 * least / t
