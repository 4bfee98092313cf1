"""Share of its roofline that the flash_attention kernel reaches: the
least time the chip needs for the kernel's work in the traced part of
the window (work.flash_chunk per layer and chunk, at the true lengths)
over the kernel's device time: the Pallas custom calls of the prefill
program, one per layer."""

from bench import work

PROGRAM = "jit_prefill_suffix_fn"
KIND = "custom-call:tpu_custom_call"


def read(ctx):
    t = ctx.op_s(PROGRAM, KIND)
    counts = [work.flash_chunk(ctx.dims, a, b) for a, b, _ in ctx.chunks]
    L = ctx.dims.n_layers
    flops = L * sum(f for f, _ in counts)
    nbytes = L * sum(b for _, b in counts)
    if not t or not flops:
        return None
    p = ctx.peaks
    least, _ = work.roofline_s(flops, nbytes, p["bf16_flops_per_s"],
                               p["hbm_bytes_per_s"])
    return 100.0 * least / t
