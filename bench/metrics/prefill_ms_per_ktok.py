"""Device time of the prefill program (chunked suffix prefill) per
thousand prompt tokens it computed in the traced part of the window
(the registry's prefill_tokens_computed)."""

PROGRAM = "jit_prefill_suffix_fn"


def read(ctx):
    n = ctx.counts.get("prefill_tokens_computed", 0)
    t = ctx.program_s(PROGRAM)
    if not n or not t:
        return None
    return 1e3 * t / (n / 1e3)
