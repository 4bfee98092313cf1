"""Device time of the P->D page copy and slot insert programs per
request admitted to decode in the traced part of the window (the
registry's sched_admissions_total). The prefix cache's copy-on-write
runs under the same program name as the P->D copy, so it counts too."""

PROGRAMS = ("jit_copy_fn", "jit_insert_fn")


def read(ctx):
    n = ctx.counts.get("sched_admissions_total", 0)
    t = ctx.program_s(*PROGRAMS)
    if not n or not t:
        return None
    return 1e3 * t / n
