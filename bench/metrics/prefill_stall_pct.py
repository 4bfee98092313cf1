"""Share of the prefill jobs' scheduling chances in the traced part that
stalled: 100 x stalls / (stalls + chunks run), where the serving loop's
scheduler counts a stall (``sched_stalls_total``, by reason) each time a
job it holds could not run its next chunk or be admitted, and a chunk
(``sched_chunks_total``) each time one ran."""


def read(ctx):
    if ctx.counts is None or "sched_stalls_total" not in ctx.counts:
        return None
    stalls = ctx.counts["sched_stalls_total"]
    total = stalls + ctx.counts.get("sched_chunks_total", 0.0)
    return 100.0 * stalls / total if total else None
