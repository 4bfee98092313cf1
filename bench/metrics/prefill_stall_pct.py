"""Share of the prefill jobs' scheduling chances in the traced part that
stalled: 100 x stalls / (stalls + chunks run), where the serving loop's
scheduler counts a stall (``sched_stalls_total``, by reason) each time a
job it holds could not run its next chunk or be admitted, and a chunk
(``sched_chunks_total``) each time one ran. The program makes the stall
counter at the first stall, so chunks run with no stall counter read 0."""


def read(ctx):
    counts = ctx.counts or {}
    if not {"sched_stalls_total", "sched_chunks_total"} & set(counts):
        return None
    stalls = counts.get("sched_stalls_total", 0.0)
    total = stalls + counts.get("sched_chunks_total", 0.0)
    return 100.0 * stalls / total if total else None
