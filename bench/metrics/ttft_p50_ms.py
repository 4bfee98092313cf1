"""Median time to first token over every request of the window: host
time the first token is seen less its burst's submit time (nearest
rank; a request that never completes reads +inf)."""


def read(ctx):
    return ctx.window.ttft_p(50) * 1e3
