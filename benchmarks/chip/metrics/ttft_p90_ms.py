"""ttft_p90_ms: 90th percentile of admission to first visible token on
the wall clock, over the requests completed in the window.  The engine
prefills one request per iteration, so a first token takes one or two
iterations and the 90th percentile reads one or the other by seed."""


def read(ctx):
    return ctx.e2e["ttft_p90_ms"]
