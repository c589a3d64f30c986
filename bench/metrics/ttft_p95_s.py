"""95th percentile of first-token time minus due time over every request
due in the window; a request that never got a token counts as infinite."""
from stats import percentile


def read(ctx):
    got, missing = ctx["log"].ttfts(ctx["t0"], ctx["t1"])
    return percentile(got + [float("inf")] * missing, 95)
