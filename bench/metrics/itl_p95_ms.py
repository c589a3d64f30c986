"""95th percentile of every gap between consecutive output tokens of a
request, over all requests, for gaps ending in the window (ms)."""
from stats import percentile


def read(ctx):
    return 1e3 * percentile(ctx["log"].gaps_in(ctx["t0"], ctx["t1"]), 95)
