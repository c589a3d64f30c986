"""99.2nd percentile of every gap between consecutive output tokens of a
request, over all requests, for gaps ending in the window (ms): a stream's
worst stall. The rank is the highest at or above the 98th that lies on the
plateau of gaps stretched by the mix's longest admission prefill, at least
half a point from either edge of it, in every run measured (PERF.md §2)."""
from stats import percentile


def read(ctx):
    return 1e3 * percentile(ctx["log"].gaps_in(ctx["t0"], ctx["t1"]), 99.2)
