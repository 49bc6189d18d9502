"""Time per output token after the first, 90th percentile over all the requests due in the window."""

from benchmarks.harness.readers import counter


def read(ctx):
    return counter(ctx, "serve_tpot_p90_ms")
