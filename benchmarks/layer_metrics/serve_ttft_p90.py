"""Due to first token, 90th percentile over all the requests due in the window."""

from benchmarks.harness.readers import counter


def read(ctx):
    return counter(ctx, "serve_ttft_p90_ms")
