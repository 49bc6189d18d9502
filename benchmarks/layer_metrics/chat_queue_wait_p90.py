"""Due to seated: 90th percentile over requests due in the window."""

from benchmarks.harness.readers import counter


def read(ctx):
    return counter(ctx, "queue_wait_p90_ms")
