"""Active slots per decode step over slots, mean over the window's steps, in percent."""

from benchmarks.harness.readers import counter


def read(ctx):
    return counter(ctx, "batch_occupancy_pct")
