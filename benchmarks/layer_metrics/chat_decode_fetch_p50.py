"""Median `fetch` span (the host's wait for the decode program's sampled tokens) over the window's rounds."""

from benchmarks.harness.readers import span_p50_ms


def read(ctx):
    return span_p50_ms(ctx, "fetch")
