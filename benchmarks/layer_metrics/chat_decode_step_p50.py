"""Median decode_step host span (dispatch to sampled-token fetch) in the window."""

from benchmarks.harness.readers import span_p50_ms


def read(ctx):
    return span_p50_ms(ctx, "decode_step")
