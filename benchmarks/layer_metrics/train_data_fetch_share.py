"""Share of the window the training loop spent fetching the next batch (the program's data_fetch stopwatch span)."""

from benchmarks.harness.readers import span_share_of_window


def read(ctx):
    return span_share_of_window(ctx, "data_fetch")
