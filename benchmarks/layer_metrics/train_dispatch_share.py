"""Share of the window the training loop spent dispatching the step (the program's dispatch stopwatch span)."""

from benchmarks.harness.readers import span_share_of_window


def read(ctx):
    return span_share_of_window(ctx, "dispatch")
