"""Share of the window the training loop spent placing the batch on the device (the program's h2d_place stopwatch span, inside dispatch)."""

from benchmarks.harness import span_tree
from benchmarks.harness.readers import span_share_of_window


def read(ctx):
    share = span_share_of_window(ctx, "h2d_place")
    if share is not None:
        # what `dispatch` is made of: its self time is the step's call
        span_tree.report(span_tree.program_spans(), ctx.record["window"],
                         ctx.out)
    return share
