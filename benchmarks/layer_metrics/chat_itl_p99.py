"""99th percentile gap between consecutive tokens of a request, over the tokens stamped in the window (`round.args.emitted`)."""

from benchmarks.harness import span_tree


def read(ctx):
    return span_tree.itl_p99_ms(ctx, span_tree.program_spans("serving"))
