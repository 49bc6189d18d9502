"""The engine's submit to first token, 90th percentile over the first tokens stamped in the window (the `first_token` instant): needs no tail."""

from benchmarks.harness import span_tree


def read(ctx):
    return span_tree.first_token_p90_ms(ctx, span_tree.program_events())
