"""Share of the window inside the engine's `prefill` spans that waited for the prefill program (fenced while traced)."""

from benchmarks.harness import span_tree


def read(ctx):
    return span_tree.fenced_prefill_share(
        span_tree.program_spans("serving"), ctx.record["window"])
