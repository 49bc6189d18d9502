"""Share of the window the host spent scheduling: `submit` and the self times of the round's tree, device waits left out."""

from benchmarks.harness import span_tree


def read(ctx):
    return span_tree.host_share(ctx, span_tree.program_spans("serving"))
