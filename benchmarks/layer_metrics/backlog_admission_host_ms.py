"""The host's milliseconds an admission with the device idle under them: the window's `admit` spans less each fenced prefill's wait, over the requests seated."""

from benchmarks.harness import admission, span_tree


def read(ctx):
    return admission.host_ms(ctx, span_tree.program_spans())
