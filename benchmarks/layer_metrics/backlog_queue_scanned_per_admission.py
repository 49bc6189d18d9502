"""Queue entries the scheduler walked (the expiry's rebuild of the queue and every pop's scan: the `admit` span's `scanned`) for each request it seated (`admitted`)."""

from benchmarks.harness import admission, span_tree


def read(ctx):
    return admission.scanned_per_admission(
        span_tree.program_spans(), ctx.record["window"])
