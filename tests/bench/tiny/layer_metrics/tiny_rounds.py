"""A throw-away per-layer metric, added as a file: the scheduling rounds or
training steps the window held. tests/bench shows with it that a metric is
found by its name in BENCHMARK.json, with no registry to edit."""


def read(ctx):
    counters = ctx.record["counters"]
    return counters.get("rounds_in_window", counters.get("steps"))
