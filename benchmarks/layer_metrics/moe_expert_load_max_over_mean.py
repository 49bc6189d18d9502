"""Tokens of the busiest expert over the mean expert's, mean over the window's recorded decode steps and expert layers (the engine's decode_step span, args.expert_load_max_over_mean): 1 is an even spread."""

from benchmarks.harness import span_tree


def read(ctx):
    loads = [v for s in span_tree.in_window(
        span_tree.program_spans("serving"), ctx.record["window"])
        if s["name"] == "decode_step"
        for v in s["args"].get("expert_load_max_over_mean", ())]
    if not loads:
        return None
    return sum(loads) / len(loads)
