"""Cache rows the decode step's read gathers over the rows its queries may see, summed over the layers, mean over the window's recorded decode steps (the engine's decode_step span: args.attended_rows over window_rows a sliding layer plus full_rows a full one, by counts/<family>.layer_plan): 1 is a read with no waste."""

from benchmarks.harness import span_tree

ARGS = ("attended_rows", "window_rows", "full_rows")


def read(ctx):
    counts = ctx.counts()
    if not hasattr(counts, "layer_plan"):
        return None
    plan = counts.layer_plan(ctx.config)
    sliding = sum(kind == "sliding_attention" for kind, _ in plan)
    ratios = []
    for s in span_tree.in_window(span_tree.program_spans("serving"),
                                 ctx.record["window"]):
        a = s["args"]
        if s["name"] != "decode_step" or not all(k in a for k in ARGS):
            continue
        visible = (sliding * a["window_rows"]
                   + (len(plan) - sliding) * a["full_rows"])
        if visible:
            ratios.append(a["attended_rows"] / visible)
    if not ratios:
        return None     # a program that does not say what a step read
    return sum(ratios) / len(ratios)
