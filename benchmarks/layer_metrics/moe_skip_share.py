"""Share of the rows a decode step's routers placed that they sent to NO expert (the router's last output: the row skips the layer's experts), over the window's recorded decode steps and expert layers (the engine's decode_step span, args.skipped_rows over args.routed_rows)."""

from benchmarks.harness import span_tree


def read(ctx):
    steps = [s["args"] for s in span_tree.in_window(
        span_tree.program_spans("serving"), ctx.record["window"])
        if s["name"] == "decode_step" and "skipped_rows" in s["args"]
        and s["args"].get("routed_rows")]
    if not steps:
        return None     # a program whose router has no such output
    return 100.0 * sum(sum(a["skipped_rows"]) for a in steps) \
        / sum(a["routed_rows"] for a in steps)
