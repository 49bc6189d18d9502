"""Roofline share of the decode step of an expert model whose attention keeps a per-slot state beside its paged rows and whose router sends a row to one expert or to none: the least time its HBM reads can take (weights outside the experts, the experts that got a row, the tied head once, the cache rows of the positions the seated slots hold and their state, each once: counts/<family>.decode_bytes_per_step over the peak bytes/s) over the decode program's device time a run."""

from benchmarks.harness import span_tree
from benchmarks.harness.readers import _device_trace

ARGS = ("experts_touched", "skipped_rows", "cached_tokens", "active")


def read(ctx):
    ts, counts = _device_trace(ctx), ctx.counts()
    main = (ts or {}).get("main_module")
    if (main is None or ctx.peaks is None or not main["runs"]
            or "decode_step" not in main["name"]
            or not hasattr(counts, "slot_state_bytes")):
        return None     # no trace, or the window's main program is another
    tw = ctx.trace_window       # the steps the device trace holds
    steps = [s["args"] for s in span_tree.in_window(
        span_tree.program_spans("serving"), (tw.begin_host, tw.end_host))
        if s["name"] == "decode_step" and all(a in s["args"] for a in ARGS)]
    if not steps:
        return None     # a program that does not say what a step touched
    n = len(steps)
    touched = sum(sum(a["experts_touched"]) for a in steps) / n
    cached = sum(a["cached_tokens"] for a in steps) / n
    slots = sum(a["active"] for a in steps) / n
    # the count is linear in all three, so the steps' means go in
    byts = counts.decode_bytes_per_step(ctx.config, touched, cached, slots)
    least = byts / ctx.peaks["hbm_bytes_per_s"]
    took = main["time_s"] / main["runs"]
    ctx.out(f"cca_moe_decode_roofline: {n} recorded steps, mean {byts:.4g} B "
            f"a step -> {least * 1e3:.3f} ms at the peak; {main['name']} ran "
            f"{main['runs']:.0f} times whole in the trace, "
            f"{took * 1e3:.3f} ms a run; experts touched a layer (mean) "
            f"{touched / len(steps[0]['experts_touched']):.1f}, cached "
            f"positions (mean) {cached:.0f} over {slots:.1f} slots")
    return 100.0 * least / took
