"""Roofline share of the decode step of an expert model: the least time its HBM reads can take (weights outside the experts, the experts that got a token, the head, the live cache rows, each once: counts/<family>.decode_bytes_per_step over the peak bytes/s) over the decode program's device time a run."""

from benchmarks.harness import span_tree
from benchmarks.harness.readers import _device_trace


def read(ctx):
    ts, counts = _device_trace(ctx), ctx.counts()
    main = (ts or {}).get("main_module")
    if (main is None or ctx.peaks is None or not main["runs"]
            or "decode_step" not in main["name"]
            or not hasattr(counts, "decode_bytes_per_step")):
        return None     # no trace, or the window's main program is another
    tw = ctx.trace_window       # the steps the device trace holds
    steps = [s["args"] for s in span_tree.in_window(
        span_tree.program_spans("serving"), (tw.begin_host, tw.end_host))
        if s["name"] == "decode_step" and "experts_touched" in s["args"]
        and "cached_tokens" in s["args"]]
    if not steps:
        return None     # a program that does not say what a step touched
    n = len(steps)
    touched = sum(sum(a["experts_touched"]) for a in steps) / n
    cached = sum(a["cached_tokens"] for a in steps) / n
    # the count is linear in both, so the steps' mean goes in
    byts = counts.decode_bytes_per_step(ctx.config, touched, cached)
    least = byts / ctx.peaks["hbm_bytes_per_s"]
    took = main["time_s"] / main["runs"]
    ctx.out(f"moe_decode_roofline: {n} recorded steps, mean {byts:.4g} B a "
            f"step -> {least * 1e3:.3f} ms at the peak; {main['name']} ran "
            f"{main['runs']:.0f} times whole in the trace, "
            f"{took * 1e3:.3f} ms a run; experts touched a layer (mean) "
            f"{touched / len(steps[0]['experts_touched']):.1f}, cached "
            f"tokens (mean) {cached:.0f}")
    return 100.0 * least / took
