"""Roofline share of the decode step of a model whose layers keep a recurrent state a slot: the least time its HBM traffic can take (every weight once, the seated slots' state read AND written, the cache rows of the positions they hold in the attention layers: counts/<family>.decode_bytes_per_step over the peak bytes/s) over the decode program's device time a run."""

from benchmarks.harness import span_tree
from benchmarks.harness.readers import _device_trace

ARGS = ("state_bytes", "cached_tokens", "active")


def read(ctx):
    ts, counts = _device_trace(ctx), ctx.counts()
    main = (ts or {}).get("main_module")
    if (main is None or ctx.peaks is None or not main["runs"]
            or "decode_step" not in main["name"]
            or not hasattr(counts, "prefill_flops")):
        return None     # no trace, or the window's main program is another
    tw = ctx.trace_window       # the steps the device trace holds
    steps = [s["args"] for s in span_tree.in_window(
        span_tree.program_spans("serving"), (tw.begin_host, tw.end_host))
        if s["name"] == "decode_step" and all(a in s["args"] for a in ARGS)]
    if not steps:
        return None     # a program that does not say what state it read
    n = len(steps)
    cached = sum(a["cached_tokens"] for a in steps) / n
    active = sum(a["active"] for a in steps) / n
    # the count is linear in both, so the steps' means go in
    byts = counts.decode_bytes_per_step(ctx.config, cached, active)
    least = byts / ctx.peaks["hbm_bytes_per_s"]
    took = main["time_s"] / main["runs"]
    ctx.out(f"ssm_decode_roofline: {n} recorded steps, mean {byts:.4g} B a "
            f"step -> {least * 1e3:.3f} ms at the peak; {main['name']} ran "
            f"{main['runs']:.0f} times whole in the trace, "
            f"{took * 1e3:.3f} ms a run; state read a step (mean, as the "
            f"program says) {sum(a['state_bytes'] for a in steps) / n:.4g} "
            f"B, cached positions (mean) {cached:.0f} over {active:.1f} "
            "slots")
    return 100.0 * least / took
