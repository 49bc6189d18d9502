"""Roofline share of the decode step of a model whose layers run several times over one set of weights: the least time its HBM traffic can take (the layers' weights once a PASS, the head once, the cache rows of the positions the seated slots hold in every row set: counts/<family>.decode_bytes_per_step over the peak bytes/s) over the decode program's device time a run. It cannot read over 100% by construction: every byte counted is one the step cannot avoid (weights that do not fit the chip's fast memory between passes, rows that are held and visible, each once), the positions are those the slots HOLD and not what the read rounds up to, and the time is the whole program's, sampler included."""

from benchmarks.harness import span_tree
from benchmarks.harness.readers import _device_trace

ARGS = ("ut_steps", "cache_entries", "cached_tokens", "active")


def read(ctx):
    ts, counts = _device_trace(ctx), ctx.counts()
    main = (ts or {}).get("main_module")
    if (main is None or ctx.peaks is None or not main["runs"]
            or "decode_step" not in main["name"]
            or not hasattr(counts, "weight_bytes_per_step")):
        return None     # no trace, or the window's main program is another
    tw = ctx.trace_window       # the steps the device trace holds
    steps = [s["args"] for s in span_tree.in_window(
        span_tree.program_spans("serving"), (tw.begin_host, tw.end_host))
        if s["name"] == "decode_step" and all(a in s["args"] for a in ARGS)]
    if not steps:
        return None     # a program that does not say it runs a loop
    n = len(steps)
    cached = sum(a["cached_tokens"] for a in steps) / n
    active = sum(a["active"] for a in steps) / n
    # the count is linear in the positions held, so the steps' mean goes in
    byts = counts.decode_bytes_per_step(ctx.config, cached, active)
    least = byts / ctx.peaks["hbm_bytes_per_s"]
    took = main["time_s"] / main["runs"]
    said = sum(a.get("weight_bytes_streamed", 0) for a in steps) / n
    ctx.out(f"loop_decode_roofline: {n} recorded steps, mean {byts:.4g} B a "
            f"step ({counts.weight_bytes_per_step(ctx.config):.4g} B of "
            f"weights; the program says {said:.4g}) -> {least * 1e3:.3f} ms "
            f"at the peak; {main['name']} ran {main['runs']:.0f} times whole "
            f"in the trace, {took * 1e3:.3f} ms a run; cached positions "
            f"(mean) {cached:.0f} over {active:.1f} slots, "
            f"{steps[0]['ut_steps']} passes, {steps[0]['cache_entries']} "
            "row sets")
    return 100.0 * least / took
