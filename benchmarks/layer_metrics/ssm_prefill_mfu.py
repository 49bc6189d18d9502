"""Share of the chip's peak FLOP/s that the prefill programs of a model with a scanned state reach: over the window's FENCED `prefill` spans (a traced run: each waits for its program), the FLOPs a prefill of each span's bucket has to do (counts/<family>.prefill_flops) over the spans' time less what each spent launching (`launched_s`)."""

from benchmarks.harness import span_tree


def read(ctx):
    counts = ctx.counts()
    if ctx.peaks is None or not hasattr(counts, "prefill_flops"):
        return None
    spans = [s for s in span_tree.in_window(
        span_tree.program_spans("serving"), ctx.record["window"])
        if s["name"] == "prefill" and s["args"].get("fenced")
        and "scan_chunks" in s["args"] and "launched_s" in s["args"]]
    if not spans:
        return None     # an untraced run, or a program without a scan
    flops = sum(counts.prefill_flops(ctx.config, s["args"]["bucket"])
                for s in spans)
    waited = sum(s["t1"] - s["t0"] - s["args"]["launched_s"] for s in spans)
    by_bucket: dict = {}
    for s in spans:
        by_bucket.setdefault(s["args"]["bucket"], []).append(
            s["t1"] - s["t0"] - s["args"]["launched_s"])
    ctx.out(f"ssm_prefill_mfu: {len(spans)} fenced prefills in the window, "
            f"{flops:.4g} FLOP in {waited:.3f} s; mean ms by bucket: "
            + ", ".join(f"{b}: {1e3 * sum(v) / len(v):.1f} ({len(v)})"
                        for b, v in sorted(by_bucket.items())))
    return 100.0 * flops / waited / ctx.peaks["bf16_flops_per_s"]
