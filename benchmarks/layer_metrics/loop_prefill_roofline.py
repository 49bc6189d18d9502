"""Roofline share of the prefill programs of a model whose layers run several times over one set of weights: over the window's FENCED `prefill` spans (a traced run: each waits for its program), the least time a prefill of each span's bucket can take, the LARGER of its FLOPs over the peak FLOP/s and its bytes over the peak bytes/s (counts/<family>.prefill_flops, prefill_bytes: at a few dozen positions the weights' bytes, streamed once a pass, are the roof), over the spans' time less what each spent launching (`launched_s`). It cannot read over 100% by construction: a program has to respect both roofs, so the larger of the two least times is under its own, and the spans' time holds the whole program."""

from benchmarks.harness import span_tree


def read(ctx):
    counts = ctx.counts()
    if ctx.peaks is None or not hasattr(counts, "prefill_bytes"):
        return None
    spans = [s for s in span_tree.in_window(
        span_tree.program_spans("serving"), ctx.record["window"])
        if s["name"] == "prefill" and s["args"].get("fenced")
        and "ut_steps" in s["args"] and "launched_s" in s["args"]]
    if not spans:
        return None     # an untraced run, or a program without a loop

    def least(bucket):
        return max(
            counts.prefill_flops(ctx.config, bucket)
            / ctx.peaks["bf16_flops_per_s"],
            counts.prefill_bytes(ctx.config, bucket)
            / ctx.peaks["hbm_bytes_per_s"])

    floor = sum(least(s["args"]["bucket"]) for s in spans)
    waited = sum(s["t1"] - s["t0"] - s["args"]["launched_s"] for s in spans)
    by_bucket: dict = {}
    for s in spans:
        by_bucket.setdefault(s["args"]["bucket"], []).append(
            s["t1"] - s["t0"] - s["args"]["launched_s"])
    ctx.out(f"loop_prefill_roofline: {len(spans)} fenced prefills in the "
            f"window, {floor:.3f} s at the roofs in {waited:.3f} s; by "
            "bucket, mean ms (n) and the roof that binds: " + ", ".join(
                f"{b}: {1e3 * sum(v) / len(v):.1f} ({len(v)}) "
                + ("bytes" if counts.prefill_bytes(ctx.config, b)
                   / ctx.peaks["hbm_bytes_per_s"]
                   >= counts.prefill_flops(ctx.config, b)
                   / ctx.peaks["bf16_flops_per_s"] else "flops")
                for b, v in sorted(by_bucket.items())))
    return 100.0 * floor / waited
