"""The arithmetic the per-layer readers share. A reader is
`benchmarks/layer_metrics/<metric name>.py` with one function `read(ctx)`;
it returns None where the run holds nothing for it to read (no trace, no
chip, another kind of cell), and the harness then leaves the metric out."""

from __future__ import annotations

from benchmarks.harness.stats import percentile


def _window_spans(ctx, name):
    t0, t1 = ctx.record["window"]
    return [(a, b) for n, a, b in ctx.record.get("spans", ())
            if n == name and a >= t0 and b <= t1]


def span_share_of_window(ctx, name):
    """Time inside host spans `name` over the window, in percent."""
    spans = _window_spans(ctx, name)
    if not spans:
        return None
    return 100.0 * sum(b - a for a, b in spans) / ctx.record["window_s"]


def span_p50_ms(ctx, name):
    spans = _window_spans(ctx, name)
    if not spans:
        return None
    return 1e3 * percentile([b - a for a, b in spans], 0.5)


def _device_trace(ctx):
    ts = ctx.trace_summary
    if ctx.device["platform"] != "tpu" or not ts or not ts.get("devices"):
        return None
    return ts


def device_idle_share(ctx):
    ts = _device_trace(ctx)
    return None if ts is None else 100.0 * (1 - ts["busy_s"] / ts["window_s"])


def custom_call_share_of_busy(ctx):
    ts = _device_trace(ctx)
    if ts is None or not ts["custom_call_s"]:
        return None
    return 100.0 * ts["custom_call_s"] / ts["busy_s"]


def collective_exposed_share(ctx):
    ts = _device_trace(ctx)
    if ts is None or ts["devices"] < 2:
        return None
    return 100.0 * ts["collective_exposed_s"] / ts["window_s"]


def peak_hbm_gb(ctx):
    if ctx.device["platform"] != "tpu" or ctx.memory_peak is None:
        return None
    return ctx.memory_peak / 1e9


def train_mfu(ctx):
    """The benchmark's own FLOPs per record (forward + backward, nothing
    recomputed) times records per second, over chips times the bf16 peak."""
    if ctx.peaks is None or "train_throughput" not in ctx.record["end_to_end"]:
        return None
    flops = ctx.counts().train_flops_per_record(ctx.config, ctx.traffic)
    rate = ctx.record["end_to_end"]["train_throughput"]
    return 100.0 * flops * rate / (
        len(ctx.devices) * ctx.peaks["bf16_flops_per_s"])


def attention_kernel_roofline(ctx):
    """Least time the chip could take for the step's attention kernels
    (the larger of operations over peak FLOP/s and bytes over peak bytes/s,
    from shapes) over the `tpu_custom_call` time of the train step's whole
    executions in the traced window."""
    ts = _device_trace(ctx)
    counts = ctx.counts()
    if (ts is None or ctx.peaks is None or "main_module" not in ts
            or not hasattr(counts, "attention_kernel_flops_per_step")
            or not ts["main_module"]["custom_call_s"]):
        return None
    flops = counts.attention_kernel_flops_per_step(ctx.config, ctx.traffic)
    byts = counts.attention_kernel_bytes_per_step(ctx.config, ctx.traffic)
    t_flops = flops / ctx.peaks["bf16_flops_per_s"]
    t_bytes = byts / ctx.peaks["hbm_bytes_per_s"]
    main = ts["main_module"]
    ctx.out(f"flash_attn_roofline: per step {flops:.4g} FLOP -> "
            f"{t_flops * 1e3:.3f} ms, {byts:.4g} B -> {t_bytes * 1e3:.3f} "
            f"ms; the {'compute' if t_flops >= t_bytes else 'memory'} roof "
            f"binds; {main['runs']:.0f} whole steps traced, kernel time "
            f"{main['custom_call_s'] / main['runs'] * 1e3:.3f} ms a step")
    return 100.0 * max(t_flops, t_bytes) * main["runs"] / main["custom_call_s"]


def counter(ctx, name):
    return ctx.record.get("counters", {}).get(name)
