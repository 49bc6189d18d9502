"""What an admission is made of (PR 40): the arithmetic of the readers
`backlog_admission_host_ms`, `chat_admission_host_ms` and
`backlog_queue_scanned_per_admission`.

The engine's `admit` span (category "serving") carries `queued`, `scanned`
and `admitted`, and holds, beside its `prefill` children, leaves of category
"serving.admit": `queue_expire`, `queue_pop`, `seat_prepare` (which says the
blocks its allocation evicted, `evicted_blocks`), `seat_commit`.
A traced `prefill` waits for the device (`args.fenced`); `args.launched_s` is
its time up to the return of the launch, which an untraced admission pays
too. These readers take the spans of EVERY category
(`span_tree.program_spans()`); the readers of the round's tree keep
"serving". Every function returns None where the run holds nothing to read:
no tracer, or a program whose `admit` carries no `admitted` (a parent commit
from before PR 40)."""

from __future__ import annotations

from benchmarks.harness import span_tree
from benchmarks.harness.stats import percentile

# the children of `admit`, in the order an admission runs them; `prefill`
# is split into its launch and its wait
PARTS = ("queue_expire", "queue_pop", "seat_prepare", "prefill launch",
         "prefill wait", "seat_commit")


def window_admits(spans, window):
    """The window's `admit` spans that count what they did."""
    return [s for s in span_tree.in_window(spans, window)
            if s["name"] == "admit" and "admitted" in s["args"]]


def breakdown(spans, window):
    """Seconds inside the window's `admit` spans by part (`PARTS`, and
    "admit's own": what no child covers), their sum `total_s`, the same
    less the fenced prefills' waits `host_s`, each part's single readings
    (`each`: a part's mean beside its median tells a steady cost from a
    few stalls), and the counts `admitted`, `scanned`, `evicted`, `rounds`.
    None without a counted `admit` in the window."""
    admits = window_admits(spans, window)
    if not admits:
        return None
    kids = span_tree.children_inside(spans)
    each: dict = {name: [] for name in PARTS}
    evicted = 0
    for a in admits:
        for k in kids.get(a["id"], ()):
            dur = k["t1"] - k["t0"]
            evicted += k["args"].get("evicted_blocks", 0)
            if k["name"] != "prefill":
                each.setdefault(k["name"], []).append(dur)
                continue
            # unfenced, the span is the launch alone; fenced by a program
            # that does not say where the launch ended, all of it is wait
            launch = dur if not k["args"].get("fenced") else min(
                k["args"].get("launched_s", 0.0), dur)
            each["prefill launch"].append(launch)
            each["prefill wait"].append(dur - launch)
    parts = {name: sum(durs) for name, durs in each.items()}
    total = sum(a["t1"] - a["t0"] for a in admits)
    parts["admit's own"] = total - sum(parts.values())
    return {"parts": parts, "each": each, "total_s": total,
            "host_s": total - parts["prefill wait"],
            "admitted": sum(a["args"]["admitted"] for a in admits),
            "scanned": sum(a["args"].get("scanned", 0) for a in admits),
            "evicted": evicted, "rounds": len(admits)}


def host_ms(ctx, spans):
    """The host's milliseconds an admission with the device idle under
    them: the window's `admit` spans less each fenced prefill's wait, over
    the requests they seated; the expiry of rounds that seated nobody is
    in it. Prints the run's two `admission:` lines: the means that add up
    to the metric, then each part's median and longest reading."""
    b = breakdown(spans, ctx.record["window"])
    if b is None or not b["admitted"]:
        return None
    n = b["admitted"]
    ctx.out(f"admission: {n} admitted in {b['rounds']} rounds; ms an "
            "admission: " + ", ".join(
                f"{name} {1e3 * s / n:.3f}" for name, s in b["parts"].items())
            + f" = {1e3 * b['total_s'] / n:.3f} (host, all but the wait: "
            f"{1e3 * b['host_s'] / n:.3f}); ms a round: "
            f"{1e3 * b['total_s'] / b['rounds']:.3f}")
    ctx.out("admission: p50 ms (longest) of one: " + ", ".join(
        f"{name} {1e3 * percentile(durs, 0.5):.3f} ({1e3 * max(durs):.3f})"
        for name, durs in b["each"].items() if durs)
        + f"; evicted blocks an admission: {b['evicted'] / n:.2f}")
    return 1e3 * b["host_s"] / n


def scanned_per_admission(spans, window):
    """Queue entries the scheduler walked (the expiry's rebuild and every
    pop's scan) for each request it seated."""
    b = breakdown(spans, window)
    if b is None or not b["admitted"]:
        return None
    return b["scanned"] / b["admitted"]
