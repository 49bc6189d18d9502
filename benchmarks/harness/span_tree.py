"""The arithmetic the span-tree readers share: the program's host spans WITH
their `args` (`id`, `parent`, the counts a span carries), read from the
program's tracer, which outlives the driver's release of the job. The
driver's own `record["spans"]` keeps names and times only; a reader that
needs no more than those uses `readers.py`.

A span is a dict `name, cat, t0, t1, id, parent, args` (seconds, on the
clock of `ctx.record["window"]`). Every function returns None where the run
holds nothing to read: no tracer (`--trace 0`), or a program that records
no such span (a parent commit from before the span existed)."""

from __future__ import annotations

from benchmarks.harness.stats import percentile

# what the host does in a scheduling round that is not a wait for the device
# (`fetch` always waits; `prefill` waits while the tracer is on): `submit`
# and the SELF time of each level of the round's tree
HOST_SPANS = ("submit", "router_step", "round", "admit", "ensure_blocks",
              "upload", "dispatch", "emit")


def spans_of(events, cat=None):
    """Complete ("X") tracer events as span dicts."""
    out = []
    for e in events:
        if e.get("ph") != "X" or (cat and e.get("cat") != cat):
            continue
        args = e.get("args") or {}
        t0 = e["ts"] / 1e6
        out.append({"name": e["name"], "cat": e.get("cat"), "t0": t0,
                    "t1": t0 + e["dur"] / 1e6, "id": args.get("id"),
                    "parent": args.get("parent"), "args": args})
    return out


def program_events():
    from bigdl_tpu import obs

    return obs.get_tracer().events()


def program_spans(cat=None):
    return spans_of(program_events(), cat)


def in_window(spans, window):
    w0, w1 = window
    return [s for s in spans if s["t0"] >= w0 and s["t1"] <= w1]


def children_inside(spans):
    """{parent id: [children]}. A child counts only if it lies inside its
    parent: a span recorded from endpoints measured elsewhere (`queued`,
    `request[<status>]`) names as parent the span that was open when it
    ENDED, and began long before it."""
    by_id = {s["id"]: s for s in spans if s["id"] is not None}
    out: dict = {}
    for s in spans:
        p = by_id.get(s["parent"])
        if p is not None and s["t0"] >= p["t0"] and s["t1"] <= p["t1"]:
            out.setdefault(p["id"], []).append(s)
    return out


def self_times(spans):
    """{id: seconds} of each span less the children that lie inside it."""
    kids = children_inside(spans)
    return {s["id"]: s["t1"] - s["t0"] - sum(
        k["t1"] - k["t0"] for k in kids.get(s["id"], ()))
        for s in spans if s["id"] is not None}


def fenced_prefill_share(spans, window):
    """Percent of the window inside `prefill` spans that waited for the
    prefill program (`args.fenced`); an unfenced one times a dispatch."""
    fenced = [s for s in in_window(spans, window)
              if s["name"] == "prefill" and s["args"].get("fenced")]
    if not fenced:
        return None
    return 100.0 * sum(s["t1"] - s["t0"] for s in fenced) / (
        window[1] - window[0])


def host_share(ctx, spans):
    """`round_host_share` of the run, and the run's span tree printed
    beside it (`report`), as every traced serve run shows it."""
    share = round_host_share(spans, ctx.record["window"])
    if share is not None:
        report(spans, ctx.record["window"], ctx.out)
    return share


def round_host_share(spans, window):
    """Percent of the window the host spent scheduling: `HOST_SPANS`' self
    times. None without a `round` in the window."""
    inside = in_window(spans, window)
    if not any(s["name"] == "round" for s in inside):
        return None
    own = self_times(spans)
    return 100.0 * sum(own[s["id"]] for s in inside
                       if s["name"] in HOST_SPANS and s["id"] in own) / (
        window[1] - window[0])


def inter_token_gaps(spans, window):
    """Seconds between consecutive tokens of one request, over every token
    whose later stamp lies in the window. A token's stamp is the end of the
    `round` whose `args.emitted` lists its request (once per token)."""
    last, gaps = {}, []
    for s in sorted((s for s in spans if s["name"] == "round"),
                    key=lambda s: s["t1"]):
        for rid in s["args"].get("emitted", ()):
            if rid in last and window[0] <= s["t1"] <= window[1]:
                gaps.append(s["t1"] - last[rid])
            last[rid] = s["t1"]
    return gaps


def itl_p99_ms(ctx, spans):
    gaps = inter_token_gaps(spans, ctx.record["window"])
    if not gaps:
        return None
    ctx.out(f"inter-token gaps: {len(gaps)} in the window, median "
            f"{1e3 * percentile(gaps, 0.5)!r} ms, p99 "
            f"{1e3 * percentile(gaps, 0.99)!r} ms, longest "
            f"{1e3 * max(gaps)!r} ms")
    return 1e3 * percentile(gaps, 0.99)


def first_token_p90_ms(ctx, events):
    """The engine's submit to first token (`first_token.args.ttft_s`), over
    the first tokens stamped in the window: TTFT with no tail to wait for."""
    w0, w1 = ctx.record["window"]
    ttft = [e["args"]["ttft_s"] for e in events
            if e["name"] == "first_token" and w0 <= e["ts"] / 1e6 <= w1]
    if not ttft:
        return None
    ctx.out(f"first tokens: {len(ttft)} in the window, submit to first "
            f"token mean {1e3 * sum(ttft) / len(ttft)!r} ms, median "
            f"{1e3 * percentile(ttft, 0.5)!r} ms, p90 "
            f"{1e3 * percentile(ttft, 0.9)!r} ms")
    return 1e3 * percentile(ttft, 0.9)


def report(spans, window, out):
    """The window's span tree by name (count, median, share of the window,
    self share), how much of each parent its children account for, a round
    by the number it admitted, and what a round uploads. PERF.md's "what a
    round is made of" and "what dp4's dispatch is made of" are these lines."""
    w_s = window[1] - window[0]
    inside = in_window(spans, window)
    own = self_times(spans)
    by_name: dict = {}
    for s in inside:
        row = by_name.setdefault(s["name"], {"dur": [], "self": 0.0})
        row["dur"].append(s["t1"] - s["t0"])
        row["self"] += own.get(s["id"], s["t1"] - s["t0"])
    out(f"span tree: window {w_s:.3f} s; name n p50_ms total_% self_%: "
        + "; ".join(
            f"{name} {len(row['dur'])} {1e3 * percentile(row['dur'], 0.5):.3f}"
            f" {100 * sum(row['dur']) / w_s:.2f} {100 * row['self'] / w_s:.2f}"
            for name, row in sorted(by_name.items(),
                                    key=lambda kv: -sum(kv[1]["dur"]))))
    kids = children_inside(spans)
    covered: dict = {}
    for p in inside:
        if p["id"] in kids and p["t1"] > p["t0"]:
            covered.setdefault(p["name"], []).append(sum(
                k["t1"] - k["t0"] for k in kids[p["id"]]) / (p["t1"] - p["t0"]))
    out("span tree: children cover (median, least): " + "; ".join(
        f"{name} {100 * percentile(c, 0.5):.2f}% {100 * min(c):.2f}%"
        for name, c in sorted(covered.items())))
    rounds = [s for s in inside if s["name"] == "round"]
    if rounds:
        by_admitted: dict = {}
        for s in rounds:
            by_admitted.setdefault(len(s["args"].get("admitted", ())),
                                   []).append(s["t1"] - s["t0"])
        uploads = [s["args"]["bytes"] for s in inside
                   if s["name"] == "upload" and "bytes" in s["args"]]
        out("span tree: attn_impl "
            + "/".join(sorted({str(s["args"].get("attn_impl"))
                               for s in rounds}))
            + "; round p50_ms by admissions: " + ", ".join(
                f"{k}: {1e3 * percentile(v, 0.5):.1f} ({len(v)})"
                for k, v in sorted(by_admitted.items()))
            + f"; upload p50 {percentile(uploads, 0.5)} bytes")
