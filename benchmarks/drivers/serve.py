"""Driver of kind `serve`: one cell's model behind the program's own
`EngineRouter([InferenceEngine(...)])`, under traffic generated from the
cell's traffic file: an open loop at a fixed rate (`arrival:
stratified_exponential`) or a backlog queued before the window (`arrival:
backlog`).

Set-up: weights from the seed, engine and pools on the device, one warm-up
request per prefill bucket the traffic uses (which also compiles the one
decode shape), then the lead: traffic that runs before the window so that
the slots hold their steady mix of request ages when it opens. The window
is `--seconds` of scheduling rounds, closed at the end of the round in
which the time is up. In an open loop the arrivals and the rounds then go
on, under the same offered load, until EVERY request that was due in the
window has ended; `tail_s` is only the hard stop (the longest answer times
a round, with room), and what arrives after the window is offered and not
measured.

Counting (the same in every run):
  attempted   requests due in the window (open loop); requests that
              finished in the window or were in a slot at its end (backlog)
  failed      attempted requests that did not end `done`: ended in another
              state, or (open loop) had not ended at the hard stop. One
              such request makes the run not correct, so a change that
              starves, sheds or preempts requests cannot read as a gain.
  TTFT, TPOT  over ALL the requests due in the window, none left out by its
              outcome (the engine gives a request's times only when it
              finishes, hence the tail). TTFT is first token minus the time
              the request was due, not the time the loop got round to
              submitting it; TPOT is (latency - ttft) / (tokens - 1).
              Which of their means and 90th percentiles is an end-to-end
              metric is the manifest's choice (PERF.md section 2 has the
              spreads that decided it).
  throughput  output tokens of the window's rounds over the time from the
              window's opening to the end of its last round: all the work
              and all the time, a request that straddles an end counts only
              its tokens inside.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from benchmarks.harness import traffic as tg
from benchmarks.harness.stats import percentile


def _warmup(job, traffic, seed):
    """One short request per prefill bucket: compiles each bucket's prefill
    and the one decode step, and nothing the traffic does not use."""
    from bigdl_tpu.serving import Request

    rng = np.random.RandomState((seed + 1) % (2 ** 32))
    lo = 0
    for bucket in sorted(traffic["engine"]["prefill_buckets"]):
        n = min(bucket, traffic["prompt_len"]["max"])
        if n <= lo:
            continue                    # no prompt of the mix reaches it
        prompt = rng.randint(0, job.vocab, n).tolist()
        job.router.run([Request(prompt=prompt, max_new_tokens=2)])
        lo = bucket


def run(ctx) -> dict:
    import jax

    from bigdl_tpu import obs
    from bigdl_tpu.serving import Request

    traffic, seconds = ctx.traffic, ctx.seconds
    job = ctx.family.ServeJob(ctx.seed, ctx.config, traffic, ctx.devices)
    jax.block_until_ready(job.engine.pool)
    ctx.phases.mark("build_s")
    if ctx.trace:
        obs.set_tracer(obs.SpanTracer(enabled=True, capacity=1 << 20))
    _warmup(job, traffic, ctx.seed)
    ctx.phases.mark("compile_or_load_s")

    arrivals = tg.make_arrivals(traffic, ctx.seed, seconds, job.vocab)
    backlog = traffic["arrival"] == "backlog"
    router, engine, slots = job.router, job.engine, traffic["engine"]["slots"]
    by_id, results, submit_t = {}, {}, {}
    tracing = ctx.new_trace_window() if ctx.trace else None
    steps = []                          # (t_end, tokens, active) per round
    nxt = 0

    def submit(i, now):
        a = arrivals[i]
        rid = router.submit(Request(
            prompt=a.prompt, max_new_tokens=a.max_new_tokens,
            temperature=a.temperature, top_k=a.top_k, top_p=a.top_p,
            seed=a.seed))
        by_id[rid], submit_t[rid] = i, now

    def one_round():
        done = router.step()
        now = time.monotonic()
        for r in done:
            if r.id in by_id:
                results[by_id[r.id]] = (r, now)
        active = engine.slots_active
        # every slot that decoded this round emitted one token: those still
        # seated, and those that finished on their last token
        steps.append((now, active + sum(
            1 for r in done if r.status == "done"), active + len(done)))

    # ---- lead: before the window, counted as set-up
    if backlog:
        t = time.monotonic()
        for i in range(len(arrivals)):
            submit(i, t)
        nxt = len(arrivals)
        for _ in range(traffic["lead_rounds"]):
            one_round()
        t_open = time.monotonic()
    else:
        t_open = time.monotonic() + traffic["lead_s"]
        while True:
            now = time.monotonic()
            if now >= t_open:
                break
            while nxt < len(arrivals) and t_open + arrivals[nxt].due_s <= now:
                submit(nxt, now)
                nxt += 1
            if engine.idle:
                time.sleep(min(0.002, t_open - now))
            else:
                one_round()
    compiles_at_open = ctx.counters.snapshot()
    ctx.phases.open_window("lead_s")
    w0 = time.monotonic()               # the window is [w0, w0 + seconds)
    offset = w0 - t_open                # the loop opens late by one round
    due = [w0 + a.due_s - offset if not backlog else w0 for a in arrivals]
    lead_steps = len(steps)

    # ---- the window
    late = []
    w1 = w0 + seconds
    while True:
        now = time.monotonic()
        if now >= w1:       # the round in which the time ran out has ended
            break
        if (tracing is not None and not tracing.active
                and ctx.trace_path is None
                and now >= w1 - traffic["trace_seconds"]):
            tracing.start()
        while nxt < len(arrivals) and due[nxt] <= now:
            late.append(now - due[nxt])
            submit(nxt, now)
            nxt += 1
        if engine.idle:
            time.sleep(0.001)
        else:
            one_round()
    t_close = time.monotonic()
    if tracing is not None and tracing.active:
        tracing.mark_end()      # the profiler itself stops after the tail
    in_window = ctx.counters.delta(ctx.counters.snapshot(), compiles_at_open)
    ctx.memory_peak = ctx.read_memory_peak()
    ctx.report_setup(compiles_at_open, in_window)
    window_steps = steps[lead_steps:]
    window_s = t_close - w0
    in_slots_at_close = engine.slots_active
    queued_at_close = engine.queue_depth

    # ---- open loop: serve on, under continued arrivals, until every
    # request due in the window has ended (hard stop: tail_s)
    if backlog:
        measured = [i for i, (r, t) in results.items() if w0 <= t <= t_close]
    else:
        measured = [i for i in range(len(arrivals)) if w0 <= due[i] < w1]
        hard_stop = w1 + traffic["tail_s"]
        while (any(i not in results for i in measured)
               and time.monotonic() < hard_stop):
            now = time.monotonic()
            while nxt < len(arrivals) and due[nxt] <= now:
                submit(nxt, now)
                nxt += 1
            one_round()
    tail_s = time.monotonic() - t_close

    if tracing is not None and tracing.active:
        ctx.trace_path = tracing.stop()
    spans = [(e["name"], e["ts"] / 1e6, (e["ts"] + e["dur"]) / 1e6)
             for e in obs.get_tracer().events() if e.get("ph") == "X"]
    admitted = {e["args"]["request"]: (e["ts"] + e["dur"]) / 1e6
                for e in obs.get_tracer().events("queued")}
    stats = dict(engine.stats)

    # ---- reduce
    finished = [i for i in measured if i in results]
    ok = [i for i in finished if results[i][0].status == "done"]
    attempted = len(measured) + (in_slots_at_close if backlog else 0)
    failed = len(measured) - len(ok)    # ended otherwise, or never ended
    rid_of = {i: rid for rid, i in by_id.items()}
    ttft, tpot, waits = [], [], []
    for i in ok:
        r = results[i][0]
        lateness = 0.0 if backlog else submit_t[rid_of[i]] - due[i]
        if r.ttft_s is not None:
            ttft.append(1e3 * (lateness + r.ttft_s))
        if len(r.tokens) > 1 and r.ttft_s is not None:
            tpot.append(1e3 * (r.latency_s - r.ttft_s) / (len(r.tokens) - 1))
        if rid_of[i] in admitted:
            waits.append(1e3 * (admitted[rid_of[i]] - due[i]))
    tokens = sum(s[1] for s in window_steps)
    end_to_end = {"serve_throughput": tokens / window_s}
    if ttft and tpot:
        end_to_end.update(serve_ttft_mean=float(np.mean(ttft)),
                          serve_tpot_mean=float(np.mean(tpot)))
    counters = {
        "requests_due_or_done": len(measured), "requests_timed": len(ok),
        "requests_unfinished": len(measured) - len(finished),
        "requests_submitted": len(arrivals),
        "queued_at_close": queued_at_close, "tail_s": tail_s,
        "tokens_in_window": tokens, "window_s": window_s,
        "rounds_in_window": len(window_steps),
        "batch_occupancy_pct": (100.0 * sum(s[2] for s in window_steps)
                                / (slots * max(len(window_steps), 1))),
        "queue_wait_p90_ms": percentile(waits, 0.9) if waits else None,
        "generator_late_p90_ms": (1e3 * percentile(late, 0.9)
                                  if late else 0.0),
        "serve_ttft_p90_ms": percentile(ttft, 0.9) if not backlog else None,
        "serve_tpot_p90_ms": percentile(tpot, 0.9) if not backlog else None,
        "serve_ttft_p50_ms": percentile(ttft, 0.5),
        "serve_tpot_p50_ms": percentile(tpot, 0.5),
        "engine": {k: stats[k] for k in (
            "decode_steps", "prefill_calls", "requests_done", "failed",
            "retries", "prefill_traces", "decode_traces") if k in stats},
    }
    ctx.out("serve " + str(counters))

    # ---- correctness, outside the window: the program's state goes first
    greedy = [i for i in ok if arrivals[i].temperature <= 0
              and results[i][0].tokens]
    rng = np.random.RandomState((ctx.seed + 2) % (2 ** 32))
    sample = sorted(greedy, key=lambda i: -(len(arrivals[i].prompt) + len(
        results[i][0].tokens)))[:1]     # the longest is always in it
    rest = [i for i in greedy if i not in sample]
    sample += list(rng.permutation(rest)[:traffic["checked_requests"] - 1])
    pairs = [(arrivals[i].prompt, results[i][0].tokens) for i in sample]
    engine = router = None
    job.release()
    gc.collect()
    ctx.out(f"live bytes before the reference: {ctx.live_bytes()}")
    t_ref = time.perf_counter()
    gaps = job.reference_gaps(pairs) if pairs else []
    if ctx.control and pairs:
        lower = job.reference_gaps(pairs, control=ctx.control)
        ctx.out(f"control {ctx.control}: token_gap widest "
                f"{max(float(g.max()) for g in lower)!r} mean "
                f"{float(np.mean(np.concatenate(lower)))!r}")
    widest = max((float(g.max()) for g in gaps), default=float("inf"))
    mean = float(np.mean(np.concatenate(gaps))) if gaps else float("inf")
    n_tok = sum(len(g) for g in gaps)
    limits = ctx.limits["serve"]
    ctx.check.compare("token_gap", widest, limits["token_gap"])
    ctx.check.compare("token_gap_mean", mean, limits["token_gap_mean"])
    ctx.check.require("requests_done", failed == 0 and len(ok) > 0,
                      f"{len(measured)} due or done in the window, "
                      f"{len(ok)} done and timed, "
                      f"{len(measured) - len(finished)} not ended, "
                      f"{len(finished) - len(ok)} ended otherwise")
    ctx.check.require("no_compile_in_window", in_window["requests"] == 0,
                      str(in_window))
    if backlog:
        ctx.check.require("backlog_never_dry", queued_at_close > 0,
                          f"{queued_at_close} requests still queued when "
                          "the window closed")
    ctx.out(f"reference: {len(pairs)} greedy requests, {n_tok} served "
            f"tokens in {time.perf_counter() - t_ref:.1f} s")
    return {
        "attempted": attempted, "failed": failed,
        "window": (w0, t_close), "window_s": window_s,
        "compiles_at_open": compiles_at_open,
        "compiles_in_window": in_window, "spans": spans,
        "end_to_end": end_to_end, "counters": counters,
    }
