"""Unified telemetry plane (ISSUE 5): registry determinism under an
injected clock, histogram percentiles vs a numpy oracle, the
structured event log (ring/sink/schema), Chrome-trace span export
(pure-parse), the serving compile-count guard re-run with telemetry
fully enabled, and the single training emission path."""

import json

import numpy as np
import pytest

from bigdl_tpu import obs


@pytest.fixture(autouse=True)
def _fresh_obs():
    """Every test gets fresh registry/log/tracer and telemetry ON;
    global state never leaks between tests."""
    prev = obs.set_enabled(True)
    obs.reset_all()
    yield
    obs.reset_all()
    obs.set_enabled(prev)


# ------------------------------------------------------------- registry

def test_registry_deterministic_under_injected_clock():
    """Identical metric activity + injected clock → byte-identical
    snapshot JSON and Prometheus text, run to run (what makes drill
    telemetry assertable bit-for-bit)."""
    def run():
        reg = obs.set_registry(obs.MetricsRegistry(clock=lambda: 7.0))
        c = reg.counter("req_total", "requests", ("status",))
        c.labels(status="done").inc(3)
        c.labels(status="shed").inc()
        reg.gauge("depth", "queue depth").set(4)
        h = reg.histogram("lat_seconds", "latency")
        for v in (0.002, 0.011, 0.4, 0.011):
            h.observe(v)
        return reg.to_json(), reg.render_prometheus()
    a, b = run(), run()
    assert a == b
    # label/name ordering is sorted, not insertion-dependent
    reg = obs.set_registry(obs.MetricsRegistry(clock=lambda: 7.0))
    c = reg.counter("req_total", "requests", ("status",))
    c.labels(status="shed").inc()           # reversed insertion order
    c.labels(status="done").inc(3)
    reg.gauge("depth", "queue depth").set(4)
    h = reg.histogram("lat_seconds", "latency")
    for v in (0.011, 0.4, 0.002, 0.011):    # permuted observations
        h.observe(v)
    assert reg.to_json() == a[0]


def test_registry_schema_conflicts_raise():
    reg = obs.get_registry()
    reg.counter("a_total", "x", ("k",))
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("a_total")
    with pytest.raises(ValueError, match="labelnames mismatch"):
        reg.counter("a_total", "x", ("other",))
    reg.histogram("h_seconds", buckets=(1.0, 2.0))
    with pytest.raises(ValueError, match="bucket mismatch"):
        reg.histogram("h_seconds", buckets=(1.0, 3.0))
    with pytest.raises(ValueError, match="only go up"):
        reg.counter("b_total").inc(-1)
    with pytest.raises(ValueError, match="do not match"):
        reg.counter("a_total", "x", ("k",)).labels(wrong="v")


def test_histogram_percentiles_vs_numpy_oracle():
    """Bucket-interpolated quantiles must track np.quantile within one
    bucket width, across distributions."""
    edges = tuple(np.linspace(0.01, 1.0, 100))     # width 0.01
    rng = np.random.RandomState(0)
    for data in (rng.uniform(0, 1, 2000),
                 rng.beta(2, 5, 2000),             # skewed low
                 rng.beta(5, 1, 2000)):            # skewed high
        reg = obs.set_registry(obs.MetricsRegistry())
        h = reg.histogram("h", buckets=edges)
        for v in data:
            h.observe(float(v))
        for q in (0.1, 0.5, 0.9, 0.95, 0.99):
            est = h.quantile(q)
            oracle = float(np.quantile(data, q))
            assert abs(est - oracle) <= 0.011, (q, est, oracle)
    # degenerate cases
    reg = obs.set_registry(obs.MetricsRegistry())
    h = reg.histogram("h2", buckets=(0.1, 1.0))
    assert h.quantile(0.5) is None                 # empty
    h.observe(5.0)                                 # +Inf bucket
    assert h.quantile(0.99) == 1.0                 # clamps to top edge
    with pytest.raises(ValueError):
        h.quantile(1.5)


def test_prometheus_exposition_format():
    reg = obs.get_registry()
    reg.counter("req_total", "reqs", ("status",)).labels(
        status="done").inc(2)
    reg.histogram("lat_seconds", buckets=(0.1, 1.0)).observe(0.05)
    text = reg.render_prometheus()
    assert "# TYPE req_total counter" in text
    assert 'req_total{status="done"} 2' in text
    assert 'lat_seconds_bucket{le="0.1"} 1' in text
    assert 'lat_seconds_bucket{le="+Inf"} 1' in text
    assert "lat_seconds_count 1" in text


# ------------------------------------------------------------ event log

def test_event_log_ring_sink_and_schema(tmp_path):
    path = tmp_path / "ev.jsonl"
    log = obs.set_event_log(obs.EventLog(capacity=4, path=str(path),
                                         clock=lambda: 9.0))
    for i in range(6):
        obs.emit_event("tick", i=i)
    # ring keeps the newest `capacity` records; seq keeps counting
    assert len(log) == 4
    assert [e["i"] for e in log.events("tick")] == [2, 3, 4, 5]
    assert [e["seq"] for e in log.events()] == [2, 3, 4, 5]
    assert all(e["schema"] == 1 and e["ts"] == 9.0
               for e in log.events())
    # the file sink kept ALL records (ring bounds memory, not disk)
    ondisk = obs.read_jsonl(str(path))
    assert [e["i"] for e in ondisk] == list(range(6))
    # field filtering
    assert log.events("tick", i=3)[0]["seq"] == 3
    assert log.events("other") == []
    assert log.counts_by_kind() == {"tick": 4}
    log.close()
    # torn final line (crash mid-write) is dropped, not an error
    with open(path, "a") as f:
        f.write('{"schema": 1, "kind": "to')
    assert len(obs.read_jsonl(str(path))) == 6


def test_event_log_disabled_emits_nothing():
    obs.set_enabled(False)
    assert obs.emit_event("x") is None
    assert len(obs.get_event_log()) == 0
    obs.set_enabled(True)
    assert obs.emit_event("x")["kind"] == "x"


# ---------------------------------------------------------------- spans

def test_span_tracer_chrome_trace_parses(tmp_path):
    """Span JSON must satisfy the chrome://tracing schema: a
    traceEvents array of objects with name/ph/ts/pid/tid, "X" events
    carrying dur — asserted on a re-parsed file (pure parse)."""
    clk = {"t": 1.0}

    def clock():
        clk["t"] += 0.5
        return clk["t"]

    tr = obs.set_tracer(obs.SpanTracer(clock=clock, enabled=True))
    with tr.span("prefill", cat="serving", args={"slot": 0}):
        pass
    tr.instant("poisoned", cat="serving")
    tr.complete("queued", "serving", 0.25, 1.5, args={"request": 7})
    path = str(tmp_path / "trace.json")
    tr.save(path)
    with open(path) as f:
        doc = json.load(f)
    evs = doc["traceEvents"]
    assert isinstance(evs, list) and len(evs) == 3
    for e in evs:
        assert {"name", "cat", "ph", "ts", "pid", "tid"} <= set(e)
        assert e["ph"] in ("X", "i")
        if e["ph"] == "X":
            assert e["dur"] >= 0
    x = [e for e in evs if e["name"] == "prefill"][0]
    assert x["ts"] == pytest.approx(1.5e6)        # seconds → µs
    assert x["dur"] == pytest.approx(0.5e6)
    assert x["args"] == {"slot": 0, "id": 1}      # a root: no parent
    q = [e for e in evs if e["name"] == "queued"][0]
    assert q["dur"] == pytest.approx(1.25e6)
    assert q["args"] == {"request": 7, "id": 2}


def test_span_tracer_disabled_is_noop():
    tr = obs.get_tracer()
    assert not tr.enabled
    with tr.span("x"):
        pass
    tr.instant("y")
    tr.complete("z", "c", 0.0, 1.0)
    assert tr.to_chrome_trace()["traceEvents"] == []


# ------------------------------------------- serving: guard + telemetry

def _tiny_lm():
    import jax

    from bigdl_tpu.models.transformer import build_lm

    m = build_lm(vocab_size=50, dim=32, num_heads=2, num_layers=1,
                 max_len=64)
    m.build(jax.random.PRNGKey(0))
    return m


def test_compile_guard_with_telemetry_enabled():
    """The zero-recompile contract with EVERY telemetry path armed —
    registry mirrors, event log, span tracer: still exactly (#buckets
    used) prefill traces + 1 decode trace, because telemetry is
    host-side by construction. health() percentiles come from the
    fixed-bucket histogram and the event log carries the request
    lifecycle."""
    from bigdl_tpu.serving import InferenceEngine, Request

    obs.set_tracer(obs.SpanTracer(enabled=True))
    log = obs.get_event_log()
    m = _tiny_lm()
    eng = InferenceEngine(m, slots=2, prefill_buckets=(8, 16))
    rng = np.random.RandomState(0)
    reqs = [Request(prompt=list(rng.randint(1, 50, n)),
                    max_new_tokens=3) for n in (3, 10, 6, 12)]
    res = eng.run(reqs)
    assert all(r.status == "done" for r in res)
    assert eng.stats["prefill_traces"] == 2       # both buckets
    assert eng.stats["decode_traces"] == 1        # ONE executable
    # second wave with telemetry still on: nothing new compiles
    res2 = eng.run([Request(prompt=[1, 2, 3], max_new_tokens=2)])
    assert eng.stats["prefill_traces"] == 2
    assert eng.stats["decode_traces"] == 1
    # health: histogram-backed percentiles + registry view
    h = eng.health()
    assert h["decode_p50_ms"] is not None
    assert h["metrics"]["decode_step_seconds"]["count"] == \
        eng.stats["decode_steps"]
    assert h["metrics"]["requests_total"]["done"] == 5
    # events: one submit + one terminal per request
    assert len(log.events("request_submit")) == 5
    done = log.events("request_terminal", status="done")
    assert len(done) == 5
    assert sum(e["tokens"] for e in done) == \
        sum(len(r.tokens) for r in res) + len(res2[0].tokens)
    # spans: queued/prefill per admission, decode_step per step,
    # request[...] per terminal — all in one coherent trace doc
    tr = obs.get_tracer()
    assert len(tr.events("prefill")) == 5
    assert len(tr.events("queued")) == 5
    assert len(tr.events("decode_step")) == eng.stats["decode_steps"]
    assert len(tr.events("request[done]")) == 5
    doc = json.loads(json.dumps(tr.to_chrome_trace()))
    assert all(e["ph"] in ("X", "i") for e in doc["traceEvents"])


def _steps_counted(metric, label, names):
    """A per-step counter's series by its label, 0 where none yet."""
    series = obs.get_registry().snapshot()["metrics"].get(
        metric, {"series": []})["series"]
    got = dict.fromkeys(names, 0)
    got.update({s["labels"][label]: s["value"] for s in series})
    return got


def test_sampler_path_counted_by_what_the_seated_rows_ask():
    """ISSUE 37: every decode step is classed on the host by the
    predicate the program's sampler branches on (the costliest seated
    row's class): counter, `health()` shares and the recorded
    `decode_step` spans say the same word. V = 50 here, so K = 50."""
    from bigdl_tpu.serving import InferenceEngine, Request
    from bigdl_tpu.serving.sampler import SAMPLER_PATHS

    obs.set_tracer(obs.SpanTracer(enabled=True))
    eng = InferenceEngine(_tiny_lm(), slots=2, prefill_buckets=(8,))

    def counted():
        return _steps_counted("serving_sampler_steps_total", "path",
                              SAMPLER_PATHS)

    sampled = dict(prompt=[4, 5, 6], max_new_tokens=4, temperature=0.8,
                   seed=3)
    waves = [
        ("greedy", [Request(prompt=[1, 2, 3], max_new_tokens=3),
                    Request(prompt=[7, 8], max_new_tokens=3)]),
        ("unfiltered", [Request(**sampled)]),
        # a candidates row beside a greedy one that outlasts it: the
        # steps they share are the sampling row's, the rest greedy
        ("candidates", [Request(**sampled, top_k=5, top_p=0.9),
                        Request(prompt=[1, 2, 3], max_new_tokens=6)]),
        ("full_sort", [Request(**sampled, top_p=0.9),
                       Request(**sampled, top_k=5, top_p=0.9)]),
        ("full_sort", [Request(**sampled, top_k=51)]),
    ]
    want = dict.fromkeys(SAMPLER_PATHS, 0)
    for path, reqs in waves:
        before = eng.stats["decode_steps"]
        assert all(r.status == "done" for r in eng.run(reqs))
        steps = eng.stats["decode_steps"] - before
        if path == "candidates":
            # a token a step: the sampling row is seated for 4 of them
            want["candidates"] += 4
            want["greedy"] += steps - 4
        else:
            want[path] += steps
        assert counted() == want, path
    assert eng.stats["decode_traces"] == 1        # ONE executable
    total = sum(want.values())
    assert total == eng.stats["decode_steps"]
    assert eng.health()["sampler_path_share"] == {
        p: round(n / total, 4) for p, n in want.items()}
    spans = [e["args"]["sampler_path"] for e in
             obs.get_tracer().events("decode_step") if e["ph"] == "X"]
    assert {p: spans.count(p) for p in SAMPLER_PATHS} == want


def test_decode_read_counted_by_the_share_its_step_was_compiled_for():
    """ISSUE 39: every decode step is counted under the share of the
    table that its ragged read was compiled for, by the program's own
    roundings on the host: 4 slots x 16 blocks in chunks of 2 blocks are
    32 chunks, read as 2, as 16 or as all. One short request seated is
    the 1/16 read, two of four seats the half read, four long ones the
    read of all; counter, `health()` shares and the spans' blocks say
    the same."""
    import jax

    from bigdl_tpu.models.transformer import build_lm
    from bigdl_tpu.ops.kv_cache import READ_SHARE_NAMES
    from bigdl_tpu.serving import InferenceEngine, Request

    assert READ_SHARE_NAMES == ("1/16", "1/2", "1")
    obs.set_tracer(obs.SpanTracer(enabled=True))
    m = build_lm(vocab_size=61, dim=128, num_heads=2, num_layers=1,
                 max_len=64)
    m.build(jax.random.PRNGKey(1))
    eng = InferenceEngine(m, slots=4, block_size=4,
                          prefill_buckets=(8, 24, 48))
    assert eng.health()["attn_form"] == "rows"
    assert eng.health()["read_share_steps"] == dict.fromkeys(
        READ_SHARE_NAMES, 0.0)
    rng = np.random.RandomState(2)

    def wave(n, length):
        return [Request(prompt=[int(t) for t in rng.randint(1, 61, length)],
                        max_new_tokens=4) for _ in range(n)]

    want = dict.fromkeys(READ_SHARE_NAMES, 0)
    for read, reqs in (("1/16", wave(1, 3)), ("1/2", wave(2, 20)),
                       ("1", wave(4, 40))):
        before = eng.stats["decode_steps"]
        assert all(r.status == "done" for r in eng.run(reqs))
        want[read] += eng.stats["decode_steps"] - before
        assert _steps_counted("serving_decode_read_steps_total", "read",
                              READ_SHARE_NAMES) == want, read
    assert all(want.values())
    total = eng.stats["decode_steps"]
    assert eng.health()["read_share_steps"] == {
        r: round(n / total, 4) for r, n in want.items()}
    spans = [e["args"] for e in obs.get_tracer().events("decode_step")
             if e["ph"] == "X"]
    blocks = {"1/16": 4, "1/2": 32, "1": 64}
    assert {r: sum(a["attended_blocks"] == b for a in spans)
            for r, b in blocks.items()} == want
    assert eng.stats["decode_traces"] == 1        # ONE executable


def test_the_head_split_form_counts_every_step_as_the_read_of_all():
    from bigdl_tpu.serving import InferenceEngine, Request

    eng = InferenceEngine(_tiny_lm(), slots=2, prefill_buckets=(8,))
    assert eng.health()["attn_form"] == "heads"
    eng.run([Request(prompt=[1, 2, 3], max_new_tokens=3)])
    steps = eng.stats["decode_steps"]
    assert steps and _steps_counted(
        "serving_decode_read_steps_total", "read",
        ("1/16", "1/2", "1")) == {"1/16": 0, "1/2": 0, "1": steps}
    assert eng.health()["read_share_steps"]["1"] == 1.0


def test_engine_metrics_off_keeps_core_bookkeeping():
    """BIGDL_OBS=off: stats AND health() — including the latency
    percentiles, which are core bookkeeping fed unconditionally —
    still work; events, spans, and counter mirrors stay silent."""
    from bigdl_tpu.serving import InferenceEngine, Request

    obs.set_enabled(False)
    obs.set_tracer(obs.SpanTracer(enabled=True))  # still muted by off
    m = _tiny_lm()
    eng = InferenceEngine(m, slots=1, prefill_buckets=(8,))
    res = eng.run([Request(prompt=[1, 2, 3], max_new_tokens=2)])[0]
    assert res.status == "done"
    assert eng.stats["requests_done"] == 1
    h = eng.health()
    assert h["requests_done"] == 1
    assert h["decode_p50_ms"] is not None         # core, not telemetry
    assert h["metrics"]["decode_step_seconds"]["count"] == \
        eng.stats["decode_steps"]
    assert len(obs.get_event_log()) == 0
    assert obs.get_tracer().to_chrome_trace()["traceEvents"] == []
    # counter MIRRORS are gated (the _stats dict is the core copy)
    snap = obs.get_registry().snapshot()["metrics"]
    assert "serving_requests_total" not in snap \
        or all(s["value"] == 0
               for s in snap["serving_requests_total"]["series"])


# ------------------------------------------------------- training plane

def test_step_telemetry_single_emission_path():
    """One emit_step call fans out to registry + event log + summary
    sink — the duplicate Loss/Throughput bookkeeping the satellites
    called out is structurally gone."""
    from bigdl_tpu.obs.training import StepTelemetry

    sunk = []

    class Sink:
        def add_scalar(self, tag, value, step):
            sunk.append((tag, float(value), step))

        def add_histogram(self, tag, values, step):
            sunk.append(("hist:" + tag, None, step))

    t = StepTelemetry(summary=Sink())
    t.emit_step(epoch=1, step=3, loss=0.5, lr=0.01, throughput=100.0,
                records=8, gnorm=2.0,
                hists=[("w", np.zeros(3))], metrics_summary="")
    t.emit_step(epoch=1, step=4, loss=0.4, lr=0.01, throughput=110.0,
                records=8, update_applied=False, metrics_summary="")
    assert ("Loss", 0.5, 3) in sunk and ("LearningRate", 0.01, 3) in sunk
    assert ("hist:w", None, 3) in sunk
    snap = obs.get_registry().snapshot()["metrics"]
    assert snap["training_steps_total"]["series"][0]["value"] == 2
    assert snap["training_updates_applied_total"]["series"][0][
        "value"] == 1
    assert snap["training_loss"]["series"][0]["value"] == 0.4
    evs = obs.get_event_log().events("train_step")
    assert [e["step"] for e in evs] == [3, 4]
    assert evs[0]["gnorm"] == 2.0 and "gnorm" not in evs[1]
    assert not evs[1]["update_applied"]
    # piggyback contract: a non-fence step passes loss=None — the
    # event still records every host-side field, omits loss, and the
    # summary sink/log line (which need the fetch) are skipped
    n_sunk = len(sunk)
    t.emit_step(epoch=1, step=5, loss=None, lr=0.01,
                throughput=120.0, records=8, metrics_summary="")
    ev = obs.get_event_log().events("train_step", step=5)[0]
    assert "loss" not in ev and ev["throughput"] == 120.0
    assert len(sunk) == n_sunk
    snap = obs.get_registry().snapshot()["metrics"]
    assert snap["training_loss"]["series"][0]["value"] == 0.4  # kept
    assert snap["training_steps_total"]["series"][0]["value"] == 3


def test_set_event_log_closes_replaced_sink(tmp_path):
    """Replacing the active log must close the old file sink (no fd
    leak across resets) while keeping its ring readable — and a fresh
    default re-attaches the BIGDL_OBS_EVENTS sink in append mode."""
    path = tmp_path / "a.jsonl"
    old = obs.set_event_log(obs.EventLog(path=str(path)))
    obs.emit_event("x")
    obs.set_event_log(obs.EventLog())
    assert old._sink is None                  # closed on replacement
    assert old.events("x")                    # ring still readable
    assert obs.set_event_log(obs.get_event_log()) is not None  # no-op


def test_metrics_timers_feed_registry_and_tracer():
    from bigdl_tpu.optim.metrics import Metrics, Timer

    obs.set_tracer(obs.SpanTracer(enabled=True))
    m = Metrics()
    with Timer(m, "data_fetch_s"):
        pass
    with Timer(m, "dispatch_s"):
        pass
    m.set("lr", 0.1)
    snap = obs.get_registry().snapshot()["metrics"]
    phases = {s["labels"]["phase"]: s["count"]
              for s in snap["training_phase_seconds"]["series"]}
    assert phases == {"data_fetch_s": 1, "dispatch_s": 1}
    gauges = {s["labels"]["name"]: s["value"]
              for s in snap["training_metric"]["series"]}
    assert gauges == {"lr": 0.1}
    names = {e["name"] for e in obs.get_tracer().events()}
    assert names == {"data_fetch", "dispatch"}
    # the local running-mean view is unchanged
    assert "data_fetch_s=" in m.summary()


def test_provenance_compact_view():
    reg = obs.get_registry()
    reg.counter("serving_x_total", "x", ("engine",)).labels(
        engine="engine0").inc(4)
    reg.histogram("serving_lat_seconds").observe(0.01)
    reg.counter("training_steps_total").inc()
    p = obs.provenance("serving_")
    assert p["telemetry"] == "on"
    assert p["metrics"]["serving_x_total{engine=engine0}"] == 4
    assert p["metrics"]["serving_lat_seconds"]["count"] == 1
    assert "training_steps_total" not in p["metrics"]
    assert "training_steps_total" in obs.provenance()["metrics"]


# ------------------------------------------------------------ obs_report

def _load_report():
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "scripts",
                        "obs_report.py")
    spec = importlib.util.spec_from_file_location("obs_report", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_obs_report_summarize_and_render(tmp_path, capsys):
    """obs_report digests a JSONL file: counts, training/serving
    summaries, percentiles from an embedded metrics snapshot."""
    path = tmp_path / "run.jsonl"
    obs.set_event_log(obs.EventLog(path=str(path), clock=lambda: 1.0))
    for i in range(3):
        obs.emit_event("train_step", plane="training", epoch=1,
                       step=i + 1, loss=1.0 - 0.1 * i, lr=0.01,
                       throughput=100.0, update_applied=i != 1)
    obs.emit_event("anomaly", plane="training", step=2,
                   action="skipped", policy="skip_step", gnorm=0.0)
    obs.emit_event("fault_injected", fault="nan", step=2)
    obs.emit_event("request_terminal", plane="serving",
                   engine="engine0", request=0, status="done",
                   reason="max_tokens", tokens=5)
    obs.emit_event("request_terminal", plane="serving",
                   engine="engine0", request=1, status="poisoned",
                   reason="poisoned", tokens=2)
    obs.get_registry().histogram("serving_decode_step_seconds",
                                 labelnames=("engine",)).labels(
        engine="engine0").observe(0.02)
    obs.log_metrics_snapshot()
    obs.get_event_log().close()

    rep = _load_report()
    s = rep.summarize(rep.read_jsonl(str(path))
                      if hasattr(rep, "read_jsonl")
                      else obs.read_jsonl(str(path)))
    assert s["training"]["steps"] == 3
    assert s["training"]["updates_applied"] == 2
    assert s["training"]["anomalies"] == 1
    assert s["serving"]["by_status"] == {"done": 1, "poisoned": 1}
    assert s["serving"]["tokens_generated"] == 7
    assert s["faults"] == ["nan@2"]
    lat = s["metrics"][
        "serving_decode_step_seconds{engine=engine0}"]
    assert lat["count"] == 1 and lat["p50"] is not None
    # quantile helper matches the registry estimator
    assert rep.quantile_from_buckets([1.0, 2.0], [1, 1, 0], 0.5) \
        == pytest.approx(1.0)
    assert rep.quantile_from_buckets([1.0], [0, 0], 0.5) is None
    # CLI renders and exits 0
    assert rep.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "training:" in out and "serving:" in out
    assert "status poisoned" in out
    assert rep.main([str(tmp_path / "missing.jsonl")]) == 2

def test_obs_report_checkpoint_section(tmp_path, capsys):
    """ISSUE 9: the checkpoint digest — save cadence and durations
    from the enriched checkpoint_save events (async/duration_s/shard/
    nshards), shard-unit tally, corrupt-skip count, and the
    training_checkpoint_seconds histogram from the snapshot."""
    path = tmp_path / "run.jsonl"
    obs.set_event_log(obs.EventLog(path=str(path), clock=lambda: 1.0))
    h = obs.get_registry().histogram(
        "training_checkpoint_seconds", "save seconds", ("mode",))
    for step, dur in ((3, 0.010), (6, 0.030), (9, 0.020)):
        for shard in range(2):
            obs.emit_event("checkpoint_save", step=step, path=f"c-{step}",
                           **{"async": True}, duration_s=dur / 4,
                           nshards=2, shard=shard)
        obs.emit_event("checkpoint_save", step=step, path=f"c-{step}",
                       **{"async": True}, duration_s=dur, nshards=2,
                       mid_cycle=False)
        h.labels(mode="async").observe(dur)
    obs.emit_event("checkpoint_corrupt_skipped", path="c-9",
                   error="crc mismatch")
    obs.emit_event("checkpoint_load", path="c-6", sharded=True, nshards=2)
    obs.log_metrics_snapshot()
    obs.get_event_log().close()

    rep = _load_report()
    s = rep.summarize(obs.read_jsonl(str(path)))
    c = s["checkpoints"]
    assert c["saves"] == 3 and c["async_saves"] == 3
    assert c["shard_unit_writes"] == 6 and c["nshards"] == 2
    assert c["save_cadence_steps"] == 3.0
    assert c["loads"] == 1 and c["sharded_loads"] == 1
    assert c["corrupt_skipped"] == 1
    assert c["save_duration_p50_s"] == pytest.approx(0.020)
    assert c["save_duration_max_s"] == pytest.approx(0.030)
    hist = c["histogram"]["async"]
    assert hist["count"] == 3 and hist["p50_s"] is not None
    assert rep.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "checkpoints:" in out and "save_cadence_steps" in out
    assert "async save (hist)" in out
