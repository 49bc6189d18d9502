"""Unified telemetry plane (ISSUE 5 tentpole).

One process-wide home for the three observability primitives both the
training loop and the serving engine report into:

* `registry` — metrics (counter / gauge / fixed-bucket histogram with
  label sets; deterministic snapshot, Prometheus text, JSON export)
* `events`   — schema-versioned JSONL event log (ring buffer +
  optional file sink); the machine-readable record of what a run did
* `spans`    — host-side span tracer emitting Chrome-trace/Perfetto
  JSON: one tree per scheduling round and per train step (ids and
  parents), each context-manager span mirrored as a
  `jax.profiler.TraceAnnotation` onto a device trace's clock
* `compiles` — the process's one `jax.monitoring` listener:
  `xla_compiles_total{cache}` and a `compile` span per backend compile

ISSUE 14 adds the LIVE layer on top: `timeseries` (bounded ring of
registry samples, windowed rate/delta/quantile queries — the
autoscaler's windowing now lives here), `slo` (declarative
SLOObjective + deterministic AlertRule/AlertEngine; alert_firing is a
flight-recorder trigger), and `exposition` (stdlib-HTTP scrape
endpoint: /metrics Prometheus text, /health + /alerts JSON).

Hard contracts (tests/test_obs.py):
* telemetry NEVER touches jitted code: zero new compiles with it on
  (the serving #buckets+1 guard passes with telemetry enabled);
* zero new device→host syncs on hot paths — emission consumes only
  values the loop already fetched. ONE stated exception, and only
  while the span tracer is enabled: the serving engine's `prefill`
  span waits for the prefill program before it closes
  (`args.fenced`; obs/spans.py says why). Tracer off — the default,
  and how every untraced benchmark run goes — the contract holds
  unchanged: no span site touches a device array;
* everything is bit-reproducible under an injected clock (the fault
  drills assert on telemetry, scripts/fault_drill.py);
* <1% step overhead on the lmdecode_batched bench row (bench.py
  measures on-vs-off in one invocation).

Global switch: `BIGDL_OBS=off` (env, read at import) or
`set_enabled(False)` at runtime — every emission path early-outs on
`enabled()`. Core serving/training bookkeeping (engine.stats, loss
logging) does NOT depend on telemetry being on.
"""

from __future__ import annotations

import os
from typing import Optional

from bigdl_tpu.obs.compiles import install_compile_listener
from bigdl_tpu.obs.events import (EventLog, get_event_log, read_jsonl,
                                  set_event_log, stream_jsonl)
from bigdl_tpu.obs.exposition import ScrapeServer
from bigdl_tpu.obs.flightrecorder import FlightRecorder, default_trigger
from bigdl_tpu.obs.journey import (build_journeys, journeys_json,
                                   summarize_journeys, to_perfetto)
from bigdl_tpu.obs.registry import (DEFAULT_LATENCY_BUCKETS, Counter,
                                    Gauge, Histogram, MetricsRegistry,
                                    get_registry, series_key,
                                    set_registry)
from bigdl_tpu.obs.slo import AlertEngine, AlertRule, SLOObjective
from bigdl_tpu.obs.spans import SpanTracer, get_tracer, set_tracer
from bigdl_tpu.obs.timeseries import HistogramWindow, MetricsSampler

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "DEFAULT_LATENCY_BUCKETS", "get_registry", "set_registry",
    "EventLog", "get_event_log", "set_event_log", "read_jsonl",
    "stream_jsonl",
    "SpanTracer", "get_tracer", "set_tracer",
    "FlightRecorder", "default_trigger",
    "build_journeys", "journeys_json", "summarize_journeys",
    "to_perfetto",
    "MetricsSampler", "HistogramWindow",
    "SLOObjective", "AlertRule", "AlertEngine", "ScrapeServer",
    "install_compile_listener",
    "enabled", "set_enabled", "emit_event", "log_metrics_snapshot",
    "provenance", "reset_all",
]

_enabled = os.environ.get("BIGDL_OBS", "on").lower() not in (
    "off", "0", "false", "no")


install_compile_listener()      # one per process: obs/compiles.py


def enabled() -> bool:
    return _enabled


def set_enabled(value: bool) -> bool:
    """Runtime switch for every emission path (registry mirrors, event
    records, spans). Returns the previous value."""
    global _enabled
    prev, _enabled = _enabled, bool(value)
    return prev


def emit_event(kind: str, **fields) -> Optional[dict]:
    """Emit into the active event log iff telemetry is enabled — THE
    call every instrumented site uses (optimizer, engine, checkpoint,
    faults, anomaly guard)."""
    if not _enabled:
        return None
    return get_event_log().emit(kind, **fields)


def log_metrics_snapshot(**extra) -> Optional[dict]:
    """Embed a full registry snapshot as a `metrics_snapshot` event,
    making a JSONL file self-contained for scripts/obs_report.py."""
    if not _enabled:
        return None
    return get_event_log().emit("metrics_snapshot",
                                snapshot=get_registry().snapshot(),
                                **extra)


def provenance(prefix: Optional[str] = None) -> dict:
    """Compact registry view for attaching to bench rows: counter and
    gauge values (histograms reduced to count/sum), optionally
    restricted to names starting with `prefix`. Deterministic ordering
    (sorted)."""
    snap = get_registry().snapshot()
    out = {}
    for name, fam in snap["metrics"].items():
        if prefix is not None and not name.startswith(prefix):
            continue
        for s in fam["series"]:
            key = series_key(name, s["labels"])
            if fam["kind"] == "histogram":
                out[key] = {"count": s["count"],
                            "sum": round(s["sum"], 6)}
            else:
                out[key] = s["value"]
    return {"telemetry": "on" if _enabled else "off", "metrics": out}


def reset_all(clock=None) -> None:
    """Fresh registry + event log + (disabled) tracer — drill/test
    isolation. `clock` (if given) is injected into all three. The
    fresh event log keeps the BIGDL_OBS_EVENTS file sink (append), so
    resetting never silently drops the operator's JSONL record.

    Caveat: objects that cache registry children at construction
    (InferenceEngine, Optimizer loops, AnomalyGuard, optim.Metrics)
    keep writing to the registry that was active WHEN THEY WERE BUILT
    — install custom telemetry first, construct after (the fault
    drills do exactly this)."""
    set_registry(MetricsRegistry(clock=clock))
    set_event_log(EventLog(
        path=os.environ.get("BIGDL_OBS_EVENTS") or None, clock=clock))
    set_tracer(SpanTracer(clock=clock))
