"""Host-side span tracer — Chrome-trace / Perfetto JSON.

Records named spans (begin/end pairs collapsed to complete "X" events)
from the serving request lifecycle (submit → queued → admit/prefill →
round×N → terminal status) and the training step phases (data /
dispatch ⊇ h2d_place / fence / checkpoint), and renders them as a
`chrome://tracing` / Perfetto-loadable JSON object.

Causality: every recorded span carries `args.id` (a counter of this
tracer) and, when it has one, `args.parent` — the id of the enclosing
open context-manager span of the same thread, or one handed over
explicitly (`span(..., parent=tracer.current())` carries a parent
across a thread hop: the engine's watchdog runs the decode dispatch
on a thread of its own). Spans of one request share `args.request`.
A context-manager span records when it is left, by an exception too:
a scheduling round whose decode dispatch was retried holds one
`decode_step` per attempt, the failed ones without children.

Alignment with device traces: a context-manager span also enters a
`jax.profiler.TraceAnnotation` of the same name, so a concurrent
`jax.profiler` capture shows the span on its host track, on the
capture's own clock, next to the XLA device timeline. `complete()`
spans (endpoints measured elsewhere: `queued`, `request[<status>]`,
`compile`) carry no annotation — they begin in the past.

Device waits: a span times what the HOST did; around an un-fenced
dispatch that is the dispatch, not the compute. A span NEVER adds a
device→host sync, with ONE stated exception: the serving engine's
`prefill` span waits for the returned pool before it closes WHILE THE
TRACER IS ENABLED (`args.fenced`), so that `prefill` holds the prefill
program and `decode_step` the decode program alone. With the tracer
off (the default, and every untraced benchmark run) no span site
touches a device array. `fetch` (inside `decode_step`) is the engine's
own sampled-token fetch, there with or without the tracer.

Categories: the serving round's tree (`submit`, `router_step`, `round`,
`admit`, `prefill`, `ensure_blocks`, `decode_step`, `upload`,
`dispatch`, `fetch`, `emit`, `queued`, `request[<status>]`,
`first_token`) is category `"serving"`, and that set is closed: the
benchmark's readers take `program_spans("serving")` and sum the self
times of a fixed tuple of names, so a new child of `admit` in that
category would take its time OUT of `*_round_host_share`, and one that
wrapped `prefill` would put the prefill's time INTO it. Detail spans
that split a span of the tree go in a category of their own: the
parts of an admission (`queue_expire`, `queue_pop`, `seat_prepare`,
`seat_commit`: leaves, children of `admit`, siblings of `prefill`;
a `request[expired]` that the expiry records names `queue_expire` as
the span open when it ENDED, and began long before it)
are category `"serving.admit"`. The tree's readers never see them; a
reader that wants them asks for every category, and the benchmark's
idle-gap attribution, which is handed every complete span whatever its
category, names the gaps under `admit` by them.

The tracer is OFF by default (`enabled=False` → `span()` is a shared
no-op context manager, one attribute test per site); drills and
profiling sessions turn it on. Both the clock and the buffer are
injectable/bounded; `span(clock=...)` lets one call site time on
another clock (the engine passes its own only when a drill injected
one — otherwise every span of a timeline is on the tracer's).
"""

from __future__ import annotations

import itertools
import json
import os
import threading
from collections import deque
from typing import Dict, List, Optional

__all__ = ["SpanTracer", "get_tracer", "set_tracer"]


class _NullSpan:
    """Shared no-op context manager for the disabled path."""

    id = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args) -> None:
        pass


_NULL_SPAN = _NullSpan()


def _obs_enabled() -> bool:
    """Global kill-switch check (call-time import — obs/__init__
    imports this module, so a top-level import would cycle). Every
    record path honors BIGDL_OBS=off even on an enabled tracer, per
    the 'every emission path early-outs on enabled()' contract."""
    from bigdl_tpu import obs

    return obs.enabled()


class _Span:
    __slots__ = ("tracer", "name", "cat", "args", "id", "parent",
                 "_clock", "_t0")

    def __init__(self, tracer: "SpanTracer", name: str, cat: str,
                 args: Optional[dict], clock, parent: Optional[int]):
        self.tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self.id = tracer._next_id()
        self.parent = parent
        self._clock = clock or tracer._clock
        self._t0 = 0.0

    def set(self, **args) -> None:
        """Add counts known only once the work is done (a round's
        `emitted`) to the span's args."""
        if self.args is None:
            self.args = args
        else:
            self.args.update(args)

    def elapsed(self) -> float:
        """Seconds since the span was entered, on its own clock: a
        reading taken INSIDE the span, for an arg that splits it
        (`prefill`'s `launched_s`)."""
        return self._clock() - self._t0

    def __enter__(self):
        stack = self.tracer._stack()
        if self.parent is None and stack:
            self.parent = stack[-1].id
        stack.append(self)
        self._t0 = self._clock()
        self.tracer._enter_annotation(self.name)
        return self

    def __exit__(self, *exc):
        self.tracer._exit_annotation()
        t1 = self._clock()
        self.tracer._stack().pop()
        self.tracer._record(self.name, self.cat, self._t0, t1,
                            self.args, self.id, self.parent)
        return False


class SpanTracer:
    """Bounded buffer of complete spans + instant events.

    `clock` returns seconds (injectable — the drills build the tracer
    and the serving engine on one virtual clock, so their spans are
    deterministic); timestamps are exported in microseconds as Chrome
    trace requires."""

    def __init__(self, capacity: int = 65536, clock=None,
                 enabled: bool = False, pid: Optional[int] = None):
        import time as _time

        self._clock = clock or _time.perf_counter
        self._events: deque = deque(maxlen=capacity)
        self.enabled = enabled
        self._pid = os.getpid() if pid is None else pid
        self._local = threading.local()
        self._ids = itertools.count(1)

    def _next_id(self) -> int:
        return next(self._ids)      # atomic under the GIL

    def _stack(self) -> list:
        stack = getattr(self._local, "spans", None)
        if stack is None:
            stack = self._local.spans = []
        return stack

    # ------------------------------------------------------------ record
    def span(self, name: str, cat: str = "host",
             args: Optional[dict] = None, clock=None,
             parent: Optional[int] = None):
        """Context manager recording one complete ("X") span, child of
        the innermost open span of this thread unless `parent` (an id
        from `current()`) says otherwise. `clock` times this one span
        on another clock than the tracer's."""
        if not self.enabled or not _obs_enabled():
            return _NULL_SPAN
        return _Span(self, name, cat, args, clock, parent)

    def now(self) -> float:
        """A reading of this tracer's clock (seconds)."""
        return self._clock()

    def current(self) -> Optional[int]:
        """Id of this thread's innermost open span (None outside any,
        or with the tracer off): what a closure about to run on another
        thread hands to `span(parent=...)`."""
        stack = getattr(self._local, "spans", None)
        return stack[-1].id if stack else None

    def instant(self, name: str, cat: str = "host",
                args: Optional[dict] = None,
                ts: Optional[float] = None) -> None:
        """Zero-duration marker ("i" event) — terminal statuses,
        faults, a request's first token. `ts` (seconds) stamps it at a
        reading the caller already took instead of now."""
        if not self.enabled or not _obs_enabled():
            return
        self._events.append({
            "name": name, "cat": cat, "ph": "i", "s": "t",
            "ts": (self._clock() if ts is None else ts) * 1e6,
            "pid": self._pid,
            "tid": threading.get_ident() & 0xFFFFFFFF,
            **({"args": args} if args else {})})

    def complete(self, name: str, cat: str, t0: float, t1: float,
                 args: Optional[dict] = None) -> None:
        """Record a span from externally measured endpoints (seconds):
        one that began before anyone knew it would be a span (`queued`,
        `request[<status>]`, `compile`). Child of this thread's
        innermost open span, like any other.

        Clock-domain contract: `t0`/`t1` must come from the SAME clock
        the rest of the timeline uses. The serving engine passes its
        own clock's readings here; the in-round spans and the training
        Timer spans use this tracer's clock (default perf_counter). On
        Linux the defaults (monotonic vs perf_counter) share an epoch;
        with an injected engine clock the engine times every span on
        that clock (`span(clock=...)`), and a drill builds the tracer
        on it too (`obs.reset_all(clock=...)`)."""
        if not self.enabled or not _obs_enabled():
            return
        self._record(name, cat, t0, t1, args, self._next_id(),
                     self.current())

    def _record(self, name, cat, t0, t1, args, span_id, parent):
        args = dict(args) if args else {}
        args["id"] = span_id
        if parent is not None:
            args["parent"] = parent
        self._events.append({
            "name": name, "cat": cat, "ph": "X",
            "ts": t0 * 1e6, "dur": max(t1 - t0, 0.0) * 1e6,
            "pid": self._pid,
            "tid": threading.get_ident() & 0xFFFFFFFF,
            "args": args})

    # ------------------------------------------------- jax trace alignment
    def _enter_annotation(self, name: str) -> None:
        """Mirror the span as a jax host TraceAnnotation so a
        concurrent jax.profiler capture shows the same label on its
        host track. Lazy import; never raises (telemetry must not take
        down the loop it observes)."""
        try:
            import jax

            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
            stack = getattr(self._local, "annotations", None)
            if stack is None:
                stack = self._local.annotations = []
            stack.append(ann)
        except Exception:
            pass

    def _exit_annotation(self) -> None:
        stack = getattr(self._local, "annotations", None)
        if stack:
            try:
                stack.pop().__exit__(None, None, None)
            except Exception:
                pass

    # ------------------------------------------------------------- export
    def to_chrome_trace(self) -> Dict[str, object]:
        """`{"traceEvents": [...], "displayTimeUnit": "ms"}` — loads
        in chrome://tracing and ui.perfetto.dev."""
        return {"traceEvents": list(self._events),
                "displayTimeUnit": "ms"}

    def save(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)
        return path

    def events(self, name: Optional[str] = None) -> List[dict]:
        return [e for e in self._events
                if name is None or e["name"] == name]

    def clear(self) -> None:
        self._events.clear()


_tracer = SpanTracer()


def get_tracer() -> SpanTracer:
    return _tracer


def set_tracer(tracer: Optional[SpanTracer]) -> SpanTracer:
    """Install a tracer (None → fresh disabled default); returns the
    active one."""
    global _tracer
    _tracer = tracer or SpanTracer()
    return _tracer
