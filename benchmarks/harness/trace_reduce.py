"""From a profiler trace (`.xplane.pb`) to numbers: device busy and idle
time, the operations that took most of it, kernel (`tpu_custom_call`) time,
collective time and the part of it that no compute hides, and each idle gap
attributed to what the host was doing in it.

Read with `jax.profiler.ProfileData`, nothing else. The reduction is the
benchmark's: a PR that claims a gain cannot change how its gain is read.
tests/bench/test_trace_reduce.py checks it on a synthetic trace with known
answers and on the small recorded traces in benchmarks/testdata/.

Time base. The harness brackets the traced window with two host
annotations, `bench_trace_begin` and `bench_trace_end`, and notes the host
clock at each. Their positions in the trace give the window, and the offset
that carries the program's host spans (host clock) onto the trace's clock.
"""

from __future__ import annotations

import re
from typing import Iterable, List, Optional, Sequence, Tuple

BEGIN, END = "bench_trace_begin", "bench_trace_end"
OPS_LINE = "XLA Ops"          # what the core executes, one op at a time
ASYNC_LINE = "Async XLA Ops"  # start-to-done spans of asynchronous ops
MODULES_LINE = "XLA Modules"  # one event per execution of a jitted program
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")
# operations that only contain others (their bodies' operations are events
# of their own on the same line): never counted as work themselves
CONTAINERS = (":while", ":conditional", ":call")

Interval = Tuple[float, float]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[Interval] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def length(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def clip(intervals: Iterable[Interval], lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(intervals: Sequence[Interval], cover: Sequence[Interval]):
    """The parts of merged `intervals` that merged `cover` leaves bare."""
    out = []
    for a, b in intervals:
        at = a
        for c, d in cover:
            if d <= at:
                continue
            if c >= b:
                break
            if c > at:
                out.append((at, c))
            at = max(at, d)
        if at < b:
            out.append((at, b))
    return out


def gaps(busy: Sequence[Interval], lo: float, hi: float):
    return subtract([(lo, hi)], busy)


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:TPU:")


def _find_markers(profile):
    begin = end = None
    for plane in profile.planes:
        if _is_device(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == BEGIN and begin is None:
                    begin = ev.start_ns
                elif ev.name == END:
                    end = ev.start_ns
    return begin, end


_OPCODE = re.compile(r"(?<![A-Za-z0-9_.\-])([a-z][a-z\-]+)\(")


def op_label(text: str) -> str:
    """A device event's name is its whole HLO instruction (`%fusion.4 =
    f32[8,512]{...} fusion(...), kind=kLoop, ...`). The label keeps the
    instruction's name and opcode, and marks a Pallas/Mosaic kernel:
    `fusion.4:fusion`, `jvp__.1:custom-call:tpu_custom_call`."""
    name, _, rest = text.partition(" = ")
    name = name.lstrip("%")
    if not rest:
        return name[:96]
    m = _OPCODE.search(rest)
    label = f"{name}:{m.group(1)}" if m else name
    if 'custom_call_target="tpu_custom_call"' in rest:
        label += ":tpu_custom_call"
    return label[:96]


def is_custom_call(label: str) -> bool:
    return label.endswith(":tpu_custom_call")


def reduce_profile(profile, host_spans=(), begin_host: Optional[float] = None,
                   top: int = 10) -> dict:
    """`profile` is a `jax.profiler.ProfileData`. `host_spans` are
    (name, start_s, end_s) on the host clock on which `begin_host` is the
    reading taken inside the `bench_trace_begin` annotation."""
    t0, t1 = _find_markers(profile)
    devices = []
    for plane in profile.planes:
        if not _is_device(plane.name):
            continue
        events = [(ev, line.name) for line in plane.lines
                  if line.name in (OPS_LINE, ASYNC_LINE, MODULES_LINE)
                  for ev in line.events]
        if any(line == OPS_LINE for _, line in events):
            devices.append((plane.name, events))
    if not devices:
        return {"devices": 0, "busy_s": 0.0, "window_s": None}
    if t0 is None or t1 is None:
        # a trace the harness did not bracket (a recorded fixture from
        # another tool): the window is the span of the device events
        t0 = min(ev.start_ns for _, evs in devices for ev, _ in evs)
        t1 = max(ev.start_ns + ev.duration_ns for _, evs in devices
                 for ev, _ in evs)
    n = len(devices)
    busy_total = custom = coll = exposed = 0.0
    by_op: dict = {}
    first_busy = None
    modules: dict = {}
    for _, events in sorted(devices):
        compute, collective, kernels = [], [], []
        # whole executions of each jitted program inside the window
        runs = [(ev.name.split("(")[0], ev.start_ns,
                 ev.start_ns + ev.duration_ns) for ev, line in events
                if line == MODULES_LINE and ev.start_ns >= t0
                and ev.start_ns + ev.duration_ns <= t1]
        for ev, line in events:
            if line == MODULES_LINE:
                continue
            iv = clip([(ev.start_ns, ev.start_ns + ev.duration_ns)], t0, t1)
            if not iv:
                continue
            label, dur = op_label(ev.name), iv[0][1] - iv[0][0]
            if COLLECTIVE.search(label):
                # on either line: a start-to-done span on the async line
                # is the time the collective is in flight
                collective.extend(iv)
            if line == ASYNC_LINE or label.endswith(CONTAINERS):
                continue
            by_op[label] = by_op.get(label, 0.0) + dur
            if not COLLECTIVE.search(label):
                compute.extend(iv)
                if is_custom_call(label):
                    custom += dur
                    kernels.extend(iv)
        compute_u, coll_u = union(compute), union(collective)
        busy = union([(ev.start_ns, ev.start_ns + ev.duration_ns)
                      for ev, line in events if line == OPS_LINE])
        busy = union(clip(busy, t0, t1))
        busy_total += length(busy)
        coll += length(coll_u)
        exposed += length(subtract(coll_u, compute_u))
        if first_busy is None:
            first_busy = busy
        kernels = union(kernels)
        for name, a, b in runs:
            m = modules.setdefault(name, {"runs": 0, "time": 0.0,
                                          "custom_call": 0.0})
            m["runs"] += 1
            m["time"] += b - a
            m["custom_call"] += length(clip(kernels, a, b))
    ns = 1e-9
    out = {
        "devices": n,
        "window_s": (t1 - t0) * ns,
        "busy_s": busy_total / n * ns,
        "custom_call_s": custom / n * ns,
        "collective_s": coll / n * ns,
        "collective_exposed_s": exposed / n * ns,
        "device_ops": [[k, v / n * ns] for k, v in sorted(
            by_op.items(), key=lambda kv: -kv[1])[:top]],
    }
    if modules:
        name, m = max(modules.items(), key=lambda kv: kv[1]["time"])
        # the program that took most device time: its whole executions in
        # the window, per device, and the kernel time inside them
        out["main_module"] = {
            "name": name, "runs": m["runs"] / n, "time_s": m["time"] / n * ns,
            "custom_call_s": m["custom_call"] / n * ns}
    by_span: dict = {}
    spans = []
    if begin_host is not None:
        spans = [(name, t0 + (a - begin_host) * 1e9,
                  t0 + (b - begin_host) * 1e9) for name, a, b in host_spans]
    idle = gaps(first_busy, t0, t1)
    names = shortest_span_over([(a + b) / 2 for a, b in idle], spans)
    for (a, b), name in zip(idle, names):
        by_span[name] = by_span.get(name, 0.0) + (b - a)
    out["idle_gaps"] = [[k, v * ns] for k, v in sorted(
        by_span.items(), key=lambda kv: -kv[1])[:top]]
    return out


def shortest_span_over(points: Sequence[float], spans) -> List[str]:
    """For each of the ascending `points`, the name of the shortest of the
    (name, start, end) `spans` that covers it (ties: the first name in
    order), or "unattributed". One sweep: a chat run's trace has some
    hundred thousand idle gaps and the run some ten thousand spans, and
    asking every span about every gap took over ten minutes (PR 36)."""
    by_start = sorted(spans, key=lambda span: span[1])
    out, over, i = [], [], 0
    for at in points:
        while i < len(by_start) and by_start[i][1] <= at:
            name, s, e = by_start[i]
            over.append((e - s, name, e))
            i += 1
        over = [span for span in over if span[2] >= at]
        out.append(min(over)[1] if over else "unattributed")
    return out


def reduce_file(path: str, host_spans=(), begin_host=None) -> dict:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path), host_spans,
                          begin_host)


def describe_file(path: str, per_line: int = 6) -> List[str]:
    """Planes, lines and first events of a trace, to read one by hand."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            out.append(f"  LINE {line.name} ({len(events)} events)")
            for ev in events[:per_line]:
                out.append(f"    {ev.name} start={ev.start_ns:.0f} "
                           f"dur={ev.duration_ns:.0f} {dict(ev.stats)}")
    return out
