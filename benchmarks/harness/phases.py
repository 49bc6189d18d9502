"""The set-up clock and the compile counters.

`setup_s` runs from the runner's first statement (`t0`) to the first timed
step of the window. Its phases are printed on an earlier line of every run,
with the compile requests, persistent-cache hits and misses of set-up and
the compile requests inside the window, so a run that recompiles says so.
"""

from __future__ import annotations

import os
import time

PHASES = ("import_s", "backend_init_s", "build_s", "compile_or_load_s",
          "warmup_s", "lead_s")

_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
}


# The one variable the benchmark sets in the environment of its own process
# before JAX is imported, unless the environment already has it. libtpu pins
# a large host buffer at start-up; with its default size the first
# `jax.devices()` took 7.3 to 14.7 s from run to run on a v5e host without
# transparent hugepages, which was nearly all of the spread of `setup_s` and
# what the first attempt at this benchmark was refused for; at 64 MiB it took
# 1.20 to 1.27 s (benchmarks/tools/probe_backend.py; PERF.md, PR 23). So
# `setup_s` of such a cell leaves out most of a deployment's backend
# start-up. That buffer stages host-to-device copies, so a cell that feeds
# its batches from the host must not shrink it: resnet50-train-dp4 read
# 1,256 records/s at 64 MiB, 1,688 at 512 MiB and 7,675 at 4 GiB. A traffic
# file gives another size, or null for libtpu's default, under
# `tpu_premapped_buffer_mib`; no other variable can be set from data.
PREMAPPED_VAR = "TPU_PREMAPPED_BUFFER_SIZE"
PREMAPPED_MIB = 64


def set_process_env(traffic: dict) -> dict:
    mib = traffic.get("tpu_premapped_buffer_mib", PREMAPPED_MIB)
    if mib is not None:
        os.environ.setdefault(PREMAPPED_VAR, str(int(mib) * 2 ** 20))
    return {PREMAPPED_VAR: os.environ.get(PREMAPPED_VAR)}


class Phases:
    """Consecutive phases on one clock: `mark(name)` closes the phase that
    has been running since the previous mark (or since t0)."""

    def __init__(self, t0: float):
        self.t0 = self._last = t0
        self.seconds = {name: 0.0 for name in PHASES}
        self.window_start = None

    def mark(self, name: str) -> float:
        now = time.perf_counter()
        self.seconds[name] += now - self._last
        self._last = now
        return now

    def open_window(self, last_phase: str) -> float:
        """Close `last_phase`; the window (and nothing of set-up) follows."""
        self.window_start = self.mark(last_phase)
        return self.window_start

    @property
    def setup_s(self) -> float:
        if self.window_start is None:
            raise RuntimeError("the window was never opened")
        return self.window_start - self.t0


class CompileCounters:
    """Counts JAX's persistent-cache events. `backend_compile_duration`
    fires on a hit too, so it is a time, never a miss count; its sum is
    kept as `backend_compile_s`."""

    def __init__(self):
        import jax

        self.counts = {"requests": 0, "hits": 0, "misses": 0}
        self.backend_compile_s = 0.0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_time)

    def _on_event(self, event, **_):
        key = _EVENTS.get(event)
        if key:
            self.counts[key] += 1

    def _on_time(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend_compile_s += duration

    def snapshot(self) -> dict:
        return dict(self.counts, backend_compile_s=self.backend_compile_s)

    @staticmethod
    def delta(after: dict, before: dict) -> dict:
        return {k: after[k] - before[k] for k in after}


def place_compile_cache(root: str) -> str:
    """Every executable goes to the persistent cache, at the directory
    `JAX_COMPILATION_CACHE_DIR` names or else at a fixed path inside the
    checkout (the path is part of the key's world: it never moves). JAX's
    defaults skip executables that compiled in under a second, which a warm
    run then recompiles on the host every time: that was the 20% spread of
    `setup_s` that the first attempt at this benchmark fell on."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
