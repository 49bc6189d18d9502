"""One traced window: start and stop of `jax.profiler`, bracketed by the two
annotations `trace_reduce` looks for, with the host clock read inside each."""

from __future__ import annotations

import glob
import os
import shutil
import time


class TraceWindow:
    def __init__(self, out_dir: str):
        self.out_dir = out_dir
        self.begin_host = self.end_host = None
        self.active = False

    def start(self) -> None:
        import jax

        shutil.rmtree(self.out_dir, ignore_errors=True)
        os.makedirs(self.out_dir, exist_ok=True)
        # no Python tracer: it hooks every call of the host loop that the
        # window is there to observe
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.out_dir, profiler_options=options)
        self.active = True
        with jax.profiler.TraceAnnotation("bench_trace_begin"):
            self.begin_host = time.perf_counter()

    def mark_end(self) -> None:
        """Closes the traced window; the profiler keeps running until
        `stop()`. Stopping it takes seconds of host time, which a serving
        loop that still has requests in flight cannot spare."""
        import jax

        with jax.profiler.TraceAnnotation("bench_trace_end"):
            self.end_host = time.perf_counter()

    def stop(self) -> str:
        """Stops the profiler; returns the path of the `.xplane.pb`."""
        import jax

        if self.end_host is None:
            self.mark_end()
        jax.profiler.stop_trace()
        self.active = False
        found = sorted(glob.glob(os.path.join(
            self.out_dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise RuntimeError(f"the profiler wrote no trace under "
                               f"{self.out_dir}")
        return found[-1]
