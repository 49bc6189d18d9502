"""One run of one cell: find the cell's files by the names in
BENCHMARK.json, set the compile cache, find the devices, hand over to the
cell's driver, then reduce what the run recorded to the cell's metrics and
print the contract's last line."""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import time

from benchmarks.harness import device as dev
from benchmarks.harness import manifest as mf
from benchmarks.harness.check import Check
from benchmarks.harness.phases import CompileCounters, Phases, \
    place_compile_cache, set_process_env


_LOADED: dict = {}


def load_part(root: str, sub: str, name: str):
    """The module `benchmarks/<sub>/<name>.py` under `root`, found by the
    name a data file gives: a driver by a traffic file's `kind`, a family
    and its counts by a configuration's `family`, a reader by a per-layer
    metric's name. No registry lists them; adding one is adding a file."""
    path = os.path.join(root, "benchmarks", sub, f"{name}.py")
    if path not in _LOADED:
        if not os.path.isfile(path):
            raise SystemExit(f"no {path}: BENCHMARK.json or a data file "
                             f"names {name!r}, and benchmarks/{sub}/ has no "
                             "such file")
        spec = importlib.util.spec_from_file_location(
            f"benchmarks.{sub}.{name}", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        _LOADED[path] = module
    return _LOADED[path]


class Context:
    """What a driver, a family and a layer-metric reader may read."""

    def __init__(self, root, cell, config, traffic, seed, seconds, trace,
                 phases, counters, devices, out):
        self.root, self.cell, self.config, self.traffic = (
            root, cell, config, traffic)
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.phases, self.counters, self.devices = phases, counters, devices
        self.out = out
        self.check = Check(out)
        self.limits = config.get("limits", {})
        self.family = load_part(root, "families", config["family"])
        self.device = dev.describe(devices)
        self.peaks = dev.peaks_of(root, self.device["kind"],
                                  self.device["platform"])
        self.trace_path = self.trace_window = None
        self.memory_peak = None
        self.trace_summary = None
        self.record = None

    def report_setup(self, at_open: dict, in_window: dict) -> None:
        """The earlier line every run prints: set-up by phase, and the
        compile requests, cache hits and misses of set-up and of the
        window. Drivers call it as soon as the window has closed."""
        import jax

        self.out("setup " + json.dumps({
            "setup_s": self.phases.setup_s, **self.phases.seconds,
            "compile_cache_dir": self.cache_dir, "setup_compiles": at_open,
            "window_compiles": in_window, "jax": jax.__version__}))

    def live_bytes(self) -> int:
        import jax

        return sum(a.nbytes for a in jax.live_arrays())

    def new_trace_window(self):
        from benchmarks.harness.profile import TraceWindow

        self.trace_window = TraceWindow(os.path.join(
            self.root, ".bench_out", f"trace-{self.cell['name']}"))
        return self.trace_window

    def read_memory_peak(self):
        return dev.memory_peak_bytes(self.devices)

    def counts(self):
        """The family's operation counts (benchmarks/counts/<family>.py)."""
        return load_part(self.root, "counts", self.config["family"])


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, t0: float, require_chip: bool = True,
             out=print, control=None) -> dict:
    """Runs the cell and returns the result object (also printed as the
    last line). `require_chip=False` exists for the CPU rehearsal in
    tests/bench, and `control` (the lower precision whose readings the
    limits were set against) for benchmarks/tools/control.py; the command
    line can reach neither."""
    phases = Phases(t0)
    manifest = mf.load(root)
    cell = mf.cell_of(manifest, workload)
    cfg_entry = mf.config_of(manifest, cell)
    config = mf.load_json(root, cfg_entry["file"])
    traffic = mf.load_json(root, mf.traffic_path(cell))
    driver = load_part(root, "drivers", traffic["kind"])
    process_env = set_process_env(traffic)      # before JAX is imported

    cache_dir = place_compile_cache(root)
    counters = CompileCounters()
    phases.mark("import_s")
    devices = dev.find_devices(cell["chips"], require_chip)
    phases.mark("backend_init_s")

    ctx = Context(root, cell, config, traffic, seed, seconds, trace, phases,
                  counters, devices, out)
    ctx.cache_dir, ctx.control = cache_dir, control
    out(f"process environment: {process_env}")
    record = driver.run(ctx)
    ctx.record = record

    if trace and ctx.trace_path:
        from benchmarks.harness import trace_reduce

        ctx.trace_summary = trace_reduce.reduce_file(
            ctx.trace_path, record.get("spans", ()),
            ctx.trace_window.begin_host)
        shutil.rmtree(os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.dirname(ctx.trace_path)))), ignore_errors=True)

    metrics = {}
    if not trace:
        values = dict(record["end_to_end"], setup_s=phases.setup_s)
        for m in mf.metrics_of(manifest, cell, "end_to_end"):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        for m in mf.metrics_of(manifest, cell, "per_layer"):
            value = load_part(root, "layer_metrics", m["name"]).read(ctx)
            if value is not None:       # nothing to read: left out
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = dict(ctx.device, memory_peak_bytes=ctx.memory_peak)
    result = {"correct": ctx.check.correct,
              "attempted": record["attempted"], "failed": record["failed"],
              "metrics": metrics, "device": device}
    ts = ctx.trace_summary
    if trace and ts and ts.get("devices") and device["platform"] == "tpu":
        device["busy_s"], device["window_s"] = ts["busy_s"], ts["window_s"]
        result["breakdown"] = {"device_ops": ts["device_ops"],
                               "idle_gaps": ts["idle_gaps"]}
    # each number compared beside its limit: last in the result's line, and
    # the last lines of standard error
    result["checks"] = ctx.check.report()
    for name, row in result["checks"].items():
        print(f"check {name}: {row['value']!r} limit {row['limit']!r} "
              f"{'ok' if row['ok'] else 'NOT OK'}", file=sys.stderr)
    out(json.dumps(result))
    return result
