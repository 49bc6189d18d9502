"""Driver of kind `train`: one cell's model through the program's own
`Optimizer(...).optimize()`, for a fixed number of warm-up steps and then a
window of `--seconds`.

ONE object is built, driven from the seed through its first steps and
handed to the window: `optimize()` is called once, and the benchmark's two
hooks ride on the program's own extension points, the end trigger (called
once an iteration) and the checkpoint trigger (which hands over parameters
and optimizer state):

  steps 1..3   compared with the plain reference afterwards: each loss, the
               first gradient as the optimizer got it (from its state after
               step one), the parameters' change after step three;
  step K       `warmup_steps`: fence on the loss, set-up ends, window opens;
  window       steps counted between fences (`float(loss)`); at the first
               iteration past `--seconds` a last fence closes it.

`attempted` is the steps started in the window, `failed` those whose loss
was not finite. Throughput is records of whole steps over the time between
the two fences: every step counted lies wholly inside them.
"""

from __future__ import annotations

import gc
import math
import time

COMPARED_STEPS = 3


class _Pool:
    """The program's dataset interface over a rotating pool of batches."""

    def __init__(self, batches):
        from bigdl_tpu.dataset.sample import MiniBatch

        self._batches = [MiniBatch(x, y) for x, y in batches]

    def size(self) -> int:
        return 1 << 60                  # never an epoch's end

    def data(self, train: bool):
        i = 0
        while True:
            yield self._batches[i % len(self._batches)]
            i += 1


class _Hooks:
    """End trigger and checkpoint sink of the one `optimize()` call."""

    sharded = False                     # the checkpoint surface it stands in

    def __init__(self, ctx, job, tracing):
        self.ctx, self.job, self.tracing = ctx, job, tracing
        self.warmup = ctx.traffic["warmup_steps"]
        if self.warmup < COMPARED_STEPS:
            raise ValueError("warmup_steps must cover the compared steps")
        self.t_start = self.t_end = None
        self.steps = 0
        self.captured = {}
        self.compiles_at_open = None
        self._slot_norms = self._delta_norms = None

    # ---- end trigger: once an iteration, before the step is dispatched
    def end_when(self, state) -> bool:
        n, ph = state["neval"], self.ctx.phases
        if self.t_start is None:
            if n == 1 and "first" not in self.captured:
                float(state["loss"])                    # fence
                ph.mark("compile_or_load_s")
                self.captured["first"] = True
            if n == self.warmup:
                float(state["loss"])                    # fence: set-up ends
                self.compiles_at_open = self.ctx.counters.snapshot()
                self.t_start = ph.open_window("warmup_s")
            return False
        elapsed = time.perf_counter() - self.t_start
        if (self.tracing is not None and not self.tracing.active
                and self.ctx.trace_path is None and elapsed >=
                self.ctx.seconds - self.ctx.traffic["trace_seconds"]):
            float(state["loss"])        # the trace covers the window's end
            self.tracing.start()
        if elapsed >= self.ctx.seconds:
            float(state["loss"])                        # fence: window ends
            self.t_end = time.perf_counter()
            self.steps = n - self.warmup
            if self.tracing is not None and self.tracing.active:
                self.ctx.trace_path = self.tracing.stop()
            return True
        return False

    # ---- checkpoint surface: the program hands over its state
    def trigger(self, state) -> bool:
        return self.t_start is None and state["neval"] in (1, COMPARED_STEPS)

    def save(self, neval, variables, slots, train_meta, optim_meta=None,
             accum_state=None):
        import jax
        import jax.numpy as jnp

        from benchmarks.reference.optim import leaf_norms as norms

        job = self.job
        if neval == 1:
            params = variables["params"]
            self._slot_norms = jax.jit(
                lambda s, p: norms(job.slot_leaves(s, p)))(slots, params)
        else:
            # the seed is an argument, not a constant: one cached program
            self._delta_norms = jax.jit(lambda p, s: norms(
                jax.tree_util.tree_map(jnp.subtract, job.param_leaves(p),
                                       job.initial_leaves(s))))(
                variables["params"], self.ctx.family.u32(self.ctx.seed))
        return f"bench-hook-{neval}"

    def wait(self):
        return None

    def latest(self):
        return None

    def program_numbers(self, losses) -> dict:
        return {"loss": losses[:COMPARED_STEPS],
                "slot": {k: float(v) for k, v in self._slot_norms.items()},
                "delta": {k: float(v) for k, v in self._delta_norms.items()}}


def run(ctx) -> dict:
    import jax

    from bigdl_tpu import obs
    from bigdl_tpu.optim import Optimizer

    traffic = ctx.traffic
    job = ctx.family.TrainJob(ctx.seed, ctx.config, traffic, ctx.devices)
    jax.block_until_ready(job.model.variables)
    ctx.phases.mark("build_s")

    if ctx.trace:
        obs.set_tracer(obs.SpanTracer(enabled=True, capacity=1 << 20))
    hooks = _Hooks(ctx, job, ctx.new_trace_window() if ctx.trace else None)
    opt = (Optimizer(job.model, _Pool(job.batches), job.criterion,
                     batch_size=traffic["batch"])
           .set_optim_method(job.method)
           .set_precision(job.precision)
           .set_end_when(hooks.end_when))
    opt.checkpoint, opt.checkpoint_trigger = hooks, hooks.trigger
    if job.mesh is not None:
        opt.set_mesh(job.mesh, zero=job.zero)
    seen = len(obs.get_event_log().events("train_step"))
    opt.optimize()

    window_s = hooks.t_end - hooks.t_start
    in_window = ctx.counters.delta(ctx.counters.snapshot(),
                                   hooks.compiles_at_open)
    ctx.report_setup(hooks.compiles_at_open, in_window)
    events = obs.get_event_log().events("train_step")[seen:]
    losses = {e["step"]: e.get("loss") for e in events}
    first = [losses.get(i) for i in range(1, COMPARED_STEPS + 1)]
    window_losses = [losses.get(i) for i in range(
        hooks.warmup + 1, hooks.warmup + hooks.steps + 1)]
    failed = sum(1 for l in window_losses
                 if l is None or not math.isfinite(l))
    records = hooks.steps * traffic["batch"]
    ctx.memory_peak = ctx.read_memory_peak()
    spans = [(e["name"], e["ts"] / 1e6, (e["ts"] + e["dur"]) / 1e6)
             for e in obs.get_tracer().events() if e.get("ph") == "X"]
    program = hooks.program_numbers(first)

    # the program's state goes before the reference's comes
    job.model.variables = None
    opt = job.model = None
    gc.collect()
    ctx.out(f"live bytes before the reference: {ctx.live_bytes()}")

    t_ref = time.perf_counter()
    reference = job.reference_steps(COMPARED_STEPS)
    from benchmarks.harness import check as chk

    numbers = chk.train_numbers(program, reference)
    if ctx.control:
        lower = job.reference_steps(COMPARED_STEPS, precision=ctx.control)
        ctx.out(f"control {ctx.control}: " + str(
            chk.train_numbers(lower, reference)))
    limits = ctx.limits["train"]
    for name, value in numbers.items():
        ctx.check.compare(name, value, limits[name])
    ctx.check.require("steps_in_window", hooks.steps > 0 and failed == 0,
                      f"{hooks.steps} steps, {failed} not finite")
    ctx.check.require("no_compile_in_window", in_window["requests"] == 0,
                      str(in_window))
    ctx.out(f"reference: {COMPARED_STEPS} steps in "
            f"{time.perf_counter() - t_ref:.1f} s; program losses {first}, "
            f"reference losses {reference['loss']}")
    return {
        "attempted": hooks.steps, "failed": failed,
        "window": (hooks.t_start, hooks.t_end), "window_s": window_s,
        "compiles_at_open": hooks.compiles_at_open,
        "compiles_in_window": in_window, "spans": spans,
        "end_to_end": {"train_throughput": records / window_s},
        "counters": {"steps": hooks.steps, "records": records,
                     "batch": traffic["batch"],
                     "chips": len(ctx.devices)},
    }
