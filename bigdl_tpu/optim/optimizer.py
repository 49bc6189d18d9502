"""Optimizer front-end and single-host training loop.

Reference parity: optim/Optimizer.scala (builder surface: `setOptimMethod`,
`setEndWhen`, `setValidation`, `setCheckpoint`, `setTrainSummary`,
`optimize`, dispatch Local vs Distri) and optim/LocalOptimizer.scala.

TPU-first redesign: the reference's LocalOptimizer clones the model across
cores and hand-splits each MiniBatch; here intra-chip parallelism belongs
to XLA — ONE jitted train step owns the whole batch. The step is pure:

    (params, mod_state, slots, batch, lr, step#, rng)
        -> (params', mod_state', slots', loss)

Distributed training subclasses this loop and swaps the step function for
the mesh-sharded one (bigdl_tpu/parallel/distri_optimizer.py), exactly
the Local/Distri split the reference has.
"""

from __future__ import annotations

import logging
import sys
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu import obs
from bigdl_tpu.dataset.dataset import AbstractDataSet
from bigdl_tpu.dataset.sample import MiniBatch, Sample
from bigdl_tpu.dataset.transformer import SampleToMiniBatch
from bigdl_tpu.nn.module import Criterion, Module
from bigdl_tpu.optim.metrics import Metrics, Timer
from bigdl_tpu.optim.optim_method import OptimMethod, SGD
from bigdl_tpu.optim.trigger import Trigger
from bigdl_tpu.optim.validation import ValidationMethod, ValidationResult
from bigdl_tpu.serialization.checkpoint import Checkpoint

logger = logging.getLogger("bigdl_tpu.optim")


def _batch_iterator(dataset: AbstractDataSet, train: bool,
                    batch_size: Optional[int], skip: int = 0):
    """Yield MiniBatch from a dataset that may produce Samples or
    MiniBatches.

    `skip`: fast-forward past the first `skip` batches — resume support.
    Training datasets replay deterministic epoch permutations from their
    seed, so skipping the batches a checkpointed run already consumed
    re-aligns the stream and makes resumed training bit-for-bit equal to
    the uninterrupted run. Samples are skipped without stacking (train
    streams are infinite, every batch is full), so the cost is bare
    iteration.

    Training streams pass through the fault-injection point
    `data@<position>` (utils/faults): a data-loader failure fires when
    the batch at that global stream position (skip + local index — the
    step number that will consume it) is fetched, so injected loader
    faults are deterministic across resumes."""
    it = dataset.data(train=train)
    first = next(it, None)
    if first is None:
        return iter(())
    import itertools

    chained = itertools.chain([first], it)
    if isinstance(first, MiniBatch):
        for _ in range(skip):
            next(chained, None)
        return _fault_gate(chained, skip) if train else chained
    if batch_size is None:
        raise ValueError("dataset yields Samples; batch_size is required")
    for _ in range(skip * batch_size):
        next(chained, None)
    batched = SampleToMiniBatch(batch_size)(chained)
    return _fault_gate(batched, skip) if train else batched


def _fault_gate(it, start: int):
    """Wrap a training batch stream with the `data` fault point; the
    skip fast-forward is NOT gated (replays must not re-fire)."""
    from bigdl_tpu.utils import faults

    def gen():
        pos = start
        for mb in it:
            faults.get_plan().maybe_raise("data", pos)
            pos += 1
            yield mb

    return gen()


def _to_device(x):
    if x is None:
        return None
    if isinstance(x, tuple):
        return tuple(jnp.asarray(e) for e in x)
    return jnp.asarray(x)


class Optimizer:
    """Builder facade (reference: optim/Optimizer.scala#Optimizer.apply)."""

    def __init__(self, model: Module, dataset: AbstractDataSet,
                 criterion: Criterion, batch_size: Optional[int] = None,
                 seed: int = 42):
        self.model = model
        self.dataset = dataset
        self.criterion = criterion
        self.batch_size = batch_size
        self.seed = seed
        self.optim_method: OptimMethod = SGD(learningrate=1e-2)
        self.end_when: Trigger = Trigger.max_epoch(1)
        self.validation_trigger: Optional[Trigger] = None
        self.validation_dataset: Optional[AbstractDataSet] = None
        self.validation_methods: List[ValidationMethod] = []
        self.validation_batch_size: Optional[int] = None
        self.checkpoint: Optional[Checkpoint] = None
        self.checkpoint_trigger: Optional[Trigger] = None
        self.train_summary = None
        self.validation_summary = None
        self.grad_clip_const: Optional[tuple] = None
        self.grad_clip_norm: Optional[float] = None
        self.log_every = 1
        self._resume = False
        self.mesh = None
        self.mesh_axis = "data"
        self.mesh_zero = 1  # 2 = ZeRO-2 weight sharding (set_mesh)
        self.precision = None  # None → full fp32; Policy → mixed precision
        self.grad_accum = 1
        self.anomaly_guard = None  # utils.anomaly.AnomalyGuard or None

    # ------------------------------------------------------- builder surface
    def set_optim_method(self, method: OptimMethod) -> "Optimizer":
        self.optim_method = method
        return self

    def set_end_when(self, trigger: Trigger) -> "Optimizer":
        self.end_when = trigger
        return self

    def set_validation(self, trigger: Trigger, dataset: AbstractDataSet,
                       methods: Sequence[ValidationMethod],
                       batch_size: Optional[int] = None) -> "Optimizer":
        self.validation_trigger = trigger
        self.validation_dataset = dataset
        self.validation_methods = list(methods)
        self.validation_batch_size = batch_size or self.batch_size
        return self

    def set_checkpoint(self, path: str, trigger: Trigger,
                       sharded: bool = False,
                       async_save: bool = False) -> "Optimizer":
        """`sharded=True` saves the ZeRO flat optimizer state as
        per-shard units with a manifest-last publish (mesh runs only —
        ISSUE 9; resume reshards across world sizes); `async_save=True`
        moves checkpoint I/O to a background thread so steps never
        stall on disk (serialization/checkpoint.py)."""
        self.checkpoint = Checkpoint(path, sharded=sharded,
                                     async_save=async_save)
        self.checkpoint_trigger = trigger
        return self

    def resume_from_checkpoint(self) -> "Optimizer":
        """Continue from the latest checkpoint under the checkpoint path
        (reference: Optimizer resume + DistriOptimizer retry recovery)."""
        self._resume = True
        return self

    @staticmethod
    def _coerce_summary(summary, cls):
        if isinstance(summary, str):
            return cls(summary, "bigdl_tpu")
        if not hasattr(summary, "add_scalar"):
            raise TypeError(
                f"expected a {cls.__name__} (or a logdir string), got "
                f"{type(summary).__name__}")
        return summary

    def set_train_summary(self, summary) -> "Optimizer":
        from bigdl_tpu.visualization import TrainSummary

        self.train_summary = self._coerce_summary(summary, TrainSummary)
        return self

    def set_validation_summary(self, summary) -> "Optimizer":
        from bigdl_tpu.visualization import ValidationSummary

        self.validation_summary = self._coerce_summary(summary, ValidationSummary)
        return self

    def set_gradient_accumulation(self, n: int) -> "Optimizer":
        """Accumulate gradients over `n` micro-batches before each
        optimizer update (effective batch = n × batch_size). TPU-first
        addition (absent in the reference, which scales batch via Spark
        partitions): lets a single chip train at pod-scale batch sizes
        without holding the activations of the full batch."""
        if n < 1:
            raise ValueError("accumulation steps must be >= 1")
        self.grad_accum = n
        return self

    def set_precision(self, policy) -> "Optimizer":
        """Enable mixed precision. `policy` is a `utils.precision.Policy`,
        or one of "bf16"/"mixed" (bf16 compute, fp32 master weights) /
        "fp32" (TPU-first replacement for the reference's FP16 gradient
        wire compression — see utils/precision.py)."""
        from bigdl_tpu.utils.precision import DEFAULT_MIXED, Policy

        if isinstance(policy, str):
            policy = {"bf16": DEFAULT_MIXED, "mixed": DEFAULT_MIXED,
                      "fp32": None}[policy]
        elif policy is not None and not isinstance(policy, Policy):
            raise TypeError(f"expected Policy or str, got {type(policy)}")
        self.precision = policy
        return self

    def set_anomaly_guard(self, guard="skip_step", **kwargs) -> "Optimizer":
        """Arm the numeric-anomaly guard (utils/anomaly.py): every train
        step checks loss + global grad-norm finiteness (and, with
        `spike_factor`, a norm-spike threshold) inside the jitted step
        and discards anomalous updates on device. `guard` is an
        AnomalyGuard, a policy string ('skip_step' | 'rollback' |
        'halt'; kwargs forward to AnomalyGuard), or None to disarm.
        The reference has no such monitoring — a NaN loss silently
        poisons the weights; TensorFlow's health-monitoring contract
        (arXiv 1605.08695 §4.3) is the model here."""
        from bigdl_tpu.utils.anomaly import AnomalyGuard

        if isinstance(guard, str):
            guard = AnomalyGuard(policy=guard, **kwargs)
        elif guard is not None and not isinstance(guard, AnomalyGuard):
            raise TypeError(
                f"expected AnomalyGuard, policy str or None, got "
                f"{type(guard).__name__}")
        elif kwargs:
            raise ValueError("kwargs only apply when guard is a policy str")
        self.anomaly_guard = guard
        return self

    def set_constant_gradient_clipping(self, min_v: float, max_v: float) -> "Optimizer":
        self.grad_clip_const = (min_v, max_v)
        return self

    def set_gradient_clipping_by_l2_norm(self, clip_norm: float) -> "Optimizer":
        self.grad_clip_norm = clip_norm
        return self

    def set_mesh(self, mesh, axis: str = "data",
                 zero: int = 1) -> "Optimizer":
        """Train data-parallel over a device mesh — switches dispatch to
        DistriOptimizer (the reference dispatches Local vs Distri on the
        dataset type; here the mesh is the explicit signal). `zero=2`
        shards the master fp32 weights across the axis too (ZeRO-2,
        arXiv 2004.13336): 1/n weight residency per device, bit-
        identical fp32 results (parallel/data_parallel.py)."""
        if zero not in (1, 2):
            raise ValueError(f"zero must be 1 or 2, got {zero!r}")
        self.mesh = mesh
        self.mesh_axis = axis
        self.mesh_zero = zero
        return self

    # ------------------------------------------------------------- dispatch
    def optimize(self) -> Module:
        try:
            if self.mesh is not None:
                from bigdl_tpu.parallel.distri_optimizer import \
                    DistriOptimizer

                return DistriOptimizer(
                    self, self.mesh, self.mesh_axis,
                    zero=getattr(self, "mesh_zero", 1)).run()
            if self.checkpoint is not None and self.checkpoint.sharded:
                raise ValueError(
                    "sharded checkpoints shard the ZeRO flat optimizer "
                    "state — they need a mesh (set_mesh); a local run "
                    "can still RESUME from one (the flat layout "
                    "unflattens)")
            return LocalOptimizer(self).run()
        except BaseException:
            # dying run: drain the background checkpoint writer so a
            # restart never races a still-live write of this process
            # (whatever the writer had PUBLISHED before the death
            # exists; an unpublished save stays torn — no MANIFEST —
            # and is skipped by latest()). A secondary writer error
            # here is swallowed: the primary exception is the story,
            # and writer errors surface on their own save()/wait() path
            if self.checkpoint is not None:
                try:
                    self.checkpoint.wait()
                except Exception:
                    pass
            raise


class LocalOptimizer:
    """Single-host jitted training loop (reference: optim/LocalOptimizer.scala).

    Also the base for DistriOptimizer: subclasses override `_make_step`
    and `_make_eval` to insert mesh sharding/collectives.
    """

    def __init__(self, opt: Optimizer):
        self.o = opt
        self.metrics = Metrics()
        # ONE emission path for step telemetry: registry + event log +
        # TrainSummary sink + log line (obs/training.py; ISSUE 5 — the
        # summary scalars and the log line used to be written by two
        # separate blocks here and in DistriOptimizer)
        from bigdl_tpu.obs.training import StepTelemetry

        self.telemetry = StepTelemetry(summary=opt.train_summary,
                                       log_every=opt.log_every)

    # --------------------------------------------------------- step builders
    def _make_step(self) -> Callable:
        model, criterion, method = self.o.model, self.o.criterion, self.o.optim_method
        clip_const, clip_norm = self.o.grad_clip_const, self.o.grad_clip_norm
        precision = self.o.precision
        accum = self.o.grad_accum
        guarded = self.o.anomaly_guard is not None

        from bigdl_tpu.ops.losses import build_train_loss

        loss_call = build_train_loss(model, criterion, precision)

        def grads_of(params, mod_state, bx, by, rng):
            return jax.value_and_grad(
                lambda p: loss_call(p, mod_state, bx, by, rng),
                has_aux=True)(params)

        def clip_and_update(grads, params, slots, lr, stepno):
            if clip_const is not None:
                lo, hi = clip_const
                grads = jax.tree_util.tree_map(
                    lambda g: jnp.clip(g, lo, hi), grads)
            if clip_norm is not None:
                gnorm = jnp.sqrt(sum(jnp.sum(g * g)
                                     for g in jax.tree_util.tree_leaves(grads)))
                scale = jnp.minimum(1.0, clip_norm / jnp.maximum(gnorm, 1e-12))
                grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
            return method.update(grads, params, slots, lr, stepno)

        if accum == 1:
            if guarded:
                from bigdl_tpu.utils.anomaly import (
                    global_norm, health_ok, select_update)

                def gstep(params, mod_state, slots, bx, by, lr, stepno,
                          rng, max_gnorm):
                    (loss, new_state), grads = grads_of(params, mod_state,
                                                        bx, by, rng)
                    gnorm = global_norm(grads)  # pre-clip, like the guard
                    ok = health_ok(loss, gnorm, max_gnorm)
                    new_params, new_slots = clip_and_update(
                        grads, params, slots, lr, stepno)
                    # anomalous step: every output is the bit-identical
                    # input — params, slots AND module state keep their
                    # pre-step values on device
                    return (select_update(ok, new_params, params),
                            select_update(ok, new_state, mod_state),
                            select_update(ok, new_slots, slots),
                            loss, ok, gnorm)

                return jax.jit(gstep, donate_argnums=(0, 2))

            def step(params, mod_state, slots, bx, by, lr, stepno, rng):
                (loss, new_state), grads = grads_of(params, mod_state, bx,
                                                    by, rng)
                new_params, new_slots = clip_and_update(grads, params,
                                                        slots, lr, stepno)
                return new_params, new_state, new_slots, loss

            return jax.jit(step, donate_argnums=(0, 2))

        # gradient accumulation: grads-only micro-steps, update every
        # `accum`-th call (Optimizer.set_gradient_accumulation)
        grad_fn = jax.jit(grads_of)
        add_fn = jax.jit(lambda a, g: jax.tree_util.tree_map(
            jnp.add, a, g), donate_argnums=(0,))
        upd_fn = jax.jit(
            lambda acc, params, slots, lr, stepno, n: clip_and_update(
                jax.tree_util.tree_map(lambda g: g / n, acc),
                params, slots, lr, stepno),
            donate_argnums=(0, 1, 2))
        micro = {"acc": None, "n": 0}
        if guarded:
            from bigdl_tpu.utils.anomaly import global_norm, health_ok

            def _health(loss, grads, thr):
                g = global_norm(grads)
                return health_ok(loss, g, thr), g

            health_fn = jax.jit(_health)

        def step(params, mod_state, slots, bx, by, lr, stepno, rng,
                 max_gnorm=None):
            (loss, new_state), grads = grad_fn(params, mod_state, bx, by,
                                               rng)
            if guarded:
                ok, gnorm = health_fn(loss, grads, max_gnorm)
                if not bool(ok):
                    # anomalous micro-batch: its gradients never touch
                    # the accumulator and the NaN-tainted module state
                    # is dropped; the cycle extends by one batch
                    return params, mod_state, slots, loss, ok, gnorm
            micro["acc"] = grads if micro["acc"] is None \
                else add_fn(micro["acc"], grads)
            micro["n"] += 1
            if micro["n"] == accum:
                params, slots = upd_fn(micro["acc"], params, slots, lr,
                                       stepno,
                                       jnp.asarray(accum, jnp.float32))
                micro["acc"], micro["n"] = None, 0
            if guarded:
                return params, new_state, slots, loss, ok, gnorm
            return params, new_state, slots, loss

        def flush(params, slots, lr, stepno):
            """Apply a pending partial accumulator (end trigger fired
            mid-cycle): mean over the micro-batches actually seen, so no
            gradient work is silently discarded."""
            if micro["n"] == 0:
                return params, slots
            params, slots = upd_fn(micro["acc"], params, slots, lr,
                                   stepno,
                                   jnp.asarray(micro["n"], jnp.float32))
            micro["acc"], micro["n"] = None, 0
            return params, slots

        def restore_micro(acc, n):
            """Reinstall a checkpointed mid-cycle accumulator (resume).
            A checkpoint from a run with a LARGER grad_accum can hold
            n >= this run's accum; the `n == accum` update check would
            then never fire again — refuse and restart the cycle."""
            if int(n) >= accum:
                logger.warning(
                    "checkpointed accumulation cycle (%d micro-batches) "
                    "does not fit grad_accum=%d; discarding the partial "
                    "accumulator and restarting the cycle", int(n), accum)
                return
            micro["acc"], micro["n"] = acc, int(n)

        step.flush = flush
        step.micro_state = lambda: (micro["acc"], micro["n"])
        step.restore_micro = restore_micro
        step.clear_micro = lambda: micro.update(acc=None, n=0)
        return step

    def _make_eval(self) -> Callable:
        model, methods = self.o.model, self.o.validation_methods
        precision = self.o.precision

        def eval_step(params, mod_state, bx, by, real_size):
            if precision is not None:
                params = precision.cast_to_compute(params)
                bx = precision.cast_to_compute(bx)
            out, _ = model.apply({"params": params, "state": mod_state}, bx,
                                 training=False)
            if precision is not None:
                out = precision.cast_to_output(out)
            return [m.stats(out, by, real_size) for m in methods]

        return jax.jit(eval_step, static_argnums=(4,))

    # ------------------------------------------------------------ validation
    def _validate(self, variables) -> Dict[str, ValidationResult]:
        o = self.o
        eval_step = self._eval_step
        results = [ValidationResult(0.0, 0.0, m.name) for m in o.validation_methods]
        for mb in _batch_iterator(o.validation_dataset, False,
                                  o.validation_batch_size):
            real = getattr(mb, "real_size", mb.size)
            stats = eval_step(variables["params"], variables["state"],
                              _to_device(mb.input), _to_device(mb.target), real)
            for i, (s, c) in enumerate(stats):
                results[i] = results[i] + ValidationResult(float(s), float(c))
        return {m.name: r for m, r in zip(o.validation_methods, results)}

    def _require_rollback_checkpoint(self) -> None:
        """The anomaly guard's 'rollback' policy has nothing to roll
        back to without a saved checkpoint — shared precondition of the
        local and distributed run loops."""
        from bigdl_tpu.utils.anomaly import AnomalyError

        o = self.o
        if o.checkpoint is None or not o.checkpoint.latest():
            raise AnomalyError(
                "anomaly policy 'rollback' needs a checkpoint "
                "(set_checkpoint) with at least one save; none found")

    # ------------------------------------------------------------------ run
    def run(self) -> Module:
        o = self.o
        rng = jax.random.PRNGKey(o.seed)
        variables = dict(o.model.variables)  # uses existing build or default init
        slots = o.optim_method.init_slots(variables["params"])
        # "nupdates" counts optimizer updates actually APPLIED — it is
        # the stepno/schedule clock. Without the anomaly guard it always
        # equals neval // grad_accum; with the guard, a discarded update
        # (skip_step) or uncounted micro-batch does NOT advance it, so
        # Adam bias correction and LR schedules never skip a step index
        # over an anomaly.
        train_state: Dict[str, Any] = {"epoch": 1, "neval": 0,
                                       "nupdates": 0, "records": 0,
                                       "loss": None, "score": None}
        guard = o.anomaly_guard

        from bigdl_tpu.utils import faults

        plan = faults.get_plan()
        batches = None  # built below; restore() rebuilds it on rollback

        def restore_from_checkpoint(rebuild_stream=True):
            """Reload model/optim/train_state from the newest VALID
            checkpoint (Checkpoint.load falls back past corrupt dirs);
            returns the saved mid-cycle accumulator (or None). Used at
            startup resume and by the anomaly guard's rollback policy."""
            nonlocal variables, slots, batches
            o.checkpoint.wait()  # surface any pending async-save error
            variables, slots, saved, optim_meta = o.checkpoint.load(
                with_optim_meta=True)
            flat_layout = (optim_meta or {}).get("layout") in (
                "zero1_flat", "zero2_flat")
            spec = None
            if flat_layout:
                # checkpoint written by DistriOptimizer: each slot is a flat
                # (padded,) vector over the whole parameter set — unflatten
                # back to the params-pytree layout this loop uses
                from bigdl_tpu.parallel.data_parallel import FlatParamSpec

                spec = FlatParamSpec(variables["params"],
                                     optim_meta["num_shards"])
                slots = jax.tree_util.tree_map(spec.unflatten, slots)
            saved_accum = o.checkpoint.load_accum()
            if saved_accum is not None and flat_layout:
                saved_accum = {"g_acc": spec.unflatten(saved_accum["g_acc"]),
                               "micro_n": saved_accum["micro_n"]}
            train_state.update(saved)
            if "nupdates" not in saved:  # pre-counter checkpoint
                train_state["nupdates"] = \
                    train_state["neval"] // o.grad_accum
            if rebuild_stream:
                batches = _batch_iterator(o.dataset, True, o.batch_size,
                                          skip=train_state["neval"])
            return saved_accum

        # host mirror of the step closure's micro-batch count — drives
        # the nupdates increment at each completed accumulation cycle
        micro_seen = [0]

        def install_accum(saved_accum):
            micro_seen[0] = 0
            if saved_accum is None:
                return
            if hasattr(self._step, "restore_micro"):
                self._step.restore_micro(saved_accum["g_acc"],
                                         int(saved_accum["micro_n"]))
                # mirror what restore_micro actually installed — it
                # refuses (leaves 0) a cycle that doesn't fit this
                # run's grad_accum
                micro_seen[0] = int(self._step.micro_state()[1])
            else:
                logger.warning(
                    "checkpoint holds a mid-cycle accumulator (%d "
                    "micro-batches) but this run has grad_accum=1; the "
                    "partial gradients are discarded",
                    int(saved_accum["micro_n"]))

        saved_accum = None
        if o._resume and o.checkpoint is not None and o.checkpoint.latest():
            saved_accum = restore_from_checkpoint(rebuild_stream=False)
            logger.info("resumed from %s at %s",
                        o.checkpoint._last_loaded, train_state)

        self._step = self._make_step()
        install_accum(saved_accum)
        if o.validation_methods:
            self._eval_step = self._make_eval()

        dataset_size = o.dataset.size()
        # fast-forward the deterministic batch stream to where the
        # checkpointed run stopped: resumed training sees the same
        # batches the uninterrupted run would have
        batches = _batch_iterator(o.dataset, True, o.batch_size,
                                  skip=train_state["neval"])
        pending = None  # deferred (epoch, neval, loss, lr, thr, vars)
        epoch_start = time.perf_counter()
        iter_start = time.perf_counter()

        while not o.end_when(train_state):
            try:
                plan.maybe_preempt(train_state["neval"])
            except faults.Preempted:
                # the worker is dead, not retryable — record the
                # incident (the flight recorder's training-plane
                # trigger, ISSUE 11) and let it propagate
                obs.emit_event("preempted", plane="training",
                               step=train_state["neval"])
                raise
            plan.maybe_raise("step", train_state["neval"])
            with Timer(self.metrics, "data_fetch_s"):
                mb = next(batches)
            if plan.fires("nan", train_state["neval"]):
                mb = faults.poison_minibatch(mb)
            step_rng = jax.random.fold_in(rng, train_state["neval"])
            # schedules and the optimizer's step counter advance per
            # APPLIED update, not per (micro-)batch: a guard-discarded
            # update re-uses its step index, so the schedule clock
            # never skips over an anomaly
            eff_step = train_state["nupdates"]
            lr_state = train_state if o.grad_accum == 1 and guard is None \
                else {**train_state, "neval": eff_step}
            lr = o.optim_method.current_rate(lr_state)
            with Timer(self.metrics, "dispatch_s"):
                # dispatch = h2d_place + the step's call (PERF.md §3)
                with Timer(self.metrics, "h2d_place_s"):
                    x, y = _to_device(mb.input), _to_device(mb.target)
                step_args = (
                    variables["params"], variables["state"], slots,
                    x, y,
                    jnp.asarray(lr, jnp.float32),
                    jnp.asarray(eff_step, jnp.int32),
                    step_rng)
                if guard is None:
                    (variables["params"], variables["state"], slots,
                     loss) = self._step(*step_args)
                else:
                    (variables["params"], variables["state"], slots, loss,
                     ok_d, gnorm_d) = self._step(
                        *step_args,
                        jnp.asarray(guard.threshold(), jnp.float32))
            ok_host, gnorm_host = True, None
            if guard is not None:
                # scalar fetch syncs the step — the documented cost of
                # arming the guard (utils/anomaly.py); an anomalous
                # update was already discarded on device either way
                ok_host, gnorm_host = bool(ok_d), float(gnorm_d)
                action = guard.observe(ok_host, gnorm_host,
                                       train_state["neval"])
                if action == "rollback":
                    self._require_rollback_checkpoint()
                    saved_accum = restore_from_checkpoint()
                    if hasattr(self._step, "clear_micro"):
                        self._step.clear_micro()
                    install_accum(saved_accum)
                    continue
            # NOTE: `loss` stays a device array — converting here would
            # block the host on every step and kill async dispatch
            # pipelining. Log/summary emission for step N happens after
            # step N+1 is dispatched (see _emit below), so the loss fetch
            # overlaps the next step's device compute instead of stalling.
            real = getattr(mb, "real_size", mb.size)
            train_state["neval"] += 1
            # advance the update clock only when an update was (or, for
            # a mid-cycle micro-batch, will be) applied: anomalous
            # steps/micro-batches were discarded on device
            if o.grad_accum == 1:
                train_state["nupdates"] += 1 if guard is None \
                    else int(ok_host)
            elif guard is None or ok_host:
                micro_seen[0] += 1
                if micro_seen[0] == o.grad_accum:
                    train_state["nupdates"] += 1
                    micro_seen[0] = 0
            train_state["records"] += real
            train_state["loss"] = loss
            now = time.perf_counter()
            iter_wall = now - iter_start
            iter_start = now
            self.metrics.add("iter_s", iter_wall)
            throughput = real / max(iter_wall, 1e-9)

            if pending is not None:
                self._emit(pending)
            # snapshot the dicts: the loop reassigns variables["params"]
            # next iteration, and _emit must see step-N state, not N+1.
            # Histograms are materialized HERE (np.asarray = host fetch):
            # step-N's param buffers are donated to step N+1's dispatch,
            # so by _emit time the arrays would already be deleted. The
            # fetch blocks until step N finishes — acceptable for a
            # histogram trigger that fires rarely.
            hists = None
            if o.train_summary is not None:
                pt = o.train_summary.get_summary_trigger("Parameters")
                if pt is not None and pt(train_state):
                    hists = [(name, np.asarray(leaf)) for name, leaf
                             in o.model.parameters(variables)]
            pending = (dict(train_state), loss, lr, throughput, real,
                       hists, gnorm_host, ok_host)

            # ---- epoch rollover (the reference counts records vs dataset size)
            if train_state["records"] >= dataset_size:
                train_state["epoch"] += 1
                train_state["records"] = 0
                logger.info("epoch %d done in %.1fs",
                            train_state["epoch"] - 1,
                            time.perf_counter() - epoch_start)
                epoch_start = time.perf_counter()

            # ---- validation
            if (o.validation_trigger is not None
                    and o.validation_trigger(train_state)):
                res = self._validate(variables)
                for name, r in res.items():
                    v, n = r.result()
                    logger.info("validation %s = %.6f (%d)", name, v, n)
                    if o.validation_summary is not None:
                        o.validation_summary.add_scalar(name, v, train_state["neval"])
                first = next(iter(res.values()), None)
                if first is not None:
                    train_state["score"] = first.result()[0]
                    sched = o.optim_method.schedule
                    if hasattr(sched, "on_metric"):
                        sched.on_metric(train_state["score"])

            # ---- checkpoint
            if (o.checkpoint is not None and o.checkpoint_trigger is not None
                    and o.checkpoint_trigger(train_state)):
                accum_state = None
                micro_state = getattr(self._step, "micro_state", None)
                if micro_state is not None:
                    acc, mn = micro_state()
                    if mn:  # mid-cycle: persist the partial accumulator
                        accum_state = {"g_acc": jax.device_get(acc),
                                       "micro_n": mn}
                with Timer(self.metrics, "checkpoint_s"):
                    path = o.checkpoint.save(
                        train_state["neval"], variables, slots,
                        {k: train_state[k] for k in
                         ("epoch", "neval", "nupdates", "records")},
                        accum_state=accum_state)
                logger.info("checkpoint -> %s", path)

        # end trigger may fire mid-accumulation-cycle: flush the partial
        # accumulator so those micro-batches' gradients aren't discarded
        flush = getattr(self._step, "flush", None)
        if flush is not None:
            eff_step = train_state["nupdates"]
            lr = o.optim_method.current_rate(
                {**train_state, "neval": eff_step})
            variables["params"], slots = flush(
                variables["params"], slots,
                jnp.asarray(lr, jnp.float32),
                jnp.asarray(eff_step, jnp.int32))

        if pending is not None:
            self._emit(pending)
        if o.checkpoint is not None:
            # drain the background writer: a failed async save (incl.
            # an injected ckpt_async_torn kill) must fail the run, not
            # vanish with the daemon thread
            o.checkpoint.wait()
        for summary in (o.train_summary, o.validation_summary):
            if summary is not None:
                summary.writer.flush()
        o.model.variables = variables
        return o.model

    def _emit(self, pending) -> None:
        """Telemetry for an already-dispatched step — registry + event
        + TrainSummary sink + log line, all through StepTelemetry;
        called one step late so the loss fetch overlaps device compute.
        The float(loss) here IS the fence for step N (timed as the
        `fence_s` phase). Histogram data arrives pre-materialized (see
        run()): the live param buffers are donated to the next step
        before _emit runs."""
        state, loss, lr, throughput, real, hists, gnorm, ok = pending
        o = self.o
        # the loss fetch piggybacks on the sinks that always needed it
        # (summary scalars, the log line); telemetry alone NEVER adds
        # a device→host sync — on a non-fence step the event simply
        # omits the loss field (StepTelemetry contract)
        fence = (o.train_summary is not None
                 or state["neval"] % o.log_every == 0)
        if not (fence or obs.enabled()):
            return
        if fence:
            with Timer(self.metrics, "fence_s"):
                loss = float(loss)
        else:
            loss = None
        self.telemetry.emit_step(
            epoch=state["epoch"], step=state["neval"], loss=loss,
            lr=lr, throughput=throughput, records=real,
            update_applied=ok, gnorm=gnorm, hists=hists,
            metrics_summary=self.metrics.summary())
