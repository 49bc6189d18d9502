"""Distributed (mesh) training loop.

Reference parity: optim/DistriOptimizer.scala — the heart of the
reference (SURVEY.md §3.1): per-iteration Spark job → local fwd/bwd →
AllReduceParameter reduce-scatter → sharded optim step → all-gather,
plus driver-side triggers/validation/checkpoint and failure recovery.

TPU-first redesign: the per-iteration Spark job becomes ONE jitted SPMD
step over the mesh (see data_parallel.py); the driver loop below is pure
host orchestration. Multi-host: every process runs this same loop in
lockstep (PJRT collectives span hosts); each feeds its own data shard —
exactly the reference's one-executor-per-node layout with "Spark only
partitions data".

Failure recovery (reference: DistriOptimizer retry + reload-last-
checkpoint, SURVEY.md §5.3): on a step exception with a checkpoint
configured, reload the latest checkpoint and continue (`max_retries`).
The reference gets its *guarantees* from Spark task retry + lineage
(arXiv 1804.05839 §4); the substitutes here are explicit and tested:
checkpoint loads verify per-array checksums and fall back past corrupt
dirs (serialization/checkpoint.py), the numeric-anomaly guard discards
NaN/Inf/spike updates on device with skip/rollback/halt policies
(utils/anomaly.py, `Optimizer.set_anomaly_guard`), and every recovery
path is exercised deterministically by fault injection
(utils/faults.py, scripts/fault_drill.py).
"""

from __future__ import annotations

import logging
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bigdl_tpu.optim.metrics import Metrics, Timer
from bigdl_tpu.optim.optimizer import LocalOptimizer, Optimizer, _batch_iterator
from bigdl_tpu.optim.validation import ValidationResult
from bigdl_tpu.parallel.data_parallel import (
    FlatParamSpec, make_dp_accum_steps, make_dp_eval_step,
    make_dp_train_step,
)
from bigdl_tpu.parallel.mesh import host_to_global, place_global

logger = logging.getLogger("bigdl_tpu.optim")


class DistriOptimizer(LocalOptimizer):
    """Mesh data-parallel optimizer (reference: optim/DistriOptimizer.scala).

    The loop's input side runs one batch ahead of the step: the host
    batch of step n + 1 is taken from the dataset's iterator at the top
    of iteration n and placed on the mesh right after step n's call,
    while the devices run it and before anything fetches its results.
    Device memory therefore holds two batches; a dataset whose iterator
    has side effects sees one `next()` more than the steps that ran
    (the batch the end trigger leaves over is dropped before `run()`
    returns); a failure of the loader met ahead is raised by the step
    that would have consumed the batch, and a recovery drops what was
    placed ahead with the iterator it came from. How often it engages:
    `training_batches_prefetched_total` (steps - 1 in a healthy run) and
    `training_batches_prefetch_dropped_total` (1)."""

    def __init__(self, opt: Optimizer, mesh: Mesh, axis: str = "data",
                 grad_dtype: Optional[str] = "bfloat16", max_retries: int = 3,
                 zero: int = 1):
        super().__init__(opt)
        if zero not in (1, 2):
            raise ValueError(f"zero must be 1 or 2, got {zero!r}")
        self.mesh = mesh
        self.axis = axis
        self.grad_dtype = grad_dtype
        self.max_retries = max_retries
        self.zero = zero
        self._gather_fn = None

    # ------------------------------------------------------------- helpers
    def _batch_spec(self, x) -> P:
        return P(self.axis, *([None] * (x.ndim - 1)))

    def _global(self, x):
        """Place a host batch (array or tuple of arrays for multi-input
        models) on the mesh, sharded over the data axis."""
        if isinstance(x, tuple):
            return tuple(self._global(e) for e in x)
        arr = np.asarray(x)
        return host_to_global(self.mesh, self._batch_spec(arr), arr)

    def _place_sharded_slots(self, slots):
        # multi-process safe: every process holds the identical global
        # slot values (same init / same checkpoint files)
        return place_global(self.mesh, P(self.axis), slots)

    def _gather(self, tree):
        """Fetch a (possibly cross-process-sharded) ZeRO-1 tree to host.

        Single process: a plain device_get. Multi-host: sharded arrays
        span non-addressable devices, so an XLA all-gather (jitted
        identity re-sharded to replicated) runs first — the analogue of
        the reference's driver pulling weight slices before writing a
        checkpoint (SURVEY.md §5.4). The jitted identity is built once
        per optimizer so repeated checkpoints hit the trace cache."""
        if jax.process_count() == 1:
            return jax.device_get(tree)
        if self._gather_fn is None:
            self._gather_fn = jax.jit(
                lambda t: t,
                out_shardings=NamedSharding(self.mesh, P()))
        return jax.device_get(self._gather_fn(tree))

    @staticmethod
    def _local_shard_slices(tree, spec, mesh=None, axis="data"):
        """{shard index: host tree of that shard's slot slices} for the
        shards whose devices are addressable from THIS process — the
        "each host saves only its shards" half of the async sharded
        checkpoint (ISSUE 9). Slot leaves are global (padded,) vectors
        sharded P(axis), so each addressable device shard IS one ZeRO
        shard; its global offset // shard_size is the shard index.
        (static: scripts/scaling_bench.py reuses it to feed the
        checkpoint-overlap row the exact shard trees the real save
        path writes)."""
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        if not leaves:
            # slot-less method (plain SGD): no sharded array to read
            # ownership from, so derive it from the mesh — shard i
            # belongs to the process owning the i-th device on the
            # data axis. Without a mesh (single-process callers) every
            # shard is this host's.
            if mesh is None:
                return {i: tree for i in range(spec.num_shards)}
            me = jax.process_index()
            axes = list(mesh.axis_names)
            dev = np.moveaxis(np.asarray(mesh.devices),
                              axes.index(axis), 0).reshape(
                                  mesh.shape[axis], -1)
            return {i: tree for i in range(spec.num_shards)
                    if dev[i, 0].process_index == me}
        per_shard: Dict[int, list] = {}
        for li, leaf in enumerate(leaves):
            for sh in leaf.addressable_shards:
                start = sh.index[0].start or 0
                sidx = start // spec.shard_size
                per_shard.setdefault(
                    sidx, [None] * len(leaves))[li] = np.asarray(sh.data)
        return {s: jax.tree_util.tree_unflatten(treedef, lv)
                for s, lv in sorted(per_shard.items())}

    @staticmethod
    def _adapt_slots(saved_slots, optim_meta, spec):
        """Convert checkpointed slots to this run's ZeRO flat layout.

        Three cases (see the `optim_meta` written at save time):
        - same `padded` → use directly
        - zero{1,2}_flat from a different mesh size → strip padding,
          re-pad (the elastic-resume reshard)
        - pytree slots from a LocalOptimizer checkpoint → flatten each
          top-level slot branch with this spec

        The algebra lives in the param-layout spine (ISSUE 18) — this
        wrapper keeps the historical call site (scripts and the
        recover/resume paths reference it by name).
        """
        from bigdl_tpu.parallel.param_layout import adapt_flat_tree

        return adapt_flat_tree(saved_slots, optim_meta, spec)

    # ------------------------------------------------------------------ run
    def run(self):
        o = self.o
        n = self.mesh.shape[self.axis]
        if o.batch_size is None or o.batch_size % n != 0:
            raise ValueError(
                f"global batch_size {o.batch_size} must be divisible by the "
                f"'{self.axis}' mesh axis size {n}")

        if o.validation_methods and (o.validation_batch_size or o.batch_size) % n != 0:
            raise ValueError(
                f"validation batch_size {o.validation_batch_size} must be "
                f"divisible by the '{self.axis}' mesh axis size {n}")

        # Multi-host: batch_size is GLOBAL; each process feeds its
        # 1/nproc shard of every batch (the reference's "Spark only
        # partitions data" — each executor iterates its partition).
        nproc = jax.process_count()
        if o.batch_size % nproc:
            raise ValueError(
                f"global batch_size {o.batch_size} must be divisible by "
                f"the process count {nproc}")
        vbs = o.validation_batch_size or o.batch_size
        if o.validation_methods and vbs % nproc:
            raise ValueError(
                f"validation batch_size {vbs} must be divisible by the "
                f"process count {nproc}")
        self._local_bs = o.batch_size // nproc
        self._local_vbs = vbs // nproc

        rng = jax.random.PRNGKey(o.seed)
        variables = dict(o.model.variables)
        spec = FlatParamSpec(variables["params"], n)
        self._unflatten = jax.jit(spec.unflatten)
        logger.info("DistriOptimizer: %d devices on axis %r (ZeRO-%d), "
                    "%d params (padded %d, %d per shard)", n, self.axis,
                    self.zero, spec.total, spec.padded, spec.shard_size)

        # ZeRO-2: the master fp32 flat weights persist SHARDED on the
        # data axis between steps (the step all_gathers on entry)
        w_spec = P(self.axis) if self.zero == 2 else P()

        guard = o.anomaly_guard
        accum = o.grad_accum
        if accum == 1:
            step_fn = make_dp_train_step(
                o.model, o.criterion, o.optim_method, self.mesh, spec,
                axis=self.axis, grad_dtype=self.grad_dtype,
                clip_const=o.grad_clip_const, clip_norm=o.grad_clip_norm,
                precision=o.precision, health=guard is not None,
                zero=self.zero)
        else:
            micro_fn, apply_fn = make_dp_accum_steps(
                o.model, o.criterion, o.optim_method, self.mesh, spec,
                axis=self.axis, grad_dtype=self.grad_dtype,
                clip_const=o.grad_clip_const, clip_norm=o.grad_clip_norm,
                precision=o.precision, health=guard is not None,
                zero=self.zero)
        if o.validation_methods:
            eval_fn = make_dp_eval_step(o.model, o.validation_methods,
                                        self.mesh, self.axis)

        flat_w = place_global(self.mesh, w_spec,
                              spec.flatten(variables["params"]))
        mod_state = place_global(self.mesh, P(), variables["state"])
        # slot arrays are GLOBAL (padded,) shapes, device-placed sharded on
        # the data axis — each device materializes only its (shard_size,)
        # slice: the ZeRO-1 optimizer-state sharding
        slots = self._place_sharded_slots(
            o.optim_method.init_slots(jnp.zeros((spec.padded,), jnp.float32)))

        def fresh_acc():
            return place_global(self.mesh, P(self.axis),
                                jnp.zeros((spec.padded,), jnp.float32))

        g_acc = fresh_acc() if accum > 1 else None
        micro_n = 0
        # "nupdates" is the applied-update clock (stepno/schedules);
        # see LocalOptimizer.run — guard-discarded updates and
        # uncounted micro-batches do not advance it
        train_state: Dict[str, Any] = {"epoch": 1, "neval": 0,
                                       "nupdates": 0, "records": 0,
                                       "loss": None, "score": None}

        def adopt_train_state(saved_ts):
            train_state.update(saved_ts)
            if "nupdates" not in saved_ts:  # pre-counter checkpoint
                train_state["nupdates"] = train_state["neval"] // accum

        def restore_accum(optim_meta):
            """Reinstall a checkpointed mid-cycle accumulator (or reset).
            Handles a pytree-layout accumulator from a LocalOptimizer
            checkpoint (flatten into this run's ZeRO-1 layout) and a
            flat accumulator from a different mesh size (strip the old
            padding, re-pad — mirrors _adapt_slots)."""
            nonlocal g_acc, micro_n
            saved = o.checkpoint.load_accum() if o.checkpoint else None
            if accum == 1:
                if saved is not None:
                    logger.warning(
                        "checkpoint holds a mid-cycle accumulator (%d "
                        "micro-batches) but this run has grad_accum=1; "
                        "the partial gradients are discarded",
                        int(saved["micro_n"]))
                return
            if saved is None or int(saved["micro_n"]) >= accum:
                if saved is not None:
                    logger.warning(
                        "checkpointed accumulation cycle (%d micro-"
                        "batches) does not fit grad_accum=%d; restarting "
                        "the cycle", int(saved["micro_n"]), accum)
                g_acc, micro_n = fresh_acc(), 0
                return
            acc = saved["g_acc"]
            if isinstance(acc, dict):
                flat = spec.flatten(acc)
            else:
                from bigdl_tpu.parallel.param_layout import repad_flat

                flat = jnp.asarray(acc)
                old_total = (optim_meta or {}).get("total")
                if flat.shape[0] != spec.padded:
                    if old_total is None or old_total > spec.padded:
                        raise ValueError(
                            f"cannot adapt accumulator of length "
                            f"{flat.shape[0]} to padded {spec.padded}")
                    flat = repad_flat(flat, old_total, spec.padded)
            g_acc = place_global(self.mesh, P(self.axis), flat)
            micro_n = int(saved["micro_n"])

        def recover():
            """Reload the newest VALID checkpoint (Checkpoint.load skips
            corrupt dirs) and re-align the batch stream — shared by the
            step-exception retry path and the anomaly guard's rollback
            policy (the reference's reload-last-checkpoint recovery,
            SURVEY.md §5.3)."""
            nonlocal flat_w, mod_state, slots, batches
            drop_ahead()  # it came from the iterator replaced below
            o.checkpoint.wait()  # surface pending async-save errors
            saved_vars, saved_slots, saved_ts, om = o.checkpoint.load(
                with_optim_meta=True)
            flat_w = place_global(self.mesh, w_spec,
                                  spec.flatten(saved_vars["params"]))
            mod_state = place_global(self.mesh, P(), saved_vars["state"])
            slots = self._place_sharded_slots(
                self._adapt_slots(saved_slots, om, spec))
            adopt_train_state(saved_ts)
            batches = _batch_iterator(o.dataset, True, self._local_bs,
                                      skip=train_state["neval"])
            restore_accum(om)

        if o._resume and o.checkpoint is not None and o.checkpoint.latest():
            saved_vars, saved_slots, saved_ts, optim_meta = o.checkpoint.load(
                with_optim_meta=True)
            flat_w = place_global(self.mesh, w_spec,
                                  spec.flatten(saved_vars["params"]))
            mod_state = place_global(self.mesh, P(), saved_vars["state"])
            slots = self._place_sharded_slots(
                self._adapt_slots(saved_slots, optim_meta, spec))
            adopt_train_state(saved_ts)
            restore_accum(optim_meta)
            logger.info("resumed from %s at %s",
                        o.checkpoint._last_loaded, saved_ts)

        from bigdl_tpu.utils import faults

        plan = faults.get_plan()
        dataset_size = o.dataset.size()
        # fast-forward the deterministic batch stream past what the
        # checkpointed run consumed (bit-for-bit resume; no-op fresh)
        batches = _batch_iterator(o.dataset, True, self._local_bs,
                                  skip=train_state["neval"])
        # The input side runs one batch ahead of the step: `ahead` is the
        # NEXT step's (host batch, its placement on the mesh), fetched
        # at the top of the running iteration and placed in the shadow
        # of its step, before anything fetches that step's results. A
        # failure of the loader or of the placement is held in the host
        # batch's place and raised by the step that would have consumed
        # it, inside its retry scope (`data@<position>` keeps its
        # meaning). None on the first iteration and after a recover().
        ahead = None

        def fetch():
            with Timer(self.metrics, "data_fetch_s"):
                try:
                    return next(batches)
                except Exception as e:  # held for the consuming step
                    return e

        def place(mb):
            with Timer(self.metrics, "h2d_place_s"):
                return self._global(mb.input), self._global(mb.target)

        def drop_ahead():
            nonlocal ahead
            if ahead is not None and ahead[1] is not None:
                self.telemetry.prefetch_dropped()
            ahead = None

        iter_start = time.perf_counter()
        retries = 0

        while not o.end_when(train_state):
            # outside the retry try — the retry budget must never
            # absorb a preemption (faults.FaultPlan.maybe_preempt)
            try:
                plan.maybe_preempt(train_state["neval"])
            except faults.Preempted:
                # dead worker propagating out (recovery is a fresh
                # process with --resume): record the incident for the
                # flight recorder (ISSUE 11) before re-raising
                from bigdl_tpu import obs

                obs.emit_event("preempted", plane="training",
                               step=train_state["neval"])
                raise
            try:
                plan.maybe_raise("step", train_state["neval"])
                mb, placed = ahead or (fetch(), None)
                ahead = None
                if isinstance(mb, Exception):
                    raise mb
                # batch n + 1 leaves the iterator here; batch n stays
                # referenced as `mb` until step n has fenced, so a
                # dataset that frees or reuses a batch's buffer once it
                # is unreferenced cannot do so under a transfer
                nxt = fetch()
                if plan.fires("nan", train_state["neval"]):
                    mb = faults.poison_minibatch(mb)
                    if placed is not None:  # placed clean: place again
                        self.telemetry.prefetch_dropped()
                        placed = None
                # schedules and the optimizer's step counter advance per
                # APPLIED update, not per (micro-)batch (mirrors
                # LocalOptimizer): a guard-discarded update re-uses its
                # step index
                eff_step = train_state["nupdates"]
                lr = o.optim_method.current_rate(
                    train_state if accum == 1 and guard is None
                    else {**train_state, "neval": eff_step})
                step_rng = jax.random.fold_in(rng, train_state["neval"])
                thr = None if guard is None else jnp.asarray(
                    guard.threshold(), jnp.float32)
                with Timer(self.metrics, "dispatch_s"):
                    # dispatch = the step's call + h2d_place (the NEXT
                    # host batch placed on the mesh while the devices
                    # run this one; PERF.md §3). Only the first step,
                    # and the one after a recover() or a poisoning,
                    # places its own batch before it calls
                    if placed is None:
                        placed = place(mb)
                    else:
                        self.telemetry.batch_prefetched()
                    x, y = placed
                    if accum == 1:
                        step_args = (
                            flat_w, slots, mod_state, x, y,
                            jnp.asarray(lr, jnp.float32),
                            jnp.asarray(eff_step, jnp.int32),
                            step_rng)
                        if guard is None:
                            flat_w, slots, mod_state, loss = step_fn(
                                *step_args)
                        else:
                            (flat_w, slots, mod_state, loss, ok_d,
                             gnorm_d) = step_fn(*step_args, thr)
                    else:
                        micro_args = (
                            flat_w, g_acc, mod_state, x, y, step_rng)
                        if guard is None:
                            g_acc, mod_state, loss = micro_fn(*micro_args)
                        else:
                            (g_acc, mod_state, loss, ok_d,
                             gnorm_d) = micro_fn(*micro_args, thr)
                    # in the shadow of the step just launched, before
                    # anything below fetches a result of it
                    ahead = (nxt, None)
                    if not isinstance(nxt, Exception):
                        try:
                            ahead = (nxt, place(nxt))
                        except Exception as e:  # held, like the loader's
                            ahead = (e, None)
                    if accum > 1:
                        # an anomalous micro-gradient was zeroed out of
                        # the accumulator on device; don't count it
                        # toward the cycle either (the guard's fetch)
                        micro_n += 1 if guard is None else int(bool(ok_d))
                        if micro_n == accum:
                            flat_w, slots, g_acc = apply_fn(
                                flat_w, slots, g_acc,
                                jnp.asarray(lr, jnp.float32),
                                jnp.asarray(eff_step, jnp.int32),
                                jnp.asarray(accum, jnp.float32))
                            micro_n = 0
                            train_state["nupdates"] += 1
            except Exception:
                if (o.checkpoint is not None and o.checkpoint.latest()
                        and retries < self.max_retries):
                    retries += 1
                    logger.exception(
                        "step failed; recovering from checkpoint "
                        "(retry %d/%d)", retries, self.max_retries)
                    recover()
                    continue
                raise

            ok_host, gnorm_host = True, None
            if guard is not None:
                # scalar fetch syncs the step (the documented guard
                # cost); the anomalous update is already discarded on
                # device — the host only applies policy
                ok_host, gnorm_host = bool(ok_d), float(gnorm_d)
                action = guard.observe(ok_host, gnorm_host,
                                       train_state["neval"])
                if action == "rollback":
                    self._require_rollback_checkpoint()
                    recover()
                    continue

            # consecutive-failure budget, not a lifetime cap (the reference
            # budgets retries against repeated failure of the same step)
            retries = 0

            real = getattr(mb, "real_size", mb.size)
            train_state["neval"] += 1
            if accum == 1:
                # a guard-discarded update keeps its step index for the
                # next batch; the applied-update clock only advances on
                # healthy steps (accum>1 advances at apply_fn above)
                train_state["nupdates"] += 1 if guard is None \
                    else int(ok_host)
            train_state["records"] += real
            train_state["loss"] = loss
            now = time.perf_counter()
            iter_wall, iter_start = now - iter_start, now
            self.metrics.add("iter_s", iter_wall)
            throughput = real / max(iter_wall, 1e-9)

            # one emission path (obs/training.StepTelemetry): registry
            # + event log + TrainSummary sink + log line. The
            # float(loss) fence only runs on steps that always fetched
            # it (summary sink armed, or a log_every step) — telemetry
            # alone never adds a device→host sync; off-fence events
            # omit the loss field (piggyback contract), and with
            # everything off the step skips emission entirely so the
            # host can run ahead of the device
            from bigdl_tpu import obs

            fence = (o.train_summary is not None
                     or train_state["neval"] % o.log_every == 0)
            if fence or obs.enabled():
                loss_host = None
                if fence:
                    with Timer(self.metrics, "fence_s"):
                        loss_host = float(loss)
                self.telemetry.emit_step(
                    epoch=train_state["epoch"],
                    step=train_state["neval"],
                    loss=loss_host, lr=lr, throughput=throughput,
                    records=real, update_applied=ok_host,
                    gnorm=gnorm_host,
                    metrics_summary=self.metrics.summary())

            if train_state["records"] >= dataset_size:
                train_state["epoch"] += 1
                train_state["records"] = 0

            if (o.validation_trigger is not None
                    and o.validation_trigger(train_state)):
                res = self._validate_mesh(eval_fn, spec, flat_w, mod_state)
                for name, r in res.items():
                    v, cnt = r.result()
                    logger.info("validation %s = %.6f (%d)", name, v, cnt)
                    if o.validation_summary is not None:
                        o.validation_summary.add_scalar(
                            name, v, train_state["neval"])
                first = next(iter(res.values()), None)
                if first is not None:
                    train_state["score"] = first.result()[0]
                    sched = o.optim_method.schedule
                    if hasattr(sched, "on_metric"):
                        sched.on_metric(train_state["score"])

            if (o.checkpoint is not None and o.checkpoint_trigger is not None
                    and o.checkpoint_trigger(train_state)):
                # zero2 keeps flat_w sharded across processes: gather
                # before unflattening the model tree for the save.
                # The gather is a COLLECTIVE (every host participates)
                # but the full-model host tree is materialized only
                # where it will be written — secondaries' sharded
                # saves ignore model_variables, so they must not pay a
                # whole-model device->host fetch on the step path.
                # Sharded zero1 saves need the host copy too: the
                # primary-only _unflatten below must never be handed a
                # device-global array (a jit entered by one controller
                # of a multi-process run is a launch mismatch)
                flat_for_save = self._gather(flat_w) \
                    if (nproc > 1 and (self.zero == 2
                                       or o.checkpoint.sharded)) \
                    else flat_w
                primary = jax.process_index() == 0
                if primary or not o.checkpoint.sharded:
                    saved_variables = {
                        "params": jax.device_get(
                            self._unflatten(flat_for_save)),
                        "state": jax.device_get(mod_state),
                    }
                else:
                    saved_variables = None
                accum_state = None
                if micro_n:  # mid-cycle: persist the partial accumulator
                    accum_state = {"g_acc": self._gather(g_acc),
                                   "micro_n": micro_n}
                train_meta = {k: train_state[k] for k in
                              ("epoch", "neval", "nupdates", "records")}
                optim_meta = {"layout": f"zero{self.zero}_flat",
                              "num_shards": n,
                              "total": spec.total,
                              "padded": spec.padded}
                with Timer(self.metrics, "checkpoint_s"):
                    # with async_save this times only the host snapshot
                    # + enqueue; the disk write overlaps the next steps
                    # (scaling_bench's checkpoint-overlap row measures
                    # the on-vs-off per-step cost)
                    if o.checkpoint.sharded:
                        # each host hands over exactly the shard slices
                        # its devices own — no slot gather, no
                        # full-state replica on any single host
                        path = o.checkpoint.save_sharded(
                            train_state["neval"], saved_variables,
                            self._local_shard_slices(
                                slots, spec, mesh=self.mesh,
                                axis=self.axis),
                            nshards=n, train_state=train_meta,
                            optim_meta=optim_meta,
                            accum_state=accum_state)
                    else:
                        path = o.checkpoint.save(
                            train_state["neval"], saved_variables,
                            self._gather(slots),
                            train_meta,
                            optim_meta=optim_meta,
                            accum_state=accum_state)
                if nproc > 1:
                    # barrier: no host may run ahead (and potentially
                    # recover from this checkpoint) until the write is
                    # complete everywhere. Async saves drain first —
                    # cross-host overlap would need a coordination
                    # service; the async win is measured per-host
                    # (single-process) where steps genuinely never
                    # stall on I/O
                    o.checkpoint.wait()
                    from jax.experimental import multihost_utils

                    multihost_utils.sync_global_devices(
                        f"ckpt-{train_state['neval']}")
                logger.info("checkpoint -> %s", path)

        # the batch placed for the step that never came: its HBM goes now
        drop_ahead()

        # end trigger may fire mid-accumulation-cycle: flush the partial
        # accumulator (mean over micro-batches actually seen) so that
        # gradient work isn't silently discarded — mirrors LocalOptimizer
        if accum > 1 and micro_n:
            eff_step = train_state["nupdates"]
            lr = o.optim_method.current_rate(
                {**train_state, "neval": eff_step})
            flat_w, slots, g_acc = apply_fn(
                flat_w, slots, g_acc, jnp.asarray(lr, jnp.float32),
                jnp.asarray(eff_step, jnp.int32),
                jnp.asarray(micro_n, jnp.float32))
            micro_n = 0

        if o.checkpoint is not None:
            # drain the background writer: a failed async save (incl.
            # an injected ckpt_async_torn kill) must fail the run
            o.checkpoint.wait()
        flat_final = self._gather(flat_w) \
            if (self.zero == 2 and jax.process_count() > 1) else flat_w
        o.model.variables = {
            "params": jax.device_get(self._unflatten(flat_final)),
            "state": jax.device_get(mod_state),
        }
        return o.model

    # ------------------------------------------------------------ validate
    def _validate_mesh(self, eval_fn, spec, flat_w, mod_state):
        o = self.o
        params = self._unflatten(flat_w)
        results = [ValidationResult(0.0, 0.0, m.name)
                   for m in o.validation_methods]
        it = _batch_iterator(o.validation_dataset, False, self._local_vbs)
        multi = jax.process_count() > 1
        last = None
        while True:
            mb = next(it, None)
            if multi:
                # Hosts may own uneven validation shards (sizes differ
                # by up to one batch). eval_fn and _global are cross-
                # process collectives, so EVERY host must join EVERY
                # round: exchange have-data flags, and exhausted hosts
                # feed an all-masked copy of their previous batch.
                from jax.experimental import multihost_utils

                flags = multihost_utils.process_allgather(
                    np.asarray([0 if mb is None else 1]))
                if not flags.any():
                    break
                if mb is None:
                    if last is None:
                        raise RuntimeError(
                            "a host has an empty validation shard; give "
                            "every process at least one batch "
                            "(DataSet.sharded of >= nproc samples)")
                    # every filler row must be IDENTICAL so the Loss
                    # edge-correction cancels the shard exactly
                    from bigdl_tpu.dataset.sample import MiniBatch

                    def tile_first(x, rows):
                        if isinstance(x, tuple):
                            return tuple(tile_first(e, rows) for e in x)
                        a = np.asarray(x)
                        return np.repeat(a[:1], rows, axis=0)

                    mb = MiniBatch(tile_first(last.input, last.size),
                                   tile_first(last.target, last.size))
                    real = 0
                else:
                    last = mb
                    real = getattr(mb, "real_size", mb.size)
            elif mb is None:
                break
            else:
                real = getattr(mb, "real_size", mb.size)
            mask = (np.arange(mb.size) < real).astype(np.float32)
            stats = eval_fn(params, mod_state,
                            self._global(mb.input), self._global(mb.target),
                            self._global(mask))
            for i, (s, c) in enumerate(stats):
                results[i] = results[i] + ValidationResult(float(s), float(c))
        return {m.name: r for m, r in zip(o.validation_methods, results)}
