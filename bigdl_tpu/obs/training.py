"""One emission path for per-step training telemetry.

Before ISSUE 5, each training loop wrote the same numbers three ways:
`TrainSummary.add_scalar` (Loss/Throughput/LearningRate, duplicated in
LocalOptimizer._emit and DistriOptimizer.run), `optim.Metrics`
stopwatches rendered into the log line, and the log line itself —
three bookkeeping paths, no shared schema. `StepTelemetry` is now the
single path: the loops hand it one already-fetched step record and it
fans out to (1) the metrics registry, (2) the structured event log,
(3) the TrainSummary sink if configured, (4) the human log line.

Sync discipline: callers pass HOST floats they already fetched (the
loops fetch loss one step late so the fetch overlaps device compute —
see LocalOptimizer._emit); this module never touches a device array.
"""

from __future__ import annotations

import logging
from typing import Optional

from bigdl_tpu import obs

__all__ = ["StepTelemetry"]

logger = logging.getLogger("bigdl_tpu.optim")


class StepTelemetry:
    """Per-run fan-out for step records.

    `summary` — an optional TrainSummary-like sink (anything with
    `add_scalar(tag, value, step)`); the registry/event emission does
    not depend on it. `plane` labels the registry series so a process
    hosting several runs stays legible."""

    def __init__(self, summary=None, log_every: int = 1,
                 plane: str = "training"):
        self.summary = summary
        self.log_every = max(int(log_every), 1)
        self.plane = plane
        reg = obs.get_registry()
        self._steps = reg.counter(
            "training_steps_total", "optimizer steps observed")
        self._updates = reg.counter(
            "training_updates_applied_total",
            "optimizer updates actually applied (guard-discarded "
            "steps excluded)")
        self._records = reg.counter(
            "training_records_total", "training records consumed")
        self._prefetched = reg.counter(
            "training_batches_prefetched_total",
            "steps that found their batch already placed on the mesh "
            "(DistriOptimizer places batch n + 1 while step n runs)")
        self._prefetch_dropped = reg.counter(
            "training_batches_prefetch_dropped_total",
            "batches placed ahead and never fed to a step (the run's "
            "end, a recovery, a poisoned batch placed again)")
        self._loss = reg.gauge("training_loss", "last step loss")
        self._lr = reg.gauge("training_learning_rate",
                             "last step learning rate")
        self._thr = reg.gauge("training_throughput_records_per_sec",
                              "last step throughput")

    def batch_prefetched(self) -> None:
        if obs.enabled():
            self._prefetched.inc()

    def prefetch_dropped(self) -> None:
        if obs.enabled():
            self._prefetch_dropped.inc()

    def emit_step(self, *, epoch: int, step: int,
                  loss: Optional[float], lr: float, throughput: float,
                  records: int, update_applied: bool = True,
                  gnorm: Optional[float] = None,
                  hists=None, metrics_summary: str = "") -> None:
        """`loss`/`gnorm` must already be host floats (no device
        fetches here) — and `loss` may be None: on a step where
        nothing else fenced the loss (no summary sink, not a log
        step), the loops do NOT fetch it just for telemetry (the
        piggyback-on-existing-fetches contract), so the event carries
        every host-side field and omits `loss`. `hists` is
        pre-materialized (name, ndarray) pairs for the TrainSummary
        parameter-histogram trigger."""
        if obs.enabled():
            self._steps.inc()
            self._records.inc(records)
            if update_applied:
                self._updates.inc()
            if loss is not None:
                self._loss.set(loss)
            self._lr.set(lr)
            self._thr.set(throughput)
            fields = {"plane": self.plane, "epoch": epoch, "step": step,
                      "lr": float(lr),
                      "throughput": round(float(throughput), 3),
                      "update_applied": bool(update_applied)}
            if loss is not None:
                fields["loss"] = float(loss)
            if gnorm is not None:
                fields["gnorm"] = float(gnorm)
            obs.emit_event("train_step", **fields)
        if self.summary is not None and loss is not None:
            self.summary.add_scalar("Loss", float(loss), step)
            self.summary.add_scalar("Throughput", throughput, step)
            self.summary.add_scalar("LearningRate", lr, step)
            for name, data in (hists or ()):
                self.summary.add_histogram(name, data, step)
        if step % self.log_every == 0 and loss is not None:
            logger.info(
                "epoch %d iter %d loss %.6f lr %.5g %.1f rec/s [%s]",
                epoch, step, float(loss), lr, throughput,
                metrics_summary)
