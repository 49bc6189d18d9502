"""The comparison that decides `correct`: each number compared is printed
beside its limit, and `correct` is their conjunction. Limits live in the
configuration's file under `limits`, set from measured readings (PERF.md
gives the readings for each)."""

from __future__ import annotations

import math
import statistics


class Check:
    def __init__(self, out=print):
        self.rows = []
        self.out = out

    def compare(self, name: str, value: float, limit: float) -> bool:
        ok = (value is not None and math.isfinite(value) and value <= limit)
        self.rows.append((name, value, limit, ok))
        self.out(f"check {name}: {value!r} <= limit {limit!r}: "
                 f"{'ok' if ok else 'NOT OK'}")
        return ok

    def require(self, name: str, ok: bool, detail: str = "") -> bool:
        self.rows.append((name, None, None, bool(ok)))
        self.out(f"check {name}: {'ok' if ok else 'NOT OK'} {detail}")
        return bool(ok)

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r[3] for r in self.rows)

    def report(self) -> dict:
        """Every comparison under its name, the number beside its limit
        (both None for a requirement): the result line's last key."""
        def plain(x):       # inf and nan are no JSON
            return x if x is None or math.isfinite(x) else repr(x)

        return {name: {"value": plain(value), "limit": limit, "ok": ok}
                for name, value, limit, ok in self.rows}


DEAD_LEAF = 1e-3


def dead_leaves(reference_slot: dict) -> set:
    """Leaves whose first gradient is all but zero in the reference (under
    a thousandth of the median leaf's norm): mathematically zero ones, such
    as GPT-2's key bias, which softmax cancels. Adam turns such a gradient
    into a full-sized step of arbitrary sign, so the leaf's change says
    nothing; its gradient is still compared, against the median leaf."""
    floor = DEAD_LEAF * statistics.median(reference_slot.values())
    return {k for k, v in reference_slot.items() if v < floor}


def worst_leaf_gap(program: dict, reference: dict) -> float:
    """The gap between the program's norm of a leaf and the reference's
    (not the norm of their difference), against the reference's norm of
    that leaf or of the median leaf, whichever is larger: some gradients
    are all but zero. The worst leaf decides."""
    if set(program) != set(reference):
        return float("inf")
    floor = statistics.median(reference.values())
    return max(abs(program[k] - reference[k]) / max(reference[k], floor)
               for k in reference)


def train_numbers(program: dict, reference: dict) -> dict:
    """program/reference: {"loss": [per step], "slot": {leaf: norm after
    step one}, "delta": {leaf: norm of the change after the last step}}."""
    dead = dead_leaves(reference["slot"])

    def alive(d):
        return {k: v for k, v in d.items() if k not in dead}

    return {
        "loss_gap": max(abs(p - r) / abs(r) for p, r in
                        zip(program["loss"], reference["loss"])),
        "grad_gap": worst_leaf_gap(program["slot"], reference["slot"]),
        "delta_gap": worst_leaf_gap(alive(program["delta"]),
                                    alive(reference["delta"])),
    }
