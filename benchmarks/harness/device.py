"""What JAX found, and the refusal to measure without it."""

from __future__ import annotations

import json
import os


class NoChip(SystemExit):
    """The run found no accelerator, or fewer chips than the cell needs."""


def find_devices(chips: int, require_chip: bool):
    """The `chips` devices the cell uses. `require_chip=False` is for the
    CPU rehearsal in the tests only: the command line never passes it."""
    import jax

    devices = jax.devices()
    if require_chip and devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform} devices; a "
                     "benchmark run never falls back to another platform")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices[:chips]


def describe(devices) -> dict:
    import jax

    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(jax.devices())}


def memory_peak_bytes(devices):
    """Peak bytes on the fullest device, or None where the backend reports
    no memory statistics (the CPU). The TPU runtime counts live arrays
    (`peak_bytes_in_use`) apart from the scratch that running programs
    reserve (`peak_bytes_reserved`: a train step's activations, 7 of
    gpt2m-train's 11.3 GB); a step holds both at once, so the peak is their
    sum (read on the chip, PR 23: the sum is within 10% of the compiler's
    own arguments + temporaries for the same step)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(stats["peak_bytes_in_use"]
                         + stats.get("peak_bytes_reserved", 0))
    return max(peaks) if peaks else None


def peaks_of(root: str, kind: str, platform: str):
    """Published peaks of the device, from benchmarks/peaks.json. A TPU that
    is not in the table is an error, never a default; the CPU has none."""
    if platform != "tpu":
        return None
    with open(os.path.join(root, "benchmarks", "peaks.json")) as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"no published peaks for device_kind {kind!r} in "
                       f"benchmarks/peaks.json (known: {sorted(table)})")
    return table[kind]
