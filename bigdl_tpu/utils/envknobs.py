"""Import-time snapshots of the BIGDL_* performance env knobs.

Why this module exists: reading `os.environ` while jit traces a
function bakes the value into the first compiled executable for that
(shape, dtype, flags) combination — changing the variable afterwards
is a silent no-op for shapes already in jit's cache, and a sweep that
rotates the knob in-process silently measures one config under many
labels (the PR-1 flash-attention bwd-tiles lesson; graftlint rule
`trace-env-read` now bans env reads from compute code outright).

So every perf knob is resolved HERE, exactly once, at import — before
any trace can exist — and compute code reads the module-level
snapshot. The semantics become strictly more predictable than the old
trace-time read: the value in the environment when `bigdl_tpu` is
imported wins, full stop.

Legitimate in-process knob rotation (the fused-RNN tile sweep in
scripts/profile_bilstm.py, the kill-switch test) mutates the
environment and then calls `refresh()` — an *explicit* re-snapshot.
Callers doing that own the jit-cache consequence: already-compiled
shapes keep their old tiles; rotate knobs only with fresh shapes or
fresh jit roots (profile_bilstm builds a fresh jitted step per
config, so each re-traces under the new snapshot).

Knobs:

* `BIGDL_FUSED_RNN` — "0"/"false"/"off" disables the persistent-RNN
  Pallas kernels in auto mode (`FUSED_RNN_ENABLED`).
* `BIGDL_FUSED_RNN_BLOCK_N` — batch-tile row override for the fused
  RNN kernels (`FUSED_RNN_BLOCK_N`).
* `BIGDL_FLASH_FWD_TILES` / `BIGDL_FLASH_BWD_TILES` — "BQxBK" tile
  overrides for the flash-attention forward / fused-backward kernels
  (`FLASH_FWD_TILES` / `FLASH_BWD_TILES`). Malformed values raise at
  import — failing fast beats silently sweeping the default tiles.
"""

from __future__ import annotations

import os
from typing import Optional, Tuple


def _parse_tiles(var: str) -> Optional[Tuple[int, int]]:
    v = os.environ.get(var)
    if not v:
        return None
    try:
        bq, bk = v.lower().split("x")
        return int(bq), int(bk)
    except ValueError:
        raise ValueError(
            f"{var}={v!r}: expected 'BQxBK', e.g. '512x1024'") from None


def _parse_optional_int(var: str) -> Optional[int]:
    v = os.environ.get(var)
    return int(v) if v else None


def _parse_switch(var: str, default: str = "1") -> bool:
    return os.environ.get(var, default).lower() not in (
        "0", "false", "off")


FUSED_RNN_ENABLED: bool = True
FUSED_RNN_BLOCK_N: Optional[int] = None
FLASH_FWD_TILES: Optional[Tuple[int, int]] = None
FLASH_BWD_TILES: Optional[Tuple[int, int]] = None


def refresh() -> None:
    """Re-snapshot every knob from the current environment. For
    in-process sweeps/tests that rotate a knob deliberately; see the
    module docstring for the jit-cache caveat."""
    global FUSED_RNN_ENABLED, FUSED_RNN_BLOCK_N
    global FLASH_FWD_TILES, FLASH_BWD_TILES
    FUSED_RNN_ENABLED = _parse_switch("BIGDL_FUSED_RNN")
    FUSED_RNN_BLOCK_N = _parse_optional_int("BIGDL_FUSED_RNN_BLOCK_N")
    FLASH_FWD_TILES = _parse_tiles("BIGDL_FLASH_FWD_TILES")
    FLASH_BWD_TILES = _parse_tiles("BIGDL_FLASH_BWD_TILES")


refresh()
