"""Stable names for the Pallas kernel families.

A Mosaic kernel reaches the device trace as one `tpu_custom_call` whose
HLO instruction is named after the innermost name-stack scope around
the `pallas_call` (`jvp__`, `closed_call` — whatever transform happened
to enclose it), so forward could not be told from backward by name and
a trace reader had to lump all custom-call time. `named_pallas_call`
gives the call both handles a reader can use: `name=` (the StableHLO
`kernel_name` attribute, checked on the CPU by
tests/test_kernel_names.py) and a `jax.named_scope` of the same name
(the HLO instruction's name on the chip: `flash_fwd.3`). Metadata only:
operands, grid and block sizes are the caller's, untouched.
"""

from __future__ import annotations

import jax
from jax.experimental import pallas as pl

__all__ = ["named_pallas_call"]


def named_pallas_call(name: str, kernel, **kwargs):
    call = pl.pallas_call(kernel, name=name, **kwargs)

    def run(*args):
        with jax.named_scope(name):
            return call(*args)

    return run
