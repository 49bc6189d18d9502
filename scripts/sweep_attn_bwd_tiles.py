"""Fused-backward tile sweep, round 5 (fwd tiles fixed at the 1024x1024
optimum; the native-dtype-dot change moved the BACKWARD's optimum, so
its tiles are now chosen independently — ops/flash_attention.py
`bwd_tiles`).

Measures fwd+bwd (all three grads live) at the 186M shape and the
16k-long-context shape per bwd-tile combo.

Usage: python scripts/sweep_attn_bwd_tiles.py
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def chain(fn, x0, n=4, reps=3):
    import jax
    import jax.numpy as jnp
    from jax import lax

    looped = jax.jit(lambda x: lax.scan(
        lambda c, _: (fn(c), None), x, None, length=n)[0])
    out = looped(x0)
    float(jnp.sum(out).astype(jnp.float32))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = looped(out)
    float(jnp.sum(out).astype(jnp.float32))
    return (time.perf_counter() - t0) / (reps * n)


def sweep_shape(tag, bh, s, d, combos):
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.ops.flash_attention import (flash_attention,
                                               resolve_bwd_form)

    rng = np.random.RandomState(0)
    q0 = jnp.asarray(rng.randn(bh, s, d), jnp.bfloat16)
    k0 = jnp.asarray(rng.randn(bh, s, d), jnp.bfloat16)
    v0 = jnp.asarray(rng.randn(bh, s, d), jnp.bfloat16)

    # record what will ACTUALLY run (the profile_bilstm convention):
    # past the fused backward's resident cap, bwd_tiles do not apply —
    # the split backward tiles at the forward blocks, and timing it
    # under a bwd_tiles label would be the ADVICE-r05 wrong-kernel
    # hazard. Skip the combos instead of mislabeling them.
    bwd_form = resolve_bwd_form(s, d, q0.dtype.itemsize, block_q=1024)
    if bwd_form != "fused":
        print(json.dumps({"shape": tag, "SKIPPED":
                          f"resolve_bwd_form -> {bwd_form}: bwd_tiles "
                          f"do not apply (split backward tiles at the "
                          f"fwd blocks); rows would mislabel"}),
              flush=True)
        return

    for bt in combos:
        try:
            g = jax.grad(lambda q, k, v: flash_attention(
                q, k, v, causal=True, block_q=1024, block_k=1024,
                impl="pallas", bwd_tiles=bt)
                .astype(jnp.float32).sum(), argnums=(0, 1, 2))

            def fwdbwd(q):
                dq, dk, dv = g(q, k0, v0)
                return (dq + 1e-30 * (dk.astype(jnp.float32).sum()
                                      + dv.astype(jnp.float32).sum())
                        .astype(dq.dtype))

            t_b = chain(fwdbwd, q0, n=4)
            row = {"shape": tag, "bwd_tiles": list(bt) if bt else None,
                   "bwd_form": bwd_form,
                   "fwdbwd_ms": round(t_b * 1e3, 3)}
        except Exception as e:
            row = {"shape": tag, "bwd_tiles": list(bt) if bt else None,
                   "FAILED": str(e)[:140]}
        print(json.dumps(row), flush=True)


def main():
    from bigdl_tpu.utils.engine import setup_compile_cache

    setup_compile_cache()
    combos = [(512, 512), (512, 1024), (1024, 512), (256, 512),
              (512, 256), (256, 1024)]
    sweep_shape("186m_B8H16_S2048_D64", 128, 2048, 64, combos)
    sweep_shape("longctx_B1H8_S16384_D64", 8, 16384, 64,
                [(512, 512), (512, 1024), (256, 1024), (256, 512)])


if __name__ == "__main__":
    main()
