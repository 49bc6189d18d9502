"""Flash-attention block-size shootout, round 4 (VERDICT r3 item 6).

PROFILE_r03 measured the Mosaic fwd kernel ~15x off its compute bound
at 512x512 blocks; arithmetic says ~2k grid cells x ~3us fixed cell
overhead explains the gap, so the lever is FEWER, BIGGER cells (more q
rows per cell hides the serial kv loop). Sweeps (block_q, block_k) for
fwd and the bwd kernels at the 186M shape; chained-in-one-jit timing
(memory: attention-kernel-tuning — micro-bench fwd+bwd WITH dk/dv live,
never grad-wrt-q-only which DCEs them).

Usage: python scripts/sweep_attn_blocks.py
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

B, H, S, D = 8, 16, 2048, 64  # 186M attention shape (BH=128)


def chain(fn, x0, n=8, reps=3):
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


def main():
    from bigdl_tpu.utils.engine import setup_compile_cache

    setup_compile_cache()

    import jax
    import jax.numpy as jnp

    from bigdl_tpu.ops.flash_attention import (_flash_core,
                                               flash_attention)

    rng = np.random.RandomState(0)
    q0 = jnp.asarray(rng.randn(B * H, S, D), jnp.bfloat16)
    k0 = jnp.asarray(rng.randn(B * H, S, D), jnp.bfloat16)
    v0 = jnp.asarray(rng.randn(B * H, S, D), jnp.bfloat16)

    # correctness anchor: current default blocks
    ref = flash_attention(q0, k0, v0, causal=True)

    combos = [(512, 512), (1024, 512), (512, 1024), (1024, 1024),
              (2048, 512), (2048, 1024)]
    for bq, bk in combos:
        tag = f"{bq}x{bk}"
        try:
            def fwd(q, _bq=bq, _bk=bk):
                return flash_attention(q, k0, v0, causal=True,
                                       block_q=_bq, block_k=_bk,
                                       impl="pallas")

            out = fwd(q0)
            err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                        - ref.astype(jnp.float32))))
            t_f = chain(fwd, q0)

            # fwd+bwd with all three grads live
            g = jax.grad(lambda q, k, v: flash_attention(
                q, k, v, causal=True, block_q=bq, block_k=bk,
                impl="pallas").astype(jnp.float32).sum(),
                argnums=(0, 1, 2))

            def fwdbwd(q):
                dq, dk, dv = g(q, k0, v0)
                return (dq + 1e-30 * (dk.astype(jnp.float32).sum()
                                      + dv.astype(jnp.float32).sum())
                        .astype(dq.dtype))

            t_b = chain(fwdbwd, q0, n=4)
            row = {"blocks": tag, "fwd_ms": round(t_f * 1e3, 3),
                   "fwdbwd_ms": round(t_b * 1e3, 3),
                   "max_err_vs_default": round(err, 5)}
        except Exception as e:
            row = {"blocks": tag, "FAILED": str(e)[:140]}
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
