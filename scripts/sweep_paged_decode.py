"""Paged-decode kernel tile/residency sweep (ISSUE 17).

Sweeps the one-launch paged-attention decode kernel
(ops/paged_decode.py) over its (block_tile, head_tile) grid at the 43M
serving shape. Off-TPU this is an INTERPRET-MODE SMOKE: every tile
combo must route the block table correctly and stay BITWISE equal to
the `ops/kv_cache.paged_attention` oracle (fp32) — timing there is the
Pallas interpreter's, i.e. meaningless, and is printed only on a real
TPU. On-chip the sweep times each combo with a fenced device→host
fetch and rotates input batches so no two launches are byte-identical;
the winning tile pair is what `BIGDL_PAGED_DECODE_TILES` should pin.
On-chip timings: not measured (ROADMAP A1) — chip_smoke.py establishes
only that the kernel compiles under Mosaic and matches the oracle.

The env-knob leg exercises the import-snapshot contract end to end:
mutate `BIGDL_PAGED_DECODE_TILES`, call `envknobs.refresh()`, build a
FRESH jit root (utils/envknobs discipline — never read env at trace
time), and check the launch resolved the env tiles.

Usage: [JAX_PLATFORMS=cpu] python scripts/sweep_paged_decode.py
       [--heads 8] [--head-dim 64] [--blocks 16] [--block-size 16]
       [--batch 4]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _setup(args):
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.ops.kv_cache import init_block_pool

    rng = np.random.RandomState(0)
    b, h, d = args.batch, args.heads, args.head_dim
    nb, bs = args.blocks, args.block_size
    pool_n = b * nb + 1                       # block 0 = reserved scratch
    shape = jax.eval_shape(
        lambda: init_block_pool(pool_n, h, bs, d))[0].shape
    k_pool = jnp.asarray(rng.randn(*shape), jnp.float32)
    v_pool = jnp.asarray(rng.randn(*shape), jnp.float32)
    # each row owns a disjoint, shuffled block chain (never block 0):
    # the routing the index maps must reproduce
    ids = rng.permutation(np.arange(1, pool_n))[:b * nb]
    table = jnp.asarray(ids.reshape(b, nb), jnp.int32)
    # ragged clocks, incl. one row mid-block
    pos = jnp.asarray(
        rng.randint(bs, nb * bs, size=b), jnp.int32)
    qs = [jnp.asarray(rng.randn(b, h, 1, d), jnp.float32)
          for _ in range(4)]                  # rotated inputs
    return qs, k_pool, v_pool, table, pos


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--blocks", type=int, default=16,
                    help="table width (logical cache blocks per row)")
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    args = ap.parse_args(argv)

    from bigdl_tpu.utils.engine import setup_compile_cache

    setup_compile_cache()

    import jax
    import jax.numpy as jnp

    from bigdl_tpu.ops.kv_cache import paged_attention
    from bigdl_tpu.ops.paged_decode import paged_decode_attention
    from bigdl_tpu.utils import envknobs

    on_tpu = jax.devices()[0].platform == "tpu"
    impl = "pallas" if on_tpu else "interpret"
    qs, k_pool, v_pool, table, pos = _setup(args)
    ref = paged_attention(qs[0], k_pool, v_pool, table, pos)

    tiles = [(bt, ht)
             for bt in (1, 2, 4, 8, 16) if args.blocks % bt == 0
             for ht in (1, 2, 4, 8) if args.heads % ht == 0]
    for bt, ht in tiles:
        try:
            fn = jax.jit(lambda q, _bt=bt, _ht=ht: paged_decode_attention(
                q, k_pool, v_pool, table, pos, impl=impl,
                block_tile=_bt, head_tile=_ht))
            out = fn(qs[0])
            err = float(jnp.max(jnp.abs(out - ref)))
            bitwise = bool(jnp.array_equal(out, ref))
            row = {"tiles": f"{bt}x{ht}", "max_err_vs_oracle": err,
                   "bitwise": bitwise}
            if on_tpu:
                float(fn(qs[1]).sum())        # compile + warm outside timing
                t0 = time.perf_counter()
                acc = 0.0
                for i in range(20):
                    acc += float(fn(qs[i % len(qs)]).sum())  # fenced fetch
                row["ms"] = round((time.perf_counter() - t0) / 20 * 1e3, 3)
                # VMEM residency the scratch pair charges this combo
                row["scratch_kb"] = round(
                    2 * ht * args.blocks * args.block_size
                    * args.head_dim * 4 / 1024, 1)
            else:
                assert bitwise, f"interpret tiles {bt}x{ht} not bitwise"
        except Exception as e:                # pragma: no cover - report
            row = {"tiles": f"{bt}x{ht}", "FAILED": str(e)[:140]}
        print(json.dumps(row), flush=True)

    # env-knob leg: snapshot discipline round-trip (fresh jit root)
    old = os.environ.get("BIGDL_PAGED_DECODE_TILES")
    os.environ["BIGDL_PAGED_DECODE_TILES"] = "2x2"
    try:
        envknobs.refresh()
        fn = jax.jit(lambda q: paged_decode_attention(
            q, k_pool, v_pool, table, pos, impl=impl))
        env_ok = bool(jnp.array_equal(fn(qs[0]), ref)) \
            and envknobs.PAGED_DECODE_TILES == (2, 2)
    finally:
        if old is None:
            os.environ.pop("BIGDL_PAGED_DECODE_TILES", None)
        else:
            os.environ["BIGDL_PAGED_DECODE_TILES"] = old
        envknobs.refresh()
    print(json.dumps({"env_knob_roundtrip": env_ok}), flush=True)
    if not on_tpu:
        print(json.dumps({
            "note": "interpret-mode smoke only — on-chip ms/tile is "
                    "not measured (ROADMAP A1)"},
        ), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
