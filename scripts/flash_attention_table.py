"""The chip table behind `ops/flash_attention.flash_attention_plan`'s
constants: ms a call of the Mosaic forward and of the fused backward at
`gpt2m-train`'s own attention shape (128 batch-heads x 1,024 x 64,
bfloat16, causal), each candidate walk beside the parent's kernels.

    python scripts/flash_attention_table.py            # on the chip

A row is (tile rows x columns, rows of q x of k, v a grid cell holds).
The parent's kernels come from a second checkout (`--parent .parent`,
made with `git archive`), loaded beside this tree's in one process.
Timings: the median of `--reps` timings of `--calls` calls chained
inside one jitted loop (the forward's output is the next call's q, the
backward's dq the next call's do), so no dispatch and no extra pass
over the operands is in the number; the wrappers' own pads and slices
are. `grad` rows time `jax.grad` of the public `flash_attention` with
its default plan. Every row is checked against `attention_reference`
in float32. Writes `chiprun_out/flash_attention_table.json` and prints
one line a reading, each with the platform and the device it was read
on. It times on a TPU only and raises without one: the wiring is
rehearsed by tests/test_attention.py's `impl="interpret"` cases.
"""

import argparse
import importlib
import importlib.util
import json
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp
from jax import lax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

fa = importlib.import_module("bigdl_tpu.ops.flash_attention")

# (tile q, tile k, cell q, cell k): a cell of the whole sequence walks
# static segments, a smaller one strips under program_id's predicates
FWD = [(256, 256, 1024, 1024), (128, 128, 1024, 1024), (256, 128, 1024, 1024),
       (128, 256, 1024, 1024), (512, 256, 1024, 1024), (512, 128, 1024, 1024),
       (512, 512, 1024, 1024), (1024, 1024, 1024, 1024),
       (256, 256, 512, 1024), (256, 256, 512, 512), (512, 512, 512, 512)]
BWD = [(256, 256, 1024, 1024), (128, 128, 1024, 1024), (256, 128, 1024, 1024),
       (128, 256, 1024, 1024), (512, 256, 1024, 1024), (256, 512, 1024, 1024),
       (512, 512, 1024, 1024), (512, 128, 1024, 1024), (128, 512, 1024, 1024),
       (1024, 1024, 1024, 1024),
       (256, 256, 512, 1024), (256, 256, 512, 512), (512, 512, 512, 512)]
# the parent's walk is its grid's: (tile q, tile k), its defaults first
PARENT_FWD = [(1024, 1024), (512, 512), (256, 256)]
PARENT_BWD = [(512, 1024), (512, 512), (256, 256)]


def load_parent(path):
    src = os.path.join(path, "bigdl_tpu", "ops", "flash_attention.py")
    if not os.path.exists(src):
        return None
    spec = importlib.util.spec_from_file_location("parent_flash_attention",
                                                  src)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def timed(step, carry, calls, reps):
    """ms a call of `step` (carry -> carry), chained `calls` times."""
    run = jax.jit(lambda c: lax.fori_loop(0, calls, lambda _, c: step(c), c))
    jax.block_until_ready(run(carry))
    times = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(run(carry))
        times.append((time.perf_counter() - t) / calls * 1e3)
    return statistics.median(times)


def rel_err(got, want):
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--bh", type=int, default=128)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--calls", type=int, default=50)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--parent", default=os.path.join(ROOT, ".parent"))
    ap.add_argument("--tag", default="", help="a suffix of the json's name")
    ap.add_argument("--only", default="",
                    help="fwd, bwd or grad: that part of the table")
    args = ap.parse_args()
    bh, s, d = args.bh, args.seq, args.dim
    scale = d ** -0.5
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"flash_attention_table times Mosaic kernels on a "
                         f"TPU; found {dev.platform} ({dev.device_kind})")
    where = {"platform": dev.platform, "device_kind": dev.device_kind}
    parent = load_parent(args.parent)

    key = jax.random.PRNGKey(args.seed)
    q, k, v, do = (jax.random.normal(jax.random.fold_in(key, i), (bh, s, d),
                                     jnp.bfloat16) for i in range(4))

    def ref_loss(q, k, v):
        out = fa.attention_reference(q, k, v, causal=True)
        return jnp.sum(out * do.astype(jnp.float32)), out

    with jax.default_matmul_precision("highest"):
        f32 = [x.astype(jnp.float32) for x in (q, k, v)]
        (ref_grads, ref_out) = jax.jit(jax.grad(
            ref_loss, argnums=(0, 1, 2), has_aux=True))(*f32)
    out, lse = jax.jit(lambda q, k, v: fa._flash_fwd_pallas(
        q, k, v, True, scale, 256, 256, fa._cell(256, s), fa._cell(256, s),
        False))(q, k, v)

    rows = []
    # a whole-sequence cell unrolls its walk (a minute of compile a row
    # at 8,192): past 4,096 only the 512-row tiles
    whole = [(t, t, s, s) for t in (128, 256, 512)
             if s <= 4096 or t == 512] if s > 1024 else []
    fwd = [r for r in FWD + whole if max(r) <= s]
    bwd = [r for r in BWD + whole if max(r) <= s]
    if s > 1024:        # the square tiles only, in cells of 1,024 and whole
        fwd, bwd = ([r for r in rows if r[0] == r[1] and r[2] == r[3]
                     and r[2] in (1024, s)] for rows in (fwd, bwd))
    # the parent took one whole-sequence tile up to 64 batch-heads x 2,048
    parent_fwd = PARENT_FWD + ([(s, s)] if s == 2048 else [])

    def report(row):
        rows.append(row)
        print(json.dumps({**row, **where}), flush=True)

    def forward(name, call):
        try:
            err = rel_err(jax.jit(call)(q, k, v)[0], ref_out)
            ms = timed(lambda c: call(c, k, v)[0], q, args.calls, args.reps)
            report({"kernel": "flash_fwd", "row": name, "ms": ms,
                    "err": err})
        except Exception as e:                        # a refused tiling
            report({"kernel": "flash_fwd", "row": name,
                    "failed": str(e)[:200]})

    def backward(name, call):
        try:
            grads = jax.jit(call)(q, k, v, out, lse, do)
            err = max(rel_err(g, r) for g, r in zip(grads, ref_grads))
            ms = timed(lambda c: call(q, k, v, out, lse, c)[0], do,
                       args.calls, args.reps)
            report({"kernel": "flash_bwd_fused", "row": name, "ms": ms,
                    "err": err})
        except Exception as e:
            report({"kernel": "flash_bwd_fused", "row": name,
                    "failed": str(e)[:200]})

    def grad(name, attention):
        def loss(q, k, v):
            return jnp.sum(attention(q, k, v, causal=True).astype(
                jnp.float32) * do.astype(jnp.float32))

        g = jax.grad(loss, argnums=(0, 1, 2))
        err = max(rel_err(a, r) for a, r in zip(jax.jit(g)(q, k, v),
                                                ref_grads))
        ms = timed(lambda c: g(c, k, v)[0], q, args.calls, args.reps)
        report({"kernel": "flash_fwd + flash_bwd_fused", "row": name,
                "ms": ms, "err": err})

    if args.only in ("", "fwd"):
        for bq, bk in parent_fwd if parent else ():
            forward(f"parent {bq}x{bk}", lambda q, k, v, bq=bq, bk=bk:
                    parent._flash_fwd_pallas(q, k, v, True, scale, bq, bk,
                                             False))
        for bq, bk, cq, ck in fwd:
            forward(f"tile {bq}x{bk} cell {cq}x{ck}",
                    lambda q, k, v, t=(bq, bk), c=(cq, ck):
                    fa._flash_fwd_pallas(q, k, v, True, scale, *t, *c,
                                         False))
    if args.only in ("", "bwd"):
        for bq, bk in PARENT_BWD if parent else ():
            backward(f"parent {bq}x{bk}",
                     lambda q, k, v, o, lse, do, bq=bq, bk=bk:
                     parent._flash_bwd_pallas_fused(
                         q, k, v, o, lse, do, True, scale, bq, bk, False))
        for bq, bk, cq, ck in bwd:
            backward(f"tile {bq}x{bk} cell {cq}x{ck}",
                     lambda q, k, v, o, lse, do, t=(bq, bk), c=(cq, ck):
                     fa._flash_bwd_pallas_fused(
                         q, k, v, o, lse, do, True, scale, *t, *c, False))
    if args.only in ("", "grad"):
        if parent:
            grad("parent, its defaults", lambda q, k, v, causal:
                 parent.flash_attention(q, k, v, causal=causal,
                                        impl="pallas"))
        plan = fa.flash_attention_plan(s, s, d, bh, 2, True)
        grad(f"the plan's defaults {plan}", lambda q, k, v, causal:
             fa.flash_attention(q, k, v, causal=causal, impl="pallas"))

    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"flash_attention_table{args.tag}.json"),
              "w") as f:
        json.dump({**where, "shape": [bh, s, d], "dtype": "bfloat16",
                   "causal": True,
                   "seed": args.seed, "calls": args.calls,
                   "reps": args.reps, "rows": rows}, f, indent=1)


if __name__ == "__main__":
    main()
