"""DP weak-scaling harness — the ≥90% AllReduce-scaling north star
(BASELINE.json "north_star"; VERDICT r3 weak item 4).

Measures, for mesh sizes 1, 2, 4, … N on whatever devices exist:
  - weak-scaled DP training step time (per-chip batch held constant, so
    perfect scaling = flat step time; efficiency_N = t_1 / t_N),
  - the gradient collective alone (reduce-scatter + all-gather at the
    flat-parameter size, the exact shape DistriOptimizer issues),
  - the analytic ring bound for that collective on the ICI
    (2·(N−1)/N · bytes / link_bw), and the north-star check
    efficiency ≥ 0.9.

Emits one JSON line per mesh size and a final summary line.

On real hardware (a pod slice) the numbers are the measurement; on the
virtual CPU mesh (--xla_force_host_platform_device_count) the absolute
times are meaningless but every code path — mesh construction, sharding,
collectives, efficiency math, JSON contract — runs, so pod time is spent
measuring, not debugging (CI covers it in tests/test_scaling_bench.py).

Usage:
    python scripts/scaling_bench.py                  # all local devices
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python scripts/scaling_bench.py --model mlp  # plumbing check
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def build_model(name):
    from bigdl_tpu import nn
    from bigdl_tpu.models import resnet

    if name == "resnet50":
        return resnet.build_imagenet(50, 1000), (224, 224, 3), 1000
    if name == "resnet8":
        return resnet.build_cifar(8, 10), (32, 32, 3), 10
    # tiny mlp: fastest plumbing check
    return (nn.Sequential(nn.Reshape([64]), nn.Linear(64, 128), nn.ReLU(),
                          nn.Linear(128, 10), nn.LogSoftMax()),
            (8, 8, 1), 10)


def measure_mesh(n, model_name, per_chip_batch, iters, ici_gbps):
    """One mesh size: DP step time + collective-only time + bounds."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from bigdl_tpu import nn
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.parallel import (FlatParamSpec, make_dp_train_step,
                                    make_mesh)
    from bigdl_tpu.utils.precision import DEFAULT_MIXED

    devices = jax.devices()[:n]
    mesh = make_mesh({"data": n}, devices=devices)
    model, shape, classes = build_model(model_name)
    variables = model.init(jax.random.PRNGKey(0))
    method = SGD(learningrate=0.1, momentum=0.9, dampening=0.0)
    spec = FlatParamSpec(variables["params"], n)

    step = make_dp_train_step(model, nn.ClassNLLCriterion(), method, mesh,
                              spec, axis="data", grad_dtype="bfloat16",
                              precision=DEFAULT_MIXED)
    replicated = NamedSharding(mesh, P())
    batch = per_chip_batch * n
    rng = np.random.RandomState(0)
    pool = [(jax.device_put(
                 rng.rand(batch, *shape).astype(np.float32),
                 NamedSharding(mesh, P("data", None, None, None))),
             jax.device_put(
                 rng.randint(0, classes, batch).astype(np.int32),
                 NamedSharding(mesh, P("data"))))
            for _ in range(2)]

    def run(bx, by, carry):
        flat_w, slots, mod_state = carry
        flat_w, slots, mod_state, loss = step(
            flat_w, slots, mod_state, bx, by,
            jnp.asarray(0.1, jnp.float32), jnp.asarray(0, jnp.int32),
            jax.random.PRNGKey(1))
        return (flat_w, slots, mod_state), loss

    carry = (jax.device_put(spec.flatten(variables["params"]), replicated),
             jax.tree_util.tree_map(
                 lambda s: jax.device_put(s, NamedSharding(mesh, P("data"))),
                 method.init_slots(jnp.zeros((spec.padded,), jnp.float32))),
             jax.device_put(variables["state"], replicated))

    def stepper(i_carry):
        i, carry = i_carry
        carry, loss = run(*pool[i % 2], carry)
        return (i + 1, carry), loss

    # fenced step timing
    (_, carry), loss = stepper((0, carry))
    float(loss)
    t0 = time.perf_counter()
    ic = (1, carry)
    for _ in range(iters):
        ic, loss = stepper(ic)
    float(loss)
    step_s = (time.perf_counter() - t0) / iters

    # collective alone: psum_scatter + all_gather at the wire size the
    # DP step uses (bf16 chunks), via shard_map like the real step
    from jax import lax, shard_map

    # chained inside one jit AND value-varying every iteration: each
    # collective consumes the previous one's output, so the chain is
    # one serial dependency the final fetch fences
    coll_iters = max(iters, 4)

    def coll_chain(flat):
        def body(c, _):
            g = lax.psum_scatter(c.astype(jnp.bfloat16), "data",
                                 scatter_dimension=0, tiled=True)
            out = lax.all_gather(g.astype(jnp.float32), "data", axis=0,
                                 tiled=True)
            return out / n, None  # /n keeps the chained values bounded

        return lax.scan(body, flat, None, length=coll_iters)[0]

    coll_fn = jax.jit(shard_map(coll_chain, mesh=mesh, in_specs=P(),
                                out_specs=P(), check_vma=False))
    flat0 = jax.device_put(spec.flatten(variables["params"]) + 1.0,
                           replicated)
    warm = coll_fn(flat0)  # compile + warmup
    float(jnp.sum(warm[:1]).astype(jnp.float32))
    t0 = time.perf_counter()
    out = coll_fn(warm)  # chained on warmup's output: fresh values
    float(jnp.sum(out[:1]).astype(jnp.float32))
    coll_s = (time.perf_counter() - t0) / coll_iters

    # analytic ring bound: reduce-scatter + all-gather each move
    # (N-1)/N of the buffer over the slowest link
    wire_bytes = spec.padded * 2  # bf16 wire
    bound_s = (0.0 if n == 1 else None if ici_gbps is None else
               2 * (n - 1) / n * wire_bytes / (ici_gbps * 1e9))
    return {
        "devices": n,
        "global_batch": batch,
        "step_ms": round(step_s * 1e3, 3),
        "collective_ms": round(coll_s * 1e3, 3),
        "ici_ring_bound_ms": (None if bound_s is None
                              else round(bound_s * 1e3, 4)),
        "wire_mb": round(wire_bytes / 1e6, 2),
    }


def measure_zero2(n, model_name, per_chip_batch, iters, ckpt_every=50,
                  windows=3, workdir=None):
    """ZeRO-2 row (ISSUE 9): per-step time of the weight-sharded DP
    step at the full mesh size, plus CHECKPOINT-OVERLAP provenance —
    the identical step window re-timed (a) without checkpointing,
    (b) with ASYNC sharded saves every `ckpt_every` steps (host
    snapshot + enqueue on the step path; the disk write overlaps the
    following steps on the background thread), and (c) with
    synchronous saves (the step stalls on the full write — the cost
    async buys back). Each mode takes the median of `windows` timed
    windows (CPU hosts jitter; CLAUDE.md). Acceptance: async-vs-nosave
    per-step within 5%. `ckpt_every` defaults to a realistic cadence:
    the async contract is "steps never stall on I/O", not "snapshots
    are free" — the synchronous host snapshot (device fetch of model
    + shard slices) is the irreducible on-path cost, and the write
    must fit inside `ckpt_every * step_time` of background time to
    fully overlap (at cadence 2 on a 2-core host nothing can hide a
    37 ms write behind 14 ms of compute). The saves go through the REAL sharded path —
    Checkpoint.save_sharded over DistriOptimizer._local_shard_slices
    with the manifest-last publish — so the row measures the shipping
    code, not a stand-in."""
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from bigdl_tpu import nn
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.parallel import (FlatParamSpec, make_dp_train_step,
                                    make_mesh)
    from bigdl_tpu.parallel.distri_optimizer import DistriOptimizer
    from bigdl_tpu.serialization.checkpoint import Checkpoint

    devices = jax.devices()[:n]
    mesh = make_mesh({"data": n}, devices=devices)
    model, shape, classes = build_model(model_name)
    variables = model.init(jax.random.PRNGKey(0))
    method = SGD(learningrate=0.1, momentum=0.9, dampening=0.0)
    spec = FlatParamSpec(variables["params"], n)
    step = make_dp_train_step(model, nn.ClassNLLCriterion(), method, mesh,
                              spec, axis="data", grad_dtype="bfloat16",
                              zero=2)
    unflatten = jax.jit(spec.unflatten)
    sharded = NamedSharding(mesh, P("data"))
    replicated = NamedSharding(mesh, P())
    batch = per_chip_batch * n
    rng = np.random.RandomState(0)
    pool = [(jax.device_put(
                 rng.rand(batch, *shape).astype(np.float32),
                 NamedSharding(mesh, P("data", None, None, None))),
             jax.device_put(
                 rng.randint(0, classes, batch).astype(np.int32),
                 NamedSharding(mesh, P("data"))))
            for _ in range(2)]
    optim_meta = {"layout": "zero2_flat", "num_shards": n,
                  "total": spec.total, "padded": spec.padded}
    tmp = workdir or tempfile.mkdtemp(prefix="scaling_zero2_")

    def fresh_carry():
        return (jax.device_put(spec.flatten(variables["params"]), sharded),
                jax.tree_util.tree_map(
                    lambda s: jax.device_put(s, sharded),
                    method.init_slots(
                        jnp.zeros((spec.padded,), jnp.float32))),
                jax.device_put(variables["state"], replicated))

    def window(mode, tag):
        ck = (None if mode == "nosave" else
              Checkpoint(os.path.join(tmp, tag),
                         sharded=True, async_save=(mode == "async")))
        flat_w, slots, mod_state = fresh_carry()
        loss = None
        t0 = time.perf_counter()
        for i in range(iters):
            flat_w, slots, mod_state, loss = step(
                flat_w, slots, mod_state, *pool[i % 2],
                jnp.asarray(0.1, jnp.float32), jnp.asarray(i, jnp.int32),
                jax.random.PRNGKey(1))
            if ck is not None and (i + 1) % ckpt_every == 0:
                # the real save path: gather/unflatten the model tree,
                # hand per-shard slot slices to the manifest-last writer
                saved = {"params": jax.device_get(unflatten(flat_w)),
                         "state": jax.device_get(mod_state)}
                ck.save_sharded(
                    i + 1, saved,
                    DistriOptimizer._local_shard_slices(slots, spec),
                    nshards=n, optim_meta=optim_meta)
        if ck is not None:
            ck.wait()  # conservative: any un-overlapped tail is charged
        float(loss)    # fence: the fetch waits for the last step
        return (time.perf_counter() - t0) / iters

    # compile + warm the write path outside every timed window
    window("sync", "warmup")
    # windows INTERLEAVED across modes: this host's speed drifts on
    # the tens-of-seconds scale, so mode-batched timing would fold the
    # drift into the mode comparison
    samples = {m: [] for m in ("nosave", "async", "sync")}
    for w in range(windows):
        for mode in samples:
            samples[mode].append(window(mode, f"{mode}{w}"))
    times = {m: sorted(v)[windows // 2] for m, v in samples.items()}
    if workdir is None:
        shutil.rmtree(tmp, ignore_errors=True)
    nosave, async_t, sync_t = (times["nosave"], times["async"],
                               times["sync"])
    return {
        "devices": n, "zero": 2, "global_batch": batch,
        "step_ms": round(nosave * 1e3, 3),
        "ckpt_overlap": {
            "cadence_steps": ckpt_every,
            "nosave_step_ms": round(nosave * 1e3, 3),
            "async_step_ms": round(async_t * 1e3, 3),
            "sync_step_ms": round(sync_t * 1e3, 3),
            "async_overhead_frac": round(async_t / nosave - 1.0, 4),
            "sync_overhead_frac": round(sync_t / nosave - 1.0, 4),
            "async_within_5pct": bool(async_t <= nosave * 1.05),
        },
        "provenance": {"layout": "zero2_flat", "nshards": n,
                       "sharded_ckpt": True, "manifest_last": True,
                       "windows": windows, "iters": iters},
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet8",
                    choices=["mlp", "resnet8", "resnet50"])
    ap.add_argument("--per-chip-batch", type=int, default=None)
    ap.add_argument("--iters", type=int, default=8)
    ap.add_argument("--ici-gbps", type=float, default=None,
                    help="chip-to-chip GB/s for the ring bound "
                         "(default: the device's published figure, "
                         "utils/engine.DEVICE_PEAKS; none off-TPU)")
    ap.add_argument("--out", default=None, help="also write JSON here")
    ap.add_argument("--no-zero2", action="store_true",
                    help="skip the zero2 checkpoint-overlap row (it "
                         "needs >=120 steps per window regardless of "
                         "--iters, so quick plumbing runs can opt out)")
    args = ap.parse_args()

    from bigdl_tpu.utils.engine import setup_compile_cache

    setup_compile_cache()

    import jax

    n_all = len(jax.devices())
    on_tpu = jax.devices()[0].platform == "tpu"
    per_chip = args.per_chip_batch or (
        {"mlp": 64, "resnet8": 32, "resnet50": 128}[args.model]
        if on_tpu else {"mlp": 16, "resnet8": 8, "resnet50": 2}[args.model])

    sizes = []
    n = 1
    while n <= n_all:
        sizes.append(n)
        n *= 2
    if sizes[-1] != n_all:
        sizes.append(n_all)

    ici_gbps = args.ici_gbps
    if ici_gbps is None and on_tpu:
        from bigdl_tpu.utils.engine import device_peaks

        ici_gbps = device_peaks()["ici_bytes_per_s"] / 1e9

    rows = []
    for n in sizes:
        row = measure_mesh(n, args.model, per_chip, args.iters, ici_gbps)
        rows.append(row)
        print(json.dumps(row), flush=True)

    # ZeRO-2 + checkpoint-overlap row at the full mesh size (ISSUE 9);
    # enough steps per window for >=2 saves at the default cadence
    zero2_row = None
    if not args.no_zero2:
        zero2_row = measure_zero2(n_all, args.model, per_chip,
                                  max(args.iters, 120))
        print(json.dumps(zero2_row), flush=True)

    t1 = rows[0]["step_ms"]
    summary = {
        "model": args.model,
        "platform": jax.devices()[0].platform,
        "per_chip_batch": per_chip,
        "weak_scaling_efficiency": {
            str(r["devices"]): round(t1 / r["step_ms"], 4) for r in rows},
        "north_star_ge_90pct": bool(
            t1 / rows[-1]["step_ms"] >= 0.9) if len(rows) > 1 else None,
        "note": ("absolute times are meaningless off-TPU; this run "
                 "validates plumbing only" if not on_tpu else
                 "fenced-fetch methodology, bf16 gradient wire"),
        "rows": rows,
        "zero2": zero2_row,
    }
    print(json.dumps(summary))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(summary, f, indent=1)


if __name__ == "__main__":
    main()
