"""BiLSTM train-step sweep — recurrent-path attribution (VERDICT r3
item 3; BASELINE config 4).

Sweeps the levers that matter for a latency-bound scan: input-proj
hoisting (one big MXU matmul outside the scan), lax.scan unroll, batch,
and — since the persistent-RNN kernel (ops/fused_rnn.py) — the fused
kernel itself with its batch-tile/residency knobs. Full train step
identical to bench.py's bench_bilstm.

Usage:
  python scripts/profile_bilstm.py [--iters 16]     # classic levers
  python scripts/profile_bilstm.py --fused-sweep    # kernel tile sweep:
      scan-vs-kernel A/B, one-launch-bidir vs two uni launches
      (residency), and BIGDL_FUSED_RNN_BLOCK_N batch-tile points
Each fused config compiles its own jit step, so the env tile knob is
read fresh at trace time (flash-attention env-knob convention).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def run_config(tag, batch, seq, unroll, hoist, iters, fused=False,
               block_n=None, bidir_fused=True):
    import jax
    import jax.numpy as jnp

    from bigdl_tpu import nn
    from bigdl_tpu.models import rnn
    from bigdl_tpu.ops.losses import build_train_loss
    from bigdl_tpu.optim import Adam
    from bigdl_tpu.utils.precision import DEFAULT_MIXED as POLICY

    from bigdl_tpu.ops.fused_rnn import resolve_impl

    if block_n is not None:
        os.environ["BIGDL_FUSED_RNN_BLOCK_N"] = str(block_n)
    else:
        os.environ.pop("BIGDL_FUSED_RNN_BLOCK_N", None)
    # knobs are snapshotted at import (graftlint trace-env-read) —
    # an in-process sweep must re-snapshot explicitly; safe here
    # because every config builds a FRESH jitted step below, so the
    # new tile re-traces instead of hitting a stale jit cache
    from bigdl_tpu.utils import envknobs
    envknobs.refresh()
    # record what will ACTUALLY run, not what was requested: a fused
    # config that resolves to the lax.scan fallback (no TPU, kill
    # switch exported) would otherwise produce sweep rows measuring
    # the wrong path with no way to tell (the flash bwd-tiles-ignored
    # lesson, ADVICE r05)
    rnn_impl = resolve_impl(128) if fused else "xla"
    if fused and rnn_impl == "xla":
        print(json.dumps({"config": tag, "SKIPPED":
                          "fused requested but resolve_impl -> xla "
                          "(no TPU / BIGDL_FUSED_RNN=0); row would "
                          "measure the scan path mislabeled"}),
              flush=True)
        return
    model = rnn.bilstm_sentiment(20000, embed_dim=128, hidden_size=128,
                                 fused=None if fused else False)
    bi = model[1]  # BiRecurrent
    for r in (bi.fwd, bi.bwd):
        r.unroll = unroll
        r.hoist_inputs = hoist
    if fused and not bidir_fused:
        # residency A/B: keep the per-direction persistent kernels but
        # drop the one-launch bidirectional fusion
        bi.fused = False
    variables = model.init(jax.random.PRNGKey(0))
    method = Adam(1e-3)
    loss_call = build_train_loss(model, nn.ClassNLLCriterion(), POLICY)

    @jax.jit
    def step(bx, by, carry):
        params, slots = carry
        (loss, _), grads = jax.value_and_grad(
            lambda p: loss_call(p, variables["state"], bx, by,
                                jax.random.PRNGKey(1)),
            has_aux=True)(params)
        new_params, new_slots = method.update(
            grads, params, slots, jnp.asarray(1e-3), jnp.asarray(0))
        return (new_params, new_slots), loss

    carry = (variables["params"], method.init_slots(variables["params"]))
    rng = np.random.RandomState(0)
    pool = [(jnp.asarray(rng.randint(0, 20000, (batch, seq)), jnp.int32),
             jnp.asarray(rng.randint(0, 2, batch), jnp.int32))
            for _ in range(4)]
    try:
        carry, loss = step(*pool[0], carry)
        float(loss)
        t0 = time.perf_counter()
        for i in range(iters):
            carry, loss = step(*pool[(i + 1) % 4], carry)
        float(loss)
        dt = (time.perf_counter() - t0) / iters
        e = h = 128
        flops = 3 * batch * 2 * seq * 8 * h * (e + h)
        from bigdl_tpu.utils.engine import bf16_utilization

        mfu = bf16_utilization(flops / dt)
        print(json.dumps({
            "config": tag, "batch": batch, "seq": seq, "unroll": unroll,
            "hoist": hoist, "fused": fused, "rnn_impl": rnn_impl,
            "block_n": block_n,
            "bidir_fused": bidir_fused if fused else None,
            "step_ms": round(dt * 1e3, 2),
            "samples_per_sec": round(batch / dt, 1),
            "mfu": None if mfu is None else round(mfu, 4),
        }), flush=True)
    except Exception as exc:
        print(json.dumps({"config": tag, "FAILED": str(exc)[:160]}),
              flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=16)
    ap.add_argument("--fused-sweep", action="store_true",
                    help="persistent-kernel tile/residency sweep "
                         "instead of the classic lever sweep")
    args = ap.parse_args()

    from bigdl_tpu.utils.engine import setup_compile_cache

    setup_compile_cache()

    if args.fused_sweep:
        # A/B anchor: the shipped lax.scan path at the bench shape
        run_config("scan_hoist", 128, 128, 1, True, args.iters,
                   fused=False)
        # one-launch bidirectional kernel, default tile
        run_config("fused_bidir", 128, 128, 1, True, args.iters,
                   fused=True)
        # residency A/B: two per-direction launches (flip-based)
        run_config("fused_uni_x2", 128, 128, 1, True, args.iters,
                   fused=True, bidir_fused=False)
        # batch-tile sweep (rows per grid cell; VMEM-resident carry size)
        for bn in (32, 64, 128):
            run_config(f"fused_bidir_bn{bn}", 128, 128, 1, True,
                       args.iters, fused=True, block_n=bn)
        # batch scaling with the kernel
        for b in (512, 1024):
            run_config(f"fused_bidir_b{b}", b, 128, 1, True, args.iters,
                       fused=True)
        return

    # r3 shipped shape first (the baseline row), then the levers
    run_config("baseline_nohoist", 128, 128, 1, False, args.iters)
    run_config("hoist", 128, 128, 1, True, args.iters)
    run_config("hoist_unroll8", 128, 128, 8, True, args.iters)
    run_config("hoist_unroll16", 128, 128, 16, True, args.iters)
    run_config("hoist_unroll8_b512", 512, 128, 8, True, args.iters)
    run_config("hoist_unroll8_b1024", 1024, 128, 8, True, args.iters)


if __name__ == "__main__":
    main()
