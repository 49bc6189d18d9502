"""ResNet-50 / Inception-v1 train-step profile — decomposed fenced
timings + ablations (round-4 attribution, VERDICT r3 item 1).

Methodology identical to scripts/profile_lm.py: the primary instrument
is component decomposition (written when no device-side profiler trace
could be had) — each stage of the network (stem, stage1..4,
head) and each ablated full step (frozen-BN, no-BN, one-pass-var BN) is
jitted separately and timed with the fenced-fetch methodology (bench.py
"Measurement notes": serial chaining inside one jit, final host fetch,
rotating inputs are unnecessary here because the chain perturbs its own
input each iteration).

Reference parity: models/utils/LocalOptimizerPerf.scala-style synthetic
harness (SURVEY.md §5.1) specialized to the vision flagship.

Usage:
    python scripts/profile_resnet.py                    # resnet50, B=256
    python scripts/profile_resnet.py --model inception_v1
    python scripts/profile_resnet.py --skip-components  # full steps only
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def fenced(fn, args, iters, fetch):
    out = fn(*args)
    float(fetch(out))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    float(fetch(out))
    return (time.perf_counter() - t0) / iters


def measure(report, key, fn, args, iters, fetch):
    try:
        t = fenced(fn, args, iters, fetch)
        report[key] = round(t * 1e3, 3)
    except Exception as e:
        report[key] = f"FAILED: {str(e)[:160]}"
    print(json.dumps({key: report[key]}), flush=True)


CHAIN_N, CHAIN_REPS = 6, 3  # overridden by --chain-n/--chain-reps


def chain_stage(report, key, apply_fn, x0, n=None, reps=None):
    """Per-call time of `apply_fn(x)` (arbitrary out-shape) with the
    dispatch floor amortized: serialize n calls inside one jit by
    coupling each call's input to the previous call's output through a
    scalar (+ c*eps forces the data dependence; compiler cannot hoist)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    n = n or CHAIN_N
    reps = reps or CHAIN_REPS

    def body(c, _):
        y = apply_fn(x0 + c.astype(x0.dtype))
        return jnp.sum(y).astype(jnp.float32) * 1e-30, None

    looped = jax.jit(lambda c: lax.scan(body, c, None, length=n)[0])
    try:
        c = looped(jnp.zeros((), jnp.float32))
        float(c)
        t0 = time.perf_counter()
        for _ in range(reps):
            c = looped(c)
        float(c)
        report[key] = round((time.perf_counter() - t0) / (reps * n) * 1e3, 3)
    except Exception as e:
        report[key] = f"FAILED: {str(e)[:160]}"
    print(json.dumps({key: report[key]}), flush=True)


def _xla_fwd_flops(fn, *args):
    try:
        ca = fn.lower(*args).compile().cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        f = float(ca.get("flops", 0.0))
        return f if f > 0 else None
    except Exception:
        return None


def build_model(name, bn_mode="train"):
    """bn_mode: train = normal; none = BN layers replaced by Identity."""
    from bigdl_tpu import nn
    from bigdl_tpu.models import inception, resnet

    model = (resnet.build_imagenet(50, 1000) if name == "resnet50"
             else inception.build(1000))
    if bn_mode == "none":
        def strip(container):
            for i, m in enumerate(container.modules):
                if isinstance(m, nn.SpatialBatchNormalization):
                    container.modules[i] = nn.Identity()
                elif hasattr(m, "modules"):
                    strip(m)
        strip(model)
    return model


def make_step(model, method, policy, frozen_bn=False):
    """Full train step exactly as bench.py's bench_vision builds it.
    frozen_bn: run the model with training=False inside the loss (BN
    normalizes with running stats — no batch reductions) while still
    taking grads; isolates the cost of BN's train-mode statistics."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu import nn
    from bigdl_tpu.ops.losses import build_train_loss

    if not frozen_bn:
        loss_call = build_train_loss(model, nn.ClassNLLCriterion(), policy)
    else:
        crit = nn.ClassNLLCriterion()

        def loss_call(p, mod_state, x, y, rng):
            p = policy.cast_to_compute(p)
            x = policy.cast_to_compute(x)
            out, _ = model.apply(
                {"params": p, "state": policy.cast_to_compute(mod_state)},
                x, training=False, rng=rng)
            # return the ORIGINAL f32 state: returning the cast copy
            # changes the carry dtype between warmup and the timed
            # loop, landing a recompile inside the timed region
            # (memory: tpu-measurement-gotchas)
            return crit(policy.cast_to_output(out), y), mod_state

    @jax.jit
    def step(bx, by, carry):
        params, state, slots = carry
        (loss, new_state), grads = jax.value_and_grad(
            lambda p: loss_call(p, state, bx, by, jax.random.PRNGKey(1)),
            has_aux=True)(params)
        new_params, new_slots = method.update(
            grads, params, slots, jnp.asarray(0.1), jnp.asarray(0))
        return (new_params, new_state, new_slots), loss

    return step


def run_full(report, key, model, batch, iters, policy):
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.optim import SGD

    method = SGD(learningrate=0.1, momentum=0.9, dampening=0.0)
    variables = model.init(jax.random.PRNGKey(0))
    step = make_step(model, method, policy,
                     frozen_bn=key.endswith("frozen_bn"))
    carry = ((variables["params"], variables["state"],
              method.init_slots(variables["params"])))
    rng = np.random.RandomState(0)
    pool = [(jnp.asarray(rng.rand(batch, 224, 224, 3).astype(np.float32)),
             jnp.asarray(rng.randint(0, 1000, batch).astype(np.int32)))
            for _ in range(4)]
    try:
        carry, loss = step(*pool[0], carry)
        float(loss)
        t0 = time.perf_counter()
        for i in range(iters):
            carry, loss = step(*pool[(i + 1) % 4], carry)
        float(loss)
        dt = (time.perf_counter() - t0) / iters
        report[key] = {"step_ms": round(dt * 1e3, 2),
                       "images_per_sec": round(batch / dt, 1)}
    except Exception as e:
        report[key] = f"FAILED: {str(e)[:160]}"
    print(json.dumps({key: report[key]}), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet50",
                    choices=["resnet50", "inception_v1"])
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--iters", type=int, default=12)
    ap.add_argument("--skip-components", action="store_true")
    ap.add_argument("--skip-ablations", action="store_true")
    ap.add_argument("--skip-full", action="store_true")
    ap.add_argument("--only-stage", default=None,
                    help="comma list: stem,stage1..stage4,head,micro")
    ap.add_argument("--chain-n", type=int, default=6)
    ap.add_argument("--chain-reps", type=int, default=3)
    args = ap.parse_args()

    from bigdl_tpu.utils.engine import setup_compile_cache

    setup_compile_cache()

    global CHAIN_N, CHAIN_REPS
    CHAIN_N, CHAIN_REPS = args.chain_n, args.chain_reps

    import jax
    import jax.numpy as jnp

    from bigdl_tpu import nn
    from bigdl_tpu.models import resnet as R
    from bigdl_tpu.utils.precision import DEFAULT_MIXED as policy

    B = args.batch
    report = {"config": {"model": args.model, "batch": B}}
    rng = np.random.RandomState(0)

    # ---- full-step baselines + ablations ----------------------------
    if not args.skip_full:
        run_full(report, "full_step", build_model(args.model), B,
                 args.iters, policy)
    if not (args.skip_ablations or args.skip_full):
        run_full(report, "full_step_frozen_bn", build_model(args.model),
                 B, args.iters, policy)
        run_full(report, "full_step_no_bn",
                 build_model(args.model, bn_mode="none"), B, args.iters,
                 policy)

    if args.skip_components or args.model != "resnet50":
        print(json.dumps(report, indent=1))
        return

    # ---- per-stage decomposition (resnet50) -------------------------
    # Shapes at B: stem (B,224,224,3)->(B,56,56,64); s1 ->(B,56,56,256);
    # s2 ->(B,28,28,512); s3 ->(B,14,14,1024); s4 ->(B,7,7,2048).
    def seq(*mods):
        return nn.Sequential(*mods)

    stages = {
        "stem": (seq(R._conv(3, 64, 7, 2, 3), R._bn(64), nn.ReLU(),
                     nn.SpatialMaxPooling(3, 3, 2, 2, 1, 1)),
                 (B, 224, 224, 3)),
        "stage1": (seq(R.bottleneck(64, 64, 1),
                       R.bottleneck(256, 64), R.bottleneck(256, 64)),
                   (B, 56, 56, 64)),
        "stage2": (seq(R.bottleneck(256, 128, 2),
                       *[R.bottleneck(512, 128) for _ in range(3)]),
                   (B, 56, 56, 256)),
        "stage3": (seq(R.bottleneck(512, 256, 2),
                       *[R.bottleneck(1024, 256) for _ in range(5)]),
                   (B, 28, 28, 512)),
        "stage4": (seq(R.bottleneck(1024, 512, 2),
                       *[R.bottleneck(2048, 512) for _ in range(2)]),
                   (B, 14, 14, 1024)),
        "head": (seq(nn.SpatialAveragePooling(7, 7, 1, 1),
                     nn.Reshape([2048]), nn.Linear(2048, 1000),
                     nn.LogSoftMax()),
                 (B, 7, 7, 2048)),
        # single interior bottlenecks (stage × block-count estimates the
        # stage; whole-stage graphs reproducibly hang the remote compile
        # service — see tpu-measurement-gotchas)
        "block1": (seq(R.bottleneck(256, 64)), (B, 56, 56, 256)),
        "block2": (seq(R.bottleneck(512, 128)), (B, 28, 28, 512)),
        "block3": (seq(R.bottleneck(1024, 256)), (B, 14, 14, 1024)),
        "block4": (seq(R.bottleneck(2048, 512)), (B, 7, 7, 2048)),
    }

    only = (set(args.only_stage.split(",")) if args.only_stage else None)
    for name, (stage, shape) in stages.items():
        if only is not None and name not in only:
            continue
        variables = stage.init(jax.random.PRNGKey(0))
        pc = policy.cast_to_compute(variables["params"])
        st = variables["state"]
        x0 = jnp.asarray(rng.rand(*shape), jnp.bfloat16)

        def fwd(x, _pc=pc, _st=st, _stage=stage):
            return _stage.apply({"params": _pc, "state": _st}, x,
                                training=True)[0]

        chain_stage(report, f"{name}_fwd_ms", fwd, x0)

        # fwd+bwd: grads wrt params AND input (params-only would DCE
        # nothing but input-only would DCE all the dW work — see
        # memory: attention-kernel-tuning "misleading micro-benchmarks")
        def loss(p, x, _st=st, _stage=stage):
            y = _stage.apply({"params": p, "state": _st}, x,
                             training=True)[0]
            return jnp.sum(y.astype(jnp.float32))

        g = jax.grad(loss, argnums=(0, 1))

        def fwdbwd(x, _g=g, _pc=pc):
            gp, gx = _g(_pc, x)
            extra = sum(jnp.sum(l).astype(jnp.float32)
                        for l in jax.tree_util.tree_leaves(gp))
            return gx + (extra * 1e-30).astype(gx.dtype)

        chain_stage(report, f"{name}_fwdbwd_ms", fwdbwd, x0,
                    n=max(1, CHAIN_N - 2))

        # XLA fwd flops per stage (conv nets: no scan, count is usable)
        jf = jax.jit(fwd)
        fl = _xla_fwd_flops(jf, x0)
        if fl:
            report[f"{name}_fwd_gflops"] = round(fl / 1e9, 1)
            if isinstance(report.get(f"{name}_fwd_ms"), float):
                report[f"{name}_fwd_tflops"] = round(
                    fl / (report[f"{name}_fwd_ms"] / 1e3) / 1e12, 1)
            print(json.dumps({f"{name}_fwd_gflops":
                              report[f"{name}_fwd_gflops"],
                              f"{name}_fwd_tflops":
                              report.get(f"{name}_fwd_tflops")}),
                  flush=True)

    # ---- BN microcosts at a representative shape --------------------
    # conv3x3 alone vs conv+bn+relu at stage-2 interior shape
    if only is not None and "micro" not in only:
        print(json.dumps(report, indent=1))
        return
    shape = (B, 28, 28, 128)
    x0 = jnp.asarray(rng.rand(*shape), jnp.bfloat16)
    convm = seq(R._conv(128, 128, 3, 1, 1))
    cbr = seq(R._conv(128, 128, 3, 1, 1), R._bn(128), nn.ReLU())
    for nm, m in [("conv3x3_alone", convm), ("conv3x3_bn_relu", cbr)]:
        v = m.init(jax.random.PRNGKey(0))
        pc = policy.cast_to_compute(v["params"])

        def f(x, _pc=pc, _st=v["state"], _m=m):
            return _m.apply({"params": _pc, "state": _st}, x,
                            training=True)[0]

        chain_stage(report, f"{nm}_fwd_ms", f, x0, n=CHAIN_N + 2)

    print(json.dumps(report, indent=1))


if __name__ == "__main__":
    main()
