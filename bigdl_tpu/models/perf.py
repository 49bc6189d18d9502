"""Synthetic-data throughput harness.

Reference parity: models/utils/LocalOptimizerPerf.scala and
DistriOptimizerPerf.scala — per-model synthetic benchmark binaries
(SURVEY.md §5.1). CLI:

    python -m bigdl_tpu.models.perf --model resnet50 -b 64 -i 20
    python -m bigdl_tpu.models.perf --model lenet --mesh data=8
"""

from __future__ import annotations

import argparse
import json
import logging
import sys
import time
from typing import Optional

import numpy as np

from bigdl_tpu import obs


def _build_model(name: str, class_num: int):
    from bigdl_tpu.models import alexnet, inception, lenet, resnet, vgg

    name = name.lower()
    table = {
        "lenet": (lambda: lenet.build(10), (28, 28, 1), 10),
        "resnet50": (lambda: resnet.build_imagenet(50, class_num), (224, 224, 3), class_num),
        "resnet18": (lambda: resnet.build_imagenet(18, class_num), (224, 224, 3), class_num),
        "resnet20-cifar": (lambda: resnet.build_cifar(20, 10), (32, 32, 3), 10),
        "inception-v1": (lambda: inception.build(class_num), (224, 224, 3), class_num),
        "inception-v2": (lambda: inception.build_v2(class_num), (224, 224, 3), class_num),
        "vgg16": (lambda: vgg.build(16, class_num), (224, 224, 3), class_num),
        "alexnet": (lambda: alexnet.build(class_num), (224, 224, 3), class_num),
    }
    if name not in table:
        raise SystemExit(f"unknown model {name!r}; choices: {sorted(table)}")
    build, shape, classes = table[name]
    return build(), shape, classes


def run_perf(model_name: str = "resnet50", batch_size: int = 32,
             iterations: int = 10, mesh_axes: Optional[str] = None,
             optimizer: str = "sgd", class_num: int = 1000,
             precision: Optional[str] = None) -> dict:
    """Steady-state throughput of the jitted train step: one warmup step
    (compile), then `iterations` timed steps. Timing is fenced by a real
    device-to-host fetch of the final loss — the last step depends on
    every prior step's params, and plain block_until_ready can be
    optimistic through remote-device transports (SURVEY.md §5.1;
    see also bench.py). `precision="bf16"` runs the mixed-precision
    configuration (bf16 compute, fp32 master weights)."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu import nn
    from bigdl_tpu.optim import Adam, SGD

    from bigdl_tpu.utils.precision import DEFAULT_MIXED

    policy = DEFAULT_MIXED if precision in ("bf16", "mixed") else None
    model, shape, classes = _build_model(model_name, class_num)
    variables = model.init(jax.random.PRNGKey(0))
    method = (SGD(learningrate=0.01, momentum=0.9, dampening=0.0)
              if optimizer == "sgd" else Adam(1e-3))
    criterion = nn.ClassNLLCriterion()
    rng = np.random.RandomState(0)
    bx_np = rng.rand(batch_size, *shape).astype(np.float32)
    by_np = rng.randint(0, classes, batch_size).astype(np.int32)

    if mesh_axes:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from bigdl_tpu.parallel import (
            FlatParamSpec, make_dp_train_step, make_mesh, parse_axes,
        )

        axes = parse_axes(mesh_axes)
        if "data" not in axes:
            raise SystemExit(
                f"--mesh {mesh_axes!r} has no 'data' axis; the perf "
                "harness benchmarks data-parallel training (e.g. "
                "--mesh data=8)")
        mesh = make_mesh(axes)
        n = mesh.shape["data"]
        spec = FlatParamSpec(variables["params"], n)
        step = make_dp_train_step(model, criterion, method, mesh, spec,
                                  precision=policy)
        repl = NamedSharding(mesh, P())
        w = jax.device_put(spec.flatten(variables["params"]), repl)
        slots = jax.tree_util.tree_map(
            lambda s: jax.device_put(s, NamedSharding(mesh, P("data"))),
            method.init_slots(jnp.zeros((spec.padded,), jnp.float32)))
        state = jax.device_put(variables["state"], repl)
        bx = jax.device_put(bx_np, NamedSharding(
            mesh, P("data", *([None] * len(shape)))))
        by = jax.device_put(by_np, NamedSharding(mesh, P("data")))
        args = lambda i: (w, slots, state, bx, by,
                          jnp.asarray(0.01, jnp.float32),
                          jnp.asarray(i, jnp.int32),
                          jax.random.fold_in(jax.random.PRNGKey(7), i))

        def run_one(i):
            nonlocal w, slots, state
            w, slots, state, loss = step(*args(i))
            return loss
    else:
        slots = method.init_slots(variables["params"])
        params, state = variables["params"], variables["state"]
        bx, by = jnp.asarray(bx_np), jnp.asarray(by_np)

        from bigdl_tpu.ops.losses import build_train_loss

        loss_call = build_train_loss(model, criterion, policy)

        @jax.jit
        def step(params, state, slots, i):
            rng = jax.random.fold_in(jax.random.PRNGKey(7), i)
            (loss, new_state), grads = jax.value_and_grad(
                lambda p: loss_call(p, state, bx, by, rng),
                has_aux=True)(params)
            new_params, new_slots = method.update(
                grads, params, slots, jnp.asarray(0.01), i)
            return new_params, new_state, new_slots, loss

        def run_one(i):
            nonlocal params, state, slots
            params, state, slots, loss = step(params, state, slots,
                                              jnp.asarray(i, jnp.int32))
            return loss

    t0 = time.perf_counter()
    float(run_one(0))  # warmup + compile; host fetch = honest fence
    compile_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    loss = None
    for i in range(1, iterations + 1):
        loss = run_one(i)
    float(loss)  # final loss depends on every step: fences the chain
    steady = time.perf_counter() - t0

    return {
        "model": model_name,
        "batch_size": batch_size,
        "iterations": iterations,
        "compile_s": round(compile_s, 3),
        "steady_wall_s": round(steady, 3),
        "images_per_sec": round(iterations * batch_size / steady, 2),
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", default="resnet50")
    ap.add_argument("-b", "--batch-size", type=int, default=32)
    ap.add_argument("-i", "--iterations", type=int, default=10)
    ap.add_argument("--mesh", default=None,
                    help="e.g. data=8 to benchmark the DP path")
    ap.add_argument("--optimizer", default="sgd", choices=["sgd", "adam"])
    ap.add_argument("--class-num", type=int, default=1000)
    ap.add_argument("--precision", default=None,
                    choices=[None, "bf16", "mixed", "fp32"],
                    help="bf16 → mixed precision (fp32 master weights)")
    args = ap.parse_args(argv)
    from bigdl_tpu.utils.engine import setup_compile_cache

    setup_compile_cache()
    result = run_perf(args.model, args.batch_size, args.iterations,
                      args.mesh, args.optimizer, args.class_num,
                      args.precision)
    # telemetry convention: results go through the obs plane + logger,
    # never print (graftlint telemetry-bypass). The handler is pinned
    # to STDOUT (basicConfig defaults to stderr) so the machine-read
    # `... | jq .` contract of the old print() survives; force=True
    # wins even if an import already configured the root logger
    obs.emit_event("perf_result", plane="training", **result)
    logging.basicConfig(level=logging.INFO, format="%(message)s",
                        stream=sys.stdout, force=True)
    logging.getLogger("bigdl_tpu.models").info(json.dumps(result))


if __name__ == "__main__":
    main()
