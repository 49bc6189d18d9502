"""Record the small device traces kept in benchmarks/testdata/ (run on a
TPU machine, from the repo root: `python3 benchmarks/tools/record_trace.py
<name>`). The program traced is tiny and fixed: a flash-attention forward
and backward (a `tpu_custom_call`), two matmuls, a host sleep that leaves
the device idle under a named host span and, where there are four chips, a
gradient all-reduce under `shard_map`. Prints what the trace holds so that
the reduction can be written against a trace that was read by hand."""

from __future__ import annotations

import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(name: str) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmarks.harness import trace_reduce
    from benchmarks.harness.profile import TraceWindow
    from bigdl_tpu.ops.flash_attention import flash_attention

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit("record_trace.py needs a TPU")
    n = len(devices)
    q = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 512, 64),
                          jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1), (1024, 1024), jnp.bfloat16)

    @jax.jit
    def step(q, w):
        def f(q):
            return flash_attention(q, q, q, causal=True).astype(
                jnp.float32).sum()
        g = jax.grad(f)(q)
        return g.sum() + (w @ w).astype(jnp.float32).sum()

    mesh = Mesh(np.array(devices), ("data",))

    @jax.jit
    def reduce_step(x):
        def body(x):
            y = (x @ x).astype(jnp.float32)
            return jax.lax.psum(y, "data")
        return jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                             out_specs=P("data"))(x)

    x = jax.device_put(jnp.ones((n * 512, 512), jnp.bfloat16),
                       NamedSharding(mesh, P("data")))
    float(step(q, w)), reduce_step(x).block_until_ready()   # compile
    out_dir = os.path.join(ROOT, "chiprun_out", f"trace_{name}")
    tw = TraceWindow(out_dir)
    spans = []
    tw.start()
    for i in range(3):
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("fixture_step"):
            float(step(q, w))
            if n > 1:
                reduce_step(x).block_until_ready()
        spans.append(("fixture_step", t, time.perf_counter()))
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("fixture_sleep"):
            time.sleep(0.002)
        spans.append(("fixture_sleep", t, time.perf_counter()))
    path = tw.stop()
    dst = os.path.join(ROOT, "chiprun_out", f"{name}.xplane.pb")
    shutil.copy(path, dst)
    shutil.rmtree(out_dir, ignore_errors=True)
    meta = {"begin_host": tw.begin_host, "end_host": tw.end_host,
            "spans": spans, "devices": n,
            "kind": devices[0].device_kind}
    with open(os.path.join(ROOT, "chiprun_out", f"{name}.json"), "w") as f:
        json.dump(meta, f)
    print("\n".join(trace_reduce.describe_file(dst)[:400]))
    print(json.dumps(trace_reduce.reduce_file(dst, spans, tw.begin_host),
                     indent=1))
    print("bytes", os.path.getsize(dst))


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "fixture")
