"""Pipeline-parallel LM training: GPipe vs interleaved 1F1B (virtual
stages). No reference counterpart (SURVEY.md §2.3 lists only data
parallelism); this is the `pipe` mesh axis with the round-5 Megatron-
style interleaved schedule that cuts the GPipe bubble ~in half at equal
microbatches. Run with real chips, or simulate:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
    JAX_PLATFORMS=cpu python pipeline_parallel_lm.py
"""

import os, sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from bigdl_tpu.models.transformer import TransformerConfig, TransformerLM
from bigdl_tpu.optim import SGD
from bigdl_tpu.parallel import (
    interleaved_bubble_fraction,
    make_mesh,
    make_pipeline_train_step,
    pipeline_bubble_fraction,
    pipeline_specs,
    shard_params,
    slot_specs_for,
    to_virtual_layout,
)


def main():
    stages, micro, virtual = 4, 8, 2
    mesh = make_mesh({"pipe": stages}, devices=jax.devices()[:stages])
    cfg = TransformerConfig(vocab_size=256, max_len=64, dim=64,
                            num_heads=4, num_layers=8, dropout=0.0)
    model = TransformerLM(cfg, name="lm")
    params = model.init(jax.random.PRNGKey(0))["params"]
    method = SGD(learningrate=0.1, momentum=0.9)
    specs = pipeline_specs("pipe")

    print(f"GPipe bubble ({stages} stages x {micro} microbatches): "
          f"{pipeline_bubble_fraction(stages, micro):.3f}")
    print(f"interleaved 1F1B bubble (x{virtual} virtual stages):     "
          f"{interleaved_bubble_fraction(stages, micro, virtual):.3f}")

    step = make_pipeline_train_step(model, method, mesh, pipe_axis="pipe",
                                    microbatches=micro,
                                    virtual_stages=virtual)

    # interleaved schedule: params/slots live in virtual-stage layout
    vp = shard_params(mesh, specs, to_virtual_layout(params, stages,
                                                     virtual))
    vs = shard_params(mesh, slot_specs_for(method, specs),
                      to_virtual_layout(method.init_slots(params),
                                        stages, virtual))
    rng = np.random.RandomState(0)
    toks = jnp.asarray(rng.randint(0, 256, (16, 64)), jnp.int32)
    tgts = jnp.asarray(rng.randint(0, 256, (16, 64)), jnp.int32)
    spec = NamedSharding(mesh, P())
    for it in range(5):
        vp, vs, loss = step(vp, vs, jax.device_put(toks, spec),
                            jax.device_put(tgts, spec),
                            jnp.asarray(0.1), jnp.asarray(it),
                            jax.random.PRNGKey(it))
        print(f"iter {it}: loss {float(loss):.4f}")

    # checkpoints should store the standard layer order
    std = to_virtual_layout(jax.device_get(vp), stages, virtual,
                            inverse=True)
    n = sum(int(np.prod(x.shape))
            for x in jax.tree_util.tree_leaves(std))
    print(f"params back in standard layout: {n} scalars")


if __name__ == "__main__":
    main()
