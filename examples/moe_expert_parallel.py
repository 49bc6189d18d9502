"""Mixture-of-Experts LM with expert parallelism: Switch top-1, GShard
top-2, and the round-5 dropless expert-choice router, over an `expert`
mesh axis (tokens exchanged via all_to_all on ICI). No reference
counterpart (SURVEY.md §2.3). Run with real chips, or simulate:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    JAX_PLATFORMS=cpu python moe_expert_parallel.py
"""

import os, sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from bigdl_tpu.models.transformer import TransformerConfig, TransformerLM
from bigdl_tpu.optim import Adam
from bigdl_tpu.parallel import (
    make_mesh,
    make_moe_lm_train_step,
    moe_lm_specs,
    shard_params,
    slot_specs_for,
)


def run(mesh, routing, top_k):
    n = mesh.shape["expert"]
    cfg = TransformerConfig(vocab_size=256, max_len=32, dim=64,
                            num_heads=4, num_layers=2, dropout=0.0,
                            moe_experts=n, moe_top_k=top_k,
                            moe_routing=routing)
    model = TransformerLM(cfg, ep_axis="expert", name="lm")
    params = model.init(jax.random.PRNGKey(0))["params"]
    method = Adam(1e-3)
    specs = moe_lm_specs("expert", cfg.tie_embeddings)
    step = make_moe_lm_train_step(model, method, mesh, ep_axis="expert")
    sp = shard_params(mesh, specs, params)
    ss = shard_params(mesh, slot_specs_for(method, specs),
                      method.init_slots(params))
    tok_sharding = NamedSharding(mesh, P("expert", None))
    rng = np.random.RandomState(0)
    toks = jax.device_put(jnp.asarray(
        rng.randint(0, 256, (2 * n, 32)), jnp.int32), tok_sharding)
    tgts = jax.device_put(jnp.asarray(
        rng.randint(0, 256, (2 * n, 32)), jnp.int32), tok_sharding)
    for it in range(3):
        sp, ss, loss = step(sp, ss, toks, tgts, jnp.asarray(1e-3),
                            jnp.asarray(it), jax.random.PRNGKey(it))
    kind = (f"top_k top-{top_k}" if routing == "top_k"
            else "expert_choice (dropless, aux=0)")
    print(f"{kind:32s} final loss {float(loss):.4f}")


def main():
    mesh = make_mesh({"expert": jax.device_count()})
    run(mesh, "top_k", 1)          # Switch
    run(mesh, "top_k", 2)          # GShard
    run(mesh, "expert_choice", 1)  # experts pick tokens
    print("every router trained through the same expert-parallel "
          "all_to_all plane")


if __name__ == "__main__":
    main()
