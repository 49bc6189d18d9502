"""What the references share: the plain optimizers (the textbook update,
one leaf at a time), gradients of a mean loss taken in blocks of rows, leaf
norms, and the seed as a device scalar. Adam as in Kingma & Ba 2015 (bias-corrected moments, epsilon outside
the root); SGD with momentum and L2 weight decay as in He et al. 2015 §3.4
(v <- mu v + g + wd w, w <- w - lr v)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

tmap = jax.tree_util.tree_map


def adam_init(params):
    z = tmap(jnp.zeros_like, params)
    return {"m": z, "v": tmap(jnp.zeros_like, params)}


def adam_step(params, grads, state, step: int, lr: float, b1=0.9, b2=0.999,
              eps=1e-8):
    t = step + 1
    m = tmap(lambda m_, g: b1 * m_ + (1 - b1) * g, state["m"], grads)
    v = tmap(lambda v_, g: b2 * v_ + (1 - b2) * g * g, state["v"], grads)
    new = tmap(lambda p, m_, v_: p - lr * (m_ / (1 - b1 ** t)) /
               (jnp.sqrt(v_ / (1 - b2 ** t)) + eps), params, m, v)
    return new, {"m": m, "v": v}


def sgd_init(params):
    return {"velocity": tmap(jnp.zeros_like, params)}


def sgd_step(params, grads, state, step: int, lr: float, momentum=0.9,
             weight_decay=1e-4):
    g = tmap(lambda g_, p: g_ + weight_decay * p, grads, params)
    vel = tmap(lambda v_, g_: momentum * v_ + g_, state["velocity"], g)
    return tmap(lambda p, v_: p - lr * v_, params, vel), {"velocity": vel}


def u32(seed: int):
    """The run's seed as the device scalar every seeded program takes as an
    ARGUMENT: a seed baked into a program as a constant would make a new
    program, and a compile-cache miss, for every seed."""
    return jnp.asarray(seed % (2 ** 32), jnp.uint32)


def leaf_norms(tree: dict) -> dict:
    """{leaf name: L2 norm} of a flat dict of arrays; traceable."""
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def host_norms(tree: dict) -> dict:
    return {k: float(v) for k, v in jax.jit(leaf_norms)(tree).items()}


def loss_and_grad_in_blocks(loss_fn, params, x, y, rows_per_block: int):
    """Loss and gradient of the mean of `loss_fn(params, x, y)` over equal
    blocks of rows (the mean of equal blocks' means is the batch mean), so
    that a full-size float32 backward pass fits the chip."""
    n = x.shape[0]
    if n % rows_per_block:
        raise ValueError(f"{n} rows do not split into blocks of "
                         f"{rows_per_block}")
    fn = jax.jit(jax.value_and_grad(loss_fn))
    add = jax.jit(lambda a, b: tmap(jnp.add, a, b))
    total, grads = 0.0, None
    for i in range(0, n, rows_per_block):
        l, g = fn(params, x[i:i + rows_per_block], y[i:i + rows_per_block])
        total = total + l
        grads = g if grads is None else add(grads, g)
    k = n // rows_per_block
    return total / k, tmap(lambda g: g / k, grads)
