"""The `resnet` family: a ResNet configuration file becomes the program's
`models/resnet.build_imagenet`, with weights and images made by the
benchmark from the seed (the reference's initialiser, handed over in the
program's tree), trained data-parallel through `Optimizer.set_mesh`."""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import optim as ref_optim
from benchmarks.reference.optim import u32  # noqa: F401 - drivers use it
from benchmarks.reference import resnet as ref


def _order(key: str):
    m = re.match(r"(\d+)_", key)
    if m:
        return (0, int(m.group(1)), key)
    return (1, {"weight": 0, "bias": 1}.get(key, 2), key)


def _walk(tree, path=()):
    """Leaves of the program's parameter tree in forward order: modules by
    their numeric prefix, a module's weight before its bias."""
    if not isinstance(tree, dict):
        yield path, tree
        return
    for k in sorted(tree, key=_order):
        yield from _walk(tree[k], path + (k,))


class Layout:
    """The correspondence between the program's tree and the reference's
    flat names, from shapes alone (`jax.eval_shape`: nothing runs)."""

    def __init__(self, model, cfg):
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        self.paths = [p for p, _ in _walk(shapes["params"])]
        self.names = [n for n, _, _ in ref.layout(cfg)]
        want = [tuple(s) for _, s, _ in ref.layout(cfg)]
        have = [tuple(l.shape) for _, l in _walk(shapes["params"])]
        if want != have:
            raise ValueError("the program's ResNet and the reference's "
                             "layout differ in shapes")
        self.param_shapes, self.state_shapes = shapes["params"], \
            shapes["state"]
        self.name_of = dict(zip(self.paths, self.names))

    def to_program(self, r: dict) -> dict:
        """The program's whole tree (its parameterless modules keep their
        empty entries) with the reference's arrays at the leaves."""
        return jax.tree_util.tree_map_with_path(
            lambda path, _: r[self.name_of[tuple(k.key for k in path)]],
            self.param_shapes)

    def to_reference(self, p: dict) -> dict:
        leaves = dict(_walk(p))
        return {n: leaves[path] for path, n in zip(self.paths, self.names)}

    def fresh_state(self) -> dict:
        return jax.tree_util.tree_map_with_path(
            lambda path, s: (jnp.ones if "running_var" in str(path[-1])
                             else jnp.zeros)(s.shape, s.dtype),
            self.state_shapes)


def _batch(seed, cfg, traffic, i):
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 7919 + i)
    k1, k2 = jax.random.split(key)
    size, b = cfg["image_size"], traffic["batch"]
    return (jax.random.normal(k1, (b, size, size, 3), jnp.float32),
            jax.random.randint(k2, (b,), 0, cfg["num_classes"], jnp.int32))


class TrainJob:
    slot = "velocity"   # after one step: g + weight_decay * w

    def __init__(self, seed, cfg, traffic, devices):
        from jax.sharding import Mesh

        from bigdl_tpu import nn
        from bigdl_tpu.models import resnet
        from bigdl_tpu.optim import SGD

        opt = traffic["optimizer"]
        if opt["name"] != "sgd":
            raise ValueError("the resnet family trains with sgd")
        self.seed, self.cfg, self.traffic = seed, cfg, traffic
        self.model = resnet.build_imagenet(cfg["depth"], cfg["num_classes"])
        self.layout = Layout(self.model, cfg)
        lay = self.layout
        self.model.variables = jax.jit(lambda s: {
            "params": lay.to_program(ref.init(s, cfg)),
            "state": lay.fresh_state()})(u32(seed))
        self.criterion = nn.ClassNLLCriterion()
        self.method = SGD(opt["lr"], momentum=opt["momentum"], dampening=0.0,
                          weightdecay=opt["weight_decay"])
        self.precision = traffic["precision"]
        self.mesh = Mesh(np.array(devices), ("data",))
        self.zero = traffic.get("zero", 1)
        # DistriOptimizer takes host batches (`np.asarray` per step), so
        # the pool lives on the host; made on the device, fetched once
        make = jax.jit(lambda s, i: _batch(s, cfg, traffic, i))
        self.batches = [tuple(np.asarray(a) for a in make(u32(seed), i))
                        for i in range(traffic["pool"])]

    def slot_leaves(self, slots, params=None) -> dict:
        flat = slots[self.slot]
        if not isinstance(flat, dict):     # the mesh path's flat vector
            from bigdl_tpu.parallel.data_parallel import FlatParamSpec

            flat = FlatParamSpec(params, self.mesh.size).unflatten(
                jnp.asarray(flat))
        return self.layout.to_reference(flat)

    def param_leaves(self, params) -> dict:
        return self.layout.to_reference(params)

    def initial_leaves(self, seed) -> dict:
        return ref.init(seed, self.cfg)

    def reference_steps(self, steps: int, precision=None) -> dict:
        return reference_steps(self.seed, self.cfg, self.traffic,
                               self.mesh.size, steps, precision,
                               batches=self.batches)


def reference_steps(seed, cfg, traffic, chips, steps, precision=None,
                    batches=None) -> dict:
    """The plain reference through `steps` SGD steps on the same weights
    and batches, each chip's share of a batch a block with its own
    BatchNorm statistics. `precision` makes it the control instead. Needs
    no program and one device."""
    opt = traffic["optimizer"]
    p0 = jax.jit(lambda s: ref.init(s, cfg))(u32(seed))
    params, state = p0, ref_optim.sgd_init(p0)
    update = jax.jit(ref_optim.sgd_step, static_argnums=(3,),
                     static_argnames=("lr", "momentum", "weight_decay"))
    make = jax.jit(lambda s, i: _batch(s, cfg, traffic, i))
    losses, slot1 = [], None
    with jax.default_matmul_precision("highest"):
        for i in range(steps):
            x, y = batches[i] if batches else make(u32(seed), i)
            loss, grads = ref.loss_and_grad_rows(
                params, jnp.asarray(x), jnp.asarray(y), cfg, precision,
                rows_per_block=traffic["batch"] // chips)
            params, state = update(
                params, grads, state, i, lr=opt["lr"],
                momentum=opt["momentum"], weight_decay=opt["weight_decay"])
            losses.append(float(loss))
            if i == 0:
                slot1 = ref_optim.host_norms(state["velocity"])
    delta = ref_optim.host_norms(jax.tree_util.tree_map(jnp.subtract, params, p0))
    return {"loss": losses, "slot": slot1, "delta": delta}
