"""Each cell's real-size step compiled ahead of time for a described
`v5e:2x2` (no chip attached; the TPU compiler is installed): it compiles,
it fits one chip's 16 GiB, the LM train step holds the flash-attention
Mosaic kernels (`tpu_custom_call`), and the data-parallel step holds an
all-reduce. The compile seconds are printed (-s): cell 4's were read here
before any chip time was spent. A compile that passes is not a chip run.

All in this one file and only inside fixtures: one process at a time may
load libtpu, and a module that touches it while being imported breaks the
collection under several workers.
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HBM = 16 * 2 ** 30


def _json(rel):
    with open(os.path.join(REPO, rel)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 - any failure means: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_cache():
    """An AOT compile for an absent chip can be written to the persistent
    cache but not read back; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _on(tree, sharding):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _compile(name, lowered):
    t = time.perf_counter()
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"\nAOT {name}: compiled in {time.perf_counter() - t:.1f} s; "
          f"arguments {mem.argument_size_in_bytes / 1e9:.2f} GB, temp "
          f"{mem.temp_size_in_bytes / 1e9:.2f} GB, per-device peak "
          f"{peak / 1e9:.2f} GB")
    assert peak < HBM, f"{name} needs {peak / 1e9:.1f} GB of one chip"
    return compiled.as_text()


def test_gpt2m_train_step(topo, no_cache):
    from benchmarks.drivers.train import _Pool
    from benchmarks.families import gpt2 as fam
    from benchmarks.reference import gpt2 as ref
    from bigdl_tpu import nn
    from bigdl_tpu.optim import Adam, Optimizer
    from bigdl_tpu.optim.optimizer import LocalOptimizer

    cfg = _json("benchmarks/configs/gpt2-medium.json")
    traffic = _json("benchmarks/traffic/lm-train-s1024-b8.json")
    one = SingleDeviceSharding(topo.devices[0])
    model = fam.program_model(cfg, traffic)
    model.attn_impl = "pallas"      # steer: on the chip the platform picks it
    method = Adam(traffic["optimizer"]["lr"])
    opt = (Optimizer(model, _Pool([]), nn.ChunkedSoftmaxCE(),
                     batch_size=traffic["batch"])
           .set_optim_method(method).set_precision(traffic["precision"]))
    step = LocalOptimizer(opt)._make_step()
    params = jax.eval_shape(lambda: fam.to_program(ref.init(0, cfg)))
    slots = jax.eval_shape(method.init_slots, params)
    tok = jax.ShapeDtypeStruct((traffic["batch"], traffic["seq_len"]),
                               jnp.int32)
    args = _on((params, {}, slots, tok, tok,
                jax.ShapeDtypeStruct((), jnp.float32),
                jax.ShapeDtypeStruct((), jnp.int32),
                jax.eval_shape(lambda: jax.random.PRNGKey(0))), one)
    text = _compile("gpt2m-train step", step.lower(*args))
    assert "tpu_custom_call" in text


def test_gpt2m_serve_steps(topo, no_cache):
    from benchmarks.families import gpt2 as fam
    from benchmarks.reference import gpt2 as ref
    from bigdl_tpu.serving import engine as eng

    cfg = _json("benchmarks/configs/gpt2-medium.json")
    e = _json("benchmarks/traffic/chat-open.json")["engine"]
    one = SingleDeviceSharding(topo.devices[0])
    model = fam.program_model(cfg, {})
    slots, bs = e["slots"], e["block_size"]
    per_slot = cfg["n_positions"] // bs
    params = jax.eval_shape(lambda: model.serving_params(
        {"params": fam.to_program(ref.init(0, cfg)), "state": {}}))
    pools = jax.eval_shape(lambda: model.init_block_pool(
        slots * per_slot + 1, bs, jnp.float32))

    def vec(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype)

    i32, f32 = jnp.int32, jnp.float32
    dec = _on((params, pools, vec(i32, slots), vec(i32, slots),
               vec(i32, slots), vec(i32, slots), vec(f32, slots),
               vec(i32, slots), vec(f32, slots), vec(jnp.bool_, slots),
               vec(i32, slots, per_slot)), one)
    _compile("gpt2m decode step, 64 slots",
             eng._decode_step.lower(model, *dec, "xla"))
    bucket = max(e["prefill_buckets"])
    pre = _on((params, pools, vec(i32, 1, bucket), vec(i32),
               vec(i32, bucket // bs), vec(i32, 1, per_slot)), one)
    _compile(f"gpt2m prefill, bucket {bucket}",
             eng._prefill_step.lower(model, *pre))


def test_resnet50_dp4_step(topo, no_cache):
    import numpy as np

    from benchmarks.families import resnet as fam
    from benchmarks.reference import resnet as ref
    from bigdl_tpu import nn
    from bigdl_tpu.models import resnet
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.parallel.data_parallel import FlatParamSpec, \
        make_dp_train_step
    from bigdl_tpu.utils.precision import DEFAULT_MIXED

    cfg = _json("benchmarks/configs/resnet50-imagenet.json")
    traffic = _json("benchmarks/traffic/image-train-dp4-b1024.json")
    opt = traffic["optimizer"]
    mesh = Mesh(np.array(topo.devices), ("data",))
    model = resnet.build_imagenet(cfg["depth"], cfg["num_classes"])
    lay = fam.Layout(model, cfg)
    params = jax.eval_shape(lambda: lay.to_program(ref.init(0, cfg)))
    state = jax.eval_shape(lay.fresh_state)
    spec = FlatParamSpec(params, mesh.size)
    method = SGD(opt["lr"], momentum=opt["momentum"], dampening=0.0,
                 weightdecay=opt["weight_decay"])
    step = make_dp_train_step(model, nn.ClassNLLCriterion(), method, mesh,
                              spec, precision=DEFAULT_MIXED,
                              zero=traffic["zero"])
    rep, split = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    flat = jax.ShapeDtypeStruct((spec.padded,), jnp.float32)
    size, b = cfg["image_size"], traffic["batch"]
    args = (_on(flat, rep), _on(jax.eval_shape(method.init_slots, flat),
                                split), _on(state, rep),
            jax.ShapeDtypeStruct((b, size, size, 3), jnp.float32,
                                 sharding=split),
            jax.ShapeDtypeStruct((b,), jnp.int32, sharding=split),
            _on(jax.ShapeDtypeStruct((), jnp.float32), rep),
            _on(jax.ShapeDtypeStruct((), jnp.int32), rep),
            _on(jax.eval_shape(lambda: jax.random.PRNGKey(0)), rep))
    text = _compile("resnet50 data-parallel step, 4 chips",
                    step.lower(*args))
    assert "all-reduce" in text or "reduce-scatter" in text
