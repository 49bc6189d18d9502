"""The `joyai-flash-serve-backlog` cell's two serving programs (and the one
program that makes its weights) compiled ahead of time for a described
`v5e:2x2` at the cell's own sizes (no chip attached; the TPU compiler is
installed): they compile, each fits one chip beside nothing else (under
15.5 GB: 11.1 GB of weights and a 1.7 GB latent pool are arguments), the
latent pool's leaves are block-major on the device and updated in place,
and no instruction rewrites a whole leaf. The compile seconds and the
memory analysis are printed (-s). A compile that passes is not a chip run.

libtpu is touched only inside the `topo` fixture (one process at a time may
load it; a module that touches it while being imported breaks the
collection under several workers); the tests skip where no topology can be
described. `tests/bench/test_aot.py` has the other cells.
"""

import json
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FITS = 15.5e9


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


def _compile(name, lowered):
    t = time.perf_counter()
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"\nAOT {name}: compiled in {time.perf_counter() - t:.1f} s; "
          f"arguments {mem.argument_size_in_bytes / 1e9:.2f} GB, outputs "
          f"{mem.output_size_in_bytes / 1e9:.2f} GB (aliased "
          f"{mem.alias_size_in_bytes / 1e9:.2f}), temp "
          f"{mem.temp_size_in_bytes / 1e9:.2f} GB, per-device peak "
          f"{peak / 1e9:.2f} GB")
    assert peak < FITS, f"{name} needs {peak / 1e9:.1f} GB of one chip"
    return compiled.as_text()


@pytest.fixture(scope="module")
def cell(topo, no_cache):
    """Shapes of the cell's weights, pools and the two programs' other
    arguments, each on one described chip."""
    from benchmarks.families import mla_moe as fam
    from benchmarks.reference import mla_moe as ref

    cfg = _json("benchmarks/configs/joyai-llm-flash.json")
    e = _json("benchmarks/traffic/longgen-backlog.json")["engine"]
    one = SingleDeviceSharding(topo.devices[0])

    def on(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            tree)

    model = fam.program_model(cfg)
    params = jax.eval_shape(lambda: fam.to_program(ref.init(0, cfg)))
    pools = jax.eval_shape(lambda: model.init_block_pool(
        e["pool_blocks"], e["block_size"], jnp.bfloat16))
    return {"cfg": cfg, "engine": e, "on": on, "model": model,
            "params": params, "pools": pools}


_INSTR = re.compile(
    r"^\s*(?:ROOT )?%?(?P<name>\S+) = (?P<dtype>\w+)\[(?P<dims>[\d,]*)\]"
    r"(?:\{(?P<layout>[\d,]*)[^}]*\})? (?P<op>[\w-]+)\(")


def _entry(text):
    body = re.search(r"^ENTRY [^\n]*\{\n(.*?)^\}", text, re.S | re.M).group(1)
    out = []
    for line in body.splitlines():
        m = _INSTR.match(line)
        if m:
            dims = tuple(int(d) for d in m["dims"].split(",") if d)
            layout = tuple(int(d) for d in (m["layout"] or "").split(",")
                           if d)
            out.append((m["name"], dims, layout, m["op"], line))
    return out, text[:text.index("\n")]


def _hold_pool_to_its_shape(text, leaf, layers):
    """The pool's leaves arrive block-major, are aliased to the outputs,
    and nothing but the scatter produces a leaf-sized result."""
    entry, header = _entry(text)
    params = [(layout, int(re.search(r"parameter\((\d+)\)", line).group(1)))
              for _, dims, layout, op, line in entry
              if op == "parameter" and dims == leaf]
    assert len(params) == layers, params
    assert all(layout[-1] == 0 for layout, _ in params), (
        f"device layout {params[0][0]} of a {leaf} leaf does not keep the "
        "block dimension major-most")
    aliased = {int(p) for p in re.findall(
        r"\{[\d,\s]*\}:\s*\((\d+),\s*\{[\d,\s]*\},\s*(?:may|must)-alias\)",
        header)}
    assert {n for _, n in params} <= aliased
    n = int(np.prod(leaf))
    for name, dims, _, op, line in entry:
        if op == "parameter" or int(np.prod(dims or (1,))) != n:
            continue
        assert dims == leaf and op == "fusion" and "/scatter" in line, (
            f"{name} = {op} -> {dims}: a whole pool leaf is produced by "
            f"something else than the scatter: {line[:200]}")


def _vec(dtype, *shape):
    return jax.ShapeDtypeStruct(shape, dtype)


def test_weights_are_made_by_one_program_that_fits(cell):
    from benchmarks.families import mla_moe as fam
    from benchmarks.reference import mla_moe as ref

    cfg = cell["cfg"]
    _compile("joyai weights from the seed", jax.jit(
        lambda s: fam.to_program(ref.init(s, cfg))).lower(
            cell["on"](_vec(jnp.uint32))))


def test_decode_step(cell):
    from bigdl_tpu.serving import engine as eng

    e, on = cell["engine"], cell["on"]
    slots, per_slot = e["slots"], e["max_len"] // e["block_size"]
    i32, f32 = jnp.int32, jnp.float32
    dec = on((cell["params"], cell["pools"], _vec(i32, slots),
              _vec(i32, slots), _vec(i32, slots), _vec(i32, slots),
              _vec(f32, slots), _vec(i32, slots), _vec(f32, slots),
              _vec(jnp.bool_, slots), _vec(i32, slots, per_slot)))
    text = _compile(f"joyai decode step, {slots} slots",
                    eng._decode_step.lower(cell["model"], *dec, "xla"))
    _hold_pool_to_its_shape(text, cell["pools"][0]["kv"].shape,
                            len(cell["pools"]))


@pytest.mark.parametrize("bucket", [2048])
def test_prefill(cell, bucket):
    from bigdl_tpu.serving import engine as eng

    e, on = cell["engine"], cell["on"]
    assert bucket in e["prefill_buckets"]
    bs = e["block_size"]
    i32 = jnp.int32
    pre = on((cell["params"], cell["pools"], _vec(i32, 1, bucket),
              _vec(i32), _vec(i32, bucket // bs),
              _vec(i32, 1, e["max_len"] // bs)))
    text = _compile(f"joyai prefill, bucket {bucket}",
                    eng._prefill_step.lower(cell["model"], *pre))
    _hold_pool_to_its_shape(text, cell["pools"][0]["kv"].shape,
                            len(cell["pools"]))
