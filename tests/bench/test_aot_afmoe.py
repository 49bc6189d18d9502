"""The `trinity-mini-serve-backlog` cell's serving programs (and the one
program that makes its weights) compiled ahead of time for a described
`v5e:2x2` at the cell's own sizes (no chip attached; the TPU compiler is
installed): they compile, each fits one chip beside nothing else (under
15.5 GB: 8.5 GB of weights and 2.2 GB of cache are arguments), every pool
leaf of either kind (a full layer's table blocks, a sliding layer's rings)
is block-major on the device and updated in place, nothing rewrites a whole
leaf, and the 6,144-token prefill holds no temporary the size of a full
score matrix. The compile seconds and the memory analysis are printed (-s).
A compile that passes is not a chip run.

The helpers and the `topo` / `no_cache` fixtures are
`tests/bench/test_aot_mla_moe.py`'s (libtpu is touched only inside `topo`).
"""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from test_aot_mla_moe import (REPO, _compile, _entry, _vec,  # noqa: F401
                              no_cache, topo)


def _json(rel):
    with open(os.path.join(REPO, rel)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cell(topo, no_cache):
    """Shapes of the cell's weights, pools and the two programs' other
    arguments, each on one described chip."""
    from benchmarks.families import afmoe as fam
    from benchmarks.reference import afmoe as ref

    cfg = _json("benchmarks/configs/trinity-mini.json")
    e = _json("benchmarks/traffic/mixedlen-backlog.json")["engine"]
    one = SingleDeviceSharding(topo.devices[0])

    def on(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            tree)

    model = fam.program_model(cfg)
    params = jax.eval_shape(lambda: fam.to_program(ref.init(0, cfg)))
    pools = jax.eval_shape(lambda: model.init_block_pool(
        e["pool_blocks"], e["block_size"], jnp.bfloat16, slots=e["slots"]))
    return {"cfg": cfg, "engine": e, "on": on, "model": model,
            "params": params, "pools": pools}


def _hold_pools_to_their_shapes(text, pools):
    """Every leaf, of either shape, arrives block-major, is aliased to an
    output, and nothing but the write into it (a scatter by block id, or
    the prefill's in-place update of a slot's ring) produces a result of
    a leaf's size."""
    entry, header = _entry(text)
    leaves = {}
    for layer in pools:
        for leaf in layer.values():
            leaves[leaf.shape] = leaves.get(leaf.shape, 0) + 1
    aliased = {int(p) for p in re.findall(
        r"\{[\d,\s]*\}:\s*\((\d+),\s*\{[\d,\s]*\},\s*(?:may|must)-alias\)",
        header)}
    for leaf, count in leaves.items():
        params = [(layout, int(re.search(r"parameter\((\d+)\)",
                                         line).group(1)))
                  for _, dims, layout, op, line in entry
                  if op == "parameter" and dims == leaf]
        assert len(params) == count, (leaf, params)
        assert all(layout[-1] == 0 for layout, _ in params), (
            f"device layout {params[0][0]} of a {leaf} leaf does not keep "
            "the block dimension major-most")
        assert {n for _, n in params} <= aliased
    sizes = {int(np.prod(leaf)): leaf for leaf in leaves}
    for name, dims, _, op, line in entry:
        leaf = sizes.get(int(np.prod(dims or (1,))))
        if op == "parameter" or leaf is None:
            continue
        # (several rings' updates may share one multi-output fusion)
        assert dims == leaf and op in ("fusion", "get-tuple-element") and (
            "/scatter" in line or "dynamic_update_slice" in line), (
            f"{name} = {op} -> {dims}: a whole pool leaf is produced by "
            f"something else than the write into it: {line[:200]}")


def test_weights_are_made_by_one_program_that_fits(cell):
    from benchmarks.families import afmoe as fam
    from benchmarks.reference import afmoe as ref

    cfg = cell["cfg"]
    _compile("trinity weights from the seed", jax.jit(
        lambda s: fam.to_program(ref.init(s, cfg))).lower(
            cell["on"](_vec(jnp.uint32))))


def test_the_pools_are_the_two_kinds_at_the_issues_sizes(cell):
    e = cell["engine"]
    shapes = [layer["k"].shape for layer in cell["pools"]]
    ring = (1 + e["slots"] * 129, 16, 512)      # window + one block a slot
    assert shapes == [ring] * 4 + [(e["pool_blocks"], 16, 512)]
    held = sum(2 * 2 * int(np.prod(s)) for s in shapes)
    assert 2.1e9 < held < 2.2e9                 # ISSUE 33: 2.16 GB


def test_decode_step(cell):
    from bigdl_tpu.serving import engine as eng

    e, on = cell["engine"], cell["on"]
    slots, per_slot = e["slots"], e["max_len"] // e["block_size"]
    i32, f32 = jnp.int32, jnp.float32
    dec = on((cell["params"], cell["pools"], _vec(i32, slots),
              _vec(i32, slots), _vec(i32, slots), _vec(i32, slots),
              _vec(f32, slots), _vec(i32, slots), _vec(f32, slots),
              _vec(jnp.bool_, slots), _vec(i32, slots, per_slot)))
    text = _compile(f"trinity decode step, {slots} slots",
                    eng._decode_step.lower(cell["model"], *dec))
    _hold_pools_to_their_shapes(text, cell["pools"])


@pytest.mark.parametrize("bucket", [6144])
def test_prefill(cell, bucket):
    from bigdl_tpu.serving import engine as eng

    e, on = cell["engine"], cell["on"]
    assert bucket == max(e["prefill_buckets"])
    bs = e["block_size"]
    i32 = jnp.int32
    ids = {"table": _vec(i32, bucket // bs),
           "ring": {"slot": _vec(i32), "sources": _vec(i32, 129)}}
    pre = on((cell["params"], cell["pools"], _vec(i32, 1, bucket),
              _vec(i32), ids, _vec(i32, 1, e["max_len"] // bs)))
    text = _compile(f"trinity prefill, bucket {bucket}",
                    eng._prefill_step.lower(cell["model"], *pre))
    _hold_pools_to_their_shapes(text, cell["pools"])
    # a full layer's scores over the whole extent, one head group: 151M
    # numbers; no instruction of the program produces as many
    heads = cell["cfg"]["num_attention_heads"]
    whole = bucket * bucket * heads // cell["cfg"]["num_key_value_heads"]
    largest = max(int(np.prod(dims or (1,))) for _, dims, _, op, _
                  in _entry(text)[0] if op != "parameter")
    assert largest < whole, (largest, whole)
