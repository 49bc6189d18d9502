"""The `ouro-2.6b-serve-backlog` cell's serving programs (and the programs
that make its weights, a layer at a time) compiled ahead of time for a
described `v5e:2x2` at the cell's own sizes (no chip attached; the TPU
compiler is installed): they compile, each fits one chip beside nothing else
(under 15.5 GB: 5.34 GB of weights and 9.69 GB of pools are arguments), EVERY
pool leaf (48 layers x keys and values, each holding the four passes' rows
of every block) arrives as a parameter that is aliased to an output, is
block-major on the device, and nothing but the write into it produces a
result the size of a whole leaf; the passes are ONE loop in the program (one
`while`), not four copies of the layers. The compile seconds and the memory
analysis are printed (-s). A compile that passes is not a chip run.

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

LAYERS, PASSES = 48, 4


def _json(rel):
    with open(os.path.join(REPO, rel)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cell(topo, no_cache):
    """Shapes of the cell's weights, pools and the programs' other
    arguments, each on one described chip."""
    from benchmarks.families import loop_lm as fam
    from benchmarks.reference import loop_lm as ref

    cfg = _json("benchmarks/configs/ouro-2.6b.json")
    e = _json("benchmarks/traffic/shortreason-backlog.json")["engine"]
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


def _hold_pools_to_their_shape(text, pools):
    """Every leaf arrives as a parameter that is aliased to an output and
    is block-major; the passes are one loop; and inside it nothing but the
    write into a leaf (a scatter by block id, in place) produces a result
    of a whole leaf's size."""
    entry, header = _entry(text)
    aliased = {int(p) for p in re.findall(
        r"\{[\d,\s]*\}:\s*\((\d+),\s*\{[\d,\s]*\},\s*(?:may|must)-alias\)",
        header)}
    leaf = pools[0]["k"].shape
    params = [(layout, int(re.search(r"parameter\((\d+)\)", line).group(1)))
              for _, dims, layout, op, line in entry
              if op == "parameter" and dims == leaf]
    assert len(params) == 2 * LAYERS, len(params)
    assert {n for _, n in params} <= aliased, (
        f"a {leaf} leaf is not updated in place: parameters "
        f"{sorted({n for _, n in params} - aliased)} alias no output")
    assert all(layout[-1] == 0 for layout, _ in params), (
        f"device layout {params[0][0]} of a {leaf} leaf does not keep the "
        "block dimension major-most")
    # one loop over the pass, whose body is the 48 layers once
    assert len(re.findall(r" while\(", text)) == 1
    # a leaf, or the same bytes seen a pass at a time
    seen = (leaf, (leaf[0] * leaf[1],) + leaf[2:])
    made = {}
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?\S+ = bf16\[([\d,]+)\]\S* ([\w-]+)\(",
                     line)
        if m and tuple(int(d) for d in m[1].split(",")) in seen:
            made[m[2]] = made.get(m[2], 0) + 1
    assert set(made) <= {"parameter", "get-tuple-element", "bitcast",
                         "scatter", "fusion"}, made
    assert made.get("scatter", 0) <= 2 * LAYERS
    copies = [line for line in text.splitlines() if " copy(" in line
              and any(",".join(map(str, s)) in line for s in seen)]
    assert not copies, copies[:2]


def test_weights_are_made_by_small_programs_that_fit(cell):
    from benchmarks.families import loop_lm as fam
    from benchmarks.reference import loop_lm as ref

    cfg, on = cell["cfg"], cell["on"]
    _compile("ouro weights of a layer from the seed", jax.jit(
        lambda s, n: fam.layer_to_program(
            ref.init_layer(s, cfg, n), jnp.bfloat16)).lower(
                *on((_vec(jnp.uint32), _vec(jnp.int32)))))
    _compile("ouro embedding and head from the seed", jax.jit(
        lambda s: fam.top_to_program(
            ref.init_top(s, cfg), jnp.bfloat16)).lower(
                on(_vec(jnp.uint32))))


def test_the_pools_are_192_row_sets_at_the_issues_sizes(cell):
    from benchmarks.counts import loop_lm as counts

    e, model, cfg = cell["engine"], cell["model"], cell["cfg"]
    assert model.cache_kinds() == ("table",) * LAYERS
    assert model.cache_entries == LAYERS * PASSES == 192 \
        == len(counts.layer_plan(cfg))
    assert (e["pool_blocks"], e["block_size"], e["slots"]) == (385, 16, 16)
    assert {leaf.shape for entry in cell["pools"]
            for leaf in entry.values()} == {(385, PASSES, 16, 2048)}
    held = sum(leaf.dtype.itemsize * int(np.prod(leaf.shape))
               for entry in cell["pools"] for leaf in entry.values())
    assert 9.68e9 < held < 9.70e9       # ISSUE 49: 9.69 GB
    assert held == 385 * 16 * 192 * counts.cache_row_bytes(cfg)
    weights = sum(s.dtype.itemsize * int(np.prod(s.shape))
                  for s in jax.tree_util.tree_leaves(cell["params"]))
    assert 5.33e9 < weights < 5.35e9    # held ONCE: 5.34 GB
    assert len(cell["params"]["layers"]) == LAYERS


def test_decode_step(cell):
    from bigdl_tpu.serving import engine as eng

    e, on = cell["engine"], cell["on"]
    slots, per_slot = e["slots"], e["max_len"] // e["block_size"]
    i32, f32 = jnp.int32, jnp.float32
    dec = on((cell["params"], cell["pools"], _vec(i32, slots),
              _vec(i32, slots), _vec(i32, slots), _vec(i32, slots),
              _vec(f32, slots), _vec(i32, slots), _vec(f32, slots),
              _vec(jnp.bool_, slots), _vec(i32, slots, per_slot)))
    text = _compile(f"ouro decode step, {slots} slots",
                    eng._decode_step.lower(cell["model"], *dec))
    _hold_pools_to_their_shape(text, cell["pools"])


@pytest.mark.parametrize("bucket", [64, 128])
def test_prefill(cell, bucket):
    from bigdl_tpu.serving import engine as eng

    e, on = cell["engine"], cell["on"]
    assert e["prefill_buckets"] == [64, 128]
    bs = e["block_size"]
    i32 = jnp.int32
    pre = on((cell["params"], cell["pools"], _vec(i32, 1, bucket),
              _vec(i32), _vec(i32, bucket // bs),
              _vec(i32, 1, e["max_len"] // bs)))
    text = _compile(f"ouro prefill, bucket {bucket}",
                    eng._prefill_step.lower(cell["model"], *pre))
    _hold_pools_to_their_shape(text, cell["pools"])
