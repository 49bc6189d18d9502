"""The `zaya1-8b-serve-backlog` cell's serving programs (and the programs
that make its weights, a layer at a time) compiled ahead of time for a described `v5e:2x2` at
the cell's own sizes (no chip attached; the TPU compiler is installed): they
compile, each fits one chip beside nothing else (under 15.5 GB: 9.4 GB of
weights and 4.0 GB of cache are arguments), every pool leaf of either kind
(a layer's table blocks, the slots' state) is updated in place with the
table blocks block-major on the device, and nothing rewrites a whole block
leaf. The program that clears a released slot's state passes every other
leaf through. The compile seconds and the memory analysis are printed (-s).
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
    """Shapes of the cell's weights, pools and the programs' other
    arguments, each on one described chip."""
    from benchmarks.families import cca_moe as fam
    from benchmarks.reference import cca_moe as ref

    cfg = _json("benchmarks/configs/zaya1-8b.json")
    e = _json("benchmarks/traffic/reasoning-backlog.json")["engine"]
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


def _hold_pools_to_their_shapes(text, pools, state_written=True):
    """Every leaf arrives as a parameter that is aliased to an output; a
    block leaf is block-major and nothing but the write into it (a scatter
    by block id) produces a result of its size."""
    entry, header = _entry(text)
    aliased = {int(p) for p in re.findall(
        r"\{[\d,\s]*\}:\s*\((\d+),\s*\{[\d,\s]*\},\s*(?:may|must)-alias\)",
        header)}
    blocks, state = pools[0]["k"].shape, pools[1]["s"].shape
    for leaf, count in ((blocks, 2 * len(pools) // 2),
                        (state, len(pools) // 2)):
        params = [(layout, int(re.search(r"parameter\((\d+)\)",
                                         line).group(1)))
                  for _, dims, layout, op, line in entry
                  if op == "parameter" and dims == leaf]
        assert len(params) == count, (leaf, len(params))
        assert {n for _, n in params} <= aliased
        if leaf == blocks:
            assert all(layout[-1] == 0 for layout, _ in params), (
                f"device layout {params[0][0]} of a {leaf} leaf does not "
                "keep the block dimension major-most")
    for name, dims, _, op, line in entry:
        if op == "parameter" or int(np.prod(dims or (1,))) \
                != int(np.prod(blocks)):
            continue
        assert dims == blocks and op in ("fusion", "get-tuple-element") \
            and "/scatter" in line, (
            f"{name} = {op} -> {dims}: a whole pool leaf is produced by "
            f"something else than the write into it: {line[:200]}")


def test_weights_are_made_by_small_programs_that_fit(cell):
    """The program that makes any layer but the first (the seed, the layer
    and the balancing rows are its arguments: it sends the 65,536 rows
    through the layer it made, beside up to nineteen made before it) and
    the one that makes the embedding."""
    from benchmarks.families import cca_moe as fam
    from benchmarks.reference import cca_moe as ref

    cfg, on = cell["cfg"], cell["on"]
    b, d = cfg["router_balance"], cfg["hidden_size"]
    rows = (b["sequences"], b["positions"])
    stream = tuple(jax.ShapeDtypeStruct(rows + (w,), jnp.float32)
                   for w in (d, d, cfg["router_hidden_size"]))

    def a_layer(s, n, stream):
        lp, stream = ref.init_layer(s, cfg, n, False, stream)
        return fam.layer_to_program(lp, jnp.bfloat16), stream

    _compile("zaya1 weights of a layer from the seed", jax.jit(
        a_layer).lower(*on((_vec(jnp.uint32), _vec(jnp.int32), stream))))
    _compile("zaya1 embedding from the seed", jax.jit(
        lambda s: fam.top_to_program(
            ref.init_top(s, cfg), jnp.bfloat16)).lower(
                on(_vec(jnp.uint32))))


def test_the_pools_are_the_two_kinds_at_the_issues_sizes(cell):
    e, model = cell["engine"], cell["model"]
    assert model.cache_kinds() == ("table", "state") * 20
    assert [tuple(leaf.shape for leaf in layer.values())
            for layer in cell["pools"]] == [
        ((e["pool_blocks"], 16, 256),) * 2, ((e["slots"], 2688),)] * 20
    held = sum(leaf.dtype.itemsize * int(np.prod(leaf.shape))
               for layer in cell["pools"] for leaf in layer.values())
    assert 4.03e9 < held < 4.05e9               # ISSUE 38: 4.03 + 0.014 GB
    assert model.slot_state_bytes() * e["slots"] == 13_762_560


def test_decode_step(cell):
    from bigdl_tpu.serving import engine as eng

    e, on = cell["engine"], cell["on"]
    slots, per_slot = e["slots"], e["max_len"] // e["block_size"]
    i32, f32 = jnp.int32, jnp.float32
    dec = on((cell["params"], cell["pools"], _vec(i32, slots),
              _vec(i32, slots), _vec(i32, slots), _vec(i32, slots),
              _vec(f32, slots), _vec(i32, slots), _vec(f32, slots),
              _vec(jnp.bool_, slots), _vec(i32, slots, per_slot)))
    text = _compile(f"zaya1 decode step, {slots} slots",
                    eng._decode_step.lower(cell["model"], *dec))
    _hold_pools_to_their_shapes(text, cell["pools"])


@pytest.mark.parametrize("bucket", [768])
def test_prefill(cell, bucket):
    from bigdl_tpu.serving import engine as eng

    e, on = cell["engine"], cell["on"]
    assert bucket == max(e["prefill_buckets"])
    bs = e["block_size"]
    i32 = jnp.int32
    ids = {"table": _vec(i32, bucket // bs),
           "state": {"slot": _vec(i32), "keep": _vec(i32)}}
    pre = on((cell["params"], cell["pools"], _vec(i32, 1, bucket),
              _vec(i32), ids, _vec(i32, 1, e["max_len"] // bs)))
    text = _compile(f"zaya1 prefill, bucket {bucket}",
                    eng._prefill_step.lower(cell["model"], *pre))
    _hold_pools_to_their_shapes(text, cell["pools"])


def test_clearing_a_released_slots_state_copies_no_leaf(cell):
    from bigdl_tpu.serving import engine as eng

    text = _compile("zaya1 clear of one slot's state",
                    eng._clear_slot_state.lower(
                        cell["model"].cache_kinds(),
                        *cell["on"]((cell["pools"], _vec(jnp.int32)))))
    entry, _ = _entry(text)
    blocks = cell["pools"][0]["k"].shape
    assert not [line for _, dims, _, op, line in entry
                if dims == blocks and op not in ("parameter",
                                                 "get-tuple-element")]
