"""The `granite-4.0-h-micro-serve-backlog` cell's serving programs (and the
programs that make its weights, a layer at a time) compiled ahead of time
for a described `v5e:2x2` at the cell's own sizes (no chip attached; the
TPU compiler is installed): they compile, each fits one chip beside
nothing else (under 15.5 GB: 6.4 GB of weights and 7.0 GB of pools are
arguments), EVERY pool leaf of either kind (the recurrence's state `h`,
4.83 GB over 36 layers, the convolution's taps, an attention layer's table
blocks) arrives as a parameter that is aliased to an output, so that no
program holds a second copy of the state, the table blocks are block-major
on the device, and nothing but a write into it produces a result the size
of a whole leaf. The program that clears a released slot's state passes
every table leaf through. The compile seconds and the memory analysis are
printed (-s). A compile that passes is not a chip run.

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

N_MAMBA, N_ATTENTION = 36, 4


def _json(rel):
    with open(os.path.join(REPO, rel)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cell(topo, no_cache):
    """Shapes of the cell's weights, pools and the programs' other
    arguments, each on one described chip."""
    from benchmarks.families import granite_hybrid as fam
    from benchmarks.reference import granite_hybrid as ref

    cfg = _json("benchmarks/configs/granite-4.0-h-micro.json")
    e = _json("benchmarks/traffic/ragtool-backlog.json")["engine"]
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


def _leaf_shapes(pools):
    """(h, taps, table blocks): the three shapes a pool leaf has."""
    state = next(layer for layer in pools if "h" in layer)
    table = next(layer for layer in pools if "k" in layer)
    return state["h"].shape, state["taps"].shape, table["k"].shape


def _hold_pools_to_their_shapes(text, pools):
    """Every leaf arrives as a parameter that is aliased to an output; a
    block leaf is block-major; nothing but the write into a leaf (an
    update in place: a scatter by block id, a row set by its slot, the
    step's own rewrite of `h`) produces a result of a whole leaf's size
    beside the parameter."""
    entry, header = _entry(text)
    aliased = {int(p) for p in re.findall(
        r"\{[\d,\s]*\}:\s*\((\d+),\s*\{[\d,\s]*\},\s*(?:may|must)-alias\)",
        header)}
    h, taps, blocks = _leaf_shapes(pools)
    for leaf, count in ((h, N_MAMBA), (taps, N_MAMBA),
                        (blocks, 2 * N_ATTENTION)):
        params = [(layout, int(re.search(r"parameter\((\d+)\)",
                                         line).group(1)))
                  for _, dims, layout, op, line in entry
                  if op == "parameter" and dims == leaf]
        assert len(params) == count, (leaf, len(params))
        assert {n for _, n in params} <= aliased, (
            f"a {leaf} leaf is not updated in place: parameters "
            f"{sorted({n for _, n in params} - aliased)} alias no output")
        if leaf == blocks:
            assert all(layout[-1] == 0 for layout, _ in params), (
                f"device layout {params[0][0]} of a {leaf} leaf does not "
                "keep the block dimension major-most")
    # a second copy of the state would be 36 results of h's size that are
    # no output: every result of that size is one of the 36 outputs
    made = [(name, op) for name, dims, _, op, _ in entry
            if dims == h and op != "parameter"]
    assert len(made) <= N_MAMBA, (
        f"{len(made)} results the size of a layer's state for {N_MAMBA} "
        f"layers: {made[:4]}")


def test_weights_are_made_by_small_programs_that_fit(cell):
    from benchmarks.families import granite_hybrid as fam
    from benchmarks.reference import granite_hybrid as ref

    cfg, on = cell["cfg"], cell["on"]
    for kind in ref.LAYER_KINDS:
        _compile(f"granite weights of a {kind} layer from the seed", jax.jit(
            lambda s, n, kind=kind: fam.layer_to_program(
                ref.init_layer(s, cfg, n, kind), jnp.bfloat16)).lower(
                    *on((_vec(jnp.uint32), _vec(jnp.int32)))))
    _compile("granite embedding from the seed", jax.jit(
        lambda s: fam.top_to_program(
            ref.init_top(s, cfg), jnp.bfloat16)).lower(
                on(_vec(jnp.uint32))))


def test_the_pools_are_one_entry_a_layer_at_the_issues_sizes(cell):
    from benchmarks.counts import granite_hybrid as counts

    e, model, cfg = cell["engine"], cell["model"], cell["cfg"]
    kinds = model.cache_kinds()
    assert kinds == tuple("table" if k == "attention" else "state"
                          for k in cfg["layer_types"])
    assert kinds.count("state") == N_MAMBA and len(kinds) == 40
    h, taps, blocks = _leaf_shapes(cell["pools"])
    assert h == (e["slots"], 64, 64, 128)
    assert taps == (e["slots"], 3, 4352)
    assert blocks == (e["pool_blocks"], 16, 512)
    assert [layer["h"].dtype for layer in cell["pools"] if "h" in layer] \
        == [jnp.float32] * N_MAMBA
    held = sum(leaf.dtype.itemsize * int(np.prod(leaf.shape))
               for layer in cell["pools"] for leaf in layer.values())
    # state 4.83 GB + taps 0.06 + table rows 2.15 (ISSUE 42's byte table
    # says 1.07: it left out one of keys and values)
    assert 7.03e9 < held < 7.05e9
    assert model.slot_state_bytes(jnp.bfloat16) \
        == counts.slot_state_bytes(cfg) == 76_437_504
    weights = sum(s.dtype.itemsize * int(np.prod(s.shape))
                  for s in jax.tree_util.tree_leaves(cell["params"]))
    assert 6.38e9 < weights < 6.39e9


def test_decode_step(cell):
    from bigdl_tpu.serving import engine as eng

    e, on = cell["engine"], cell["on"]
    slots, per_slot = e["slots"], e["max_len"] // e["block_size"]
    i32, f32 = jnp.int32, jnp.float32
    dec = on((cell["params"], cell["pools"], _vec(i32, slots),
              _vec(i32, slots), _vec(i32, slots), _vec(i32, slots),
              _vec(f32, slots), _vec(i32, slots), _vec(f32, slots),
              _vec(jnp.bool_, slots), _vec(i32, slots, per_slot)))
    text = _compile(f"granite decode step, {slots} slots",
                    eng._decode_step.lower(cell["model"], *dec))
    _hold_pools_to_their_shapes(text, cell["pools"])


@pytest.mark.parametrize("bucket", [512, 1024, 2048, 3072])
def test_prefill(cell, bucket):
    from bigdl_tpu.serving import engine as eng

    e, on = cell["engine"], cell["on"]
    assert bucket in e["prefill_buckets"] and len(e["prefill_buckets"]) == 4
    assert bucket % cell["cfg"]["mamba_chunk_size"] == 0
    bs = e["block_size"]
    i32 = jnp.int32
    ids = {"table": _vec(i32, bucket // bs),
           "state": {"slot": _vec(i32), "keep": _vec(i32)}}
    pre = on((cell["params"], cell["pools"], _vec(i32, 1, bucket),
              _vec(i32), ids, _vec(i32, 1, e["max_len"] // bs)))
    text = _compile(f"granite prefill, bucket {bucket}",
                    eng._prefill_step.lower(cell["model"], *pre))
    _hold_pools_to_their_shapes(text, cell["pools"])


def test_clearing_a_released_slots_state_copies_no_leaf(cell):
    from bigdl_tpu.serving import engine as eng

    text = _compile("granite clear of one slot's state",
                    eng._clear_slot_state.lower(
                        cell["model"].cache_kinds(),
                        *cell["on"]((cell["pools"], _vec(jnp.int32)))))
    _hold_pools_to_their_shapes(text, cell["pools"])
    entry, _ = _entry(text)
    _, _, blocks = _leaf_shapes(cell["pools"])
    assert not [line for _, dims, _, op, line in entry
                if dims == blocks and op not in ("parameter",
                                                 "get-tuple-element")]
