"""The paged KV pool's storage shape (ISSUE 25): what the TPU compiler
makes of it, and that any widths round-trip through it.

The first half compiles the engine's two serving programs ahead of time
for a described `v5e:2x2` (no chip attached; the TPU compiler is
installed), on a 2-layer model at gpt2-medium's widths with the chat
cell's pool geometry, and holds them to what the shape was chosen for:
the device lays a leaf out block-major, no instruction relayouts a whole
leaf on the way in or out, and every leaf is updated in place. A compile
that passes is not a chip run. The decode program attends the gathered
rows as they are stored (ISSUE 29): nothing cache-sized in it has a head
for its minor dimension, and one layer's attention holds less in
temporaries than the head-split form compiled beside it. It reads each
slot's live chunks only (ISSUE 32): nothing in it spans the full
gathered table, and no second copy of a pool leaf exists.

libtpu is touched only inside the `topo` fixture (one process at a time
may load it; a module that touches it while being imported breaks the
collection under several workers), and the tests skip where no topology
can be described.

The second half runs on the CPU: pools whose rows are not whole
128-lane tiles still hold exactly what was written.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from bigdl_tpu.ops.kv_cache import (gather_block_cache, init_block_pool,
                                    paged_attention_form,
                                    paged_attention_heads,
                                    paged_attention_rows,
                                    write_decode_blocks,
                                    write_prompt_blocks)

# gpt2-medium's widths, the chat cell's engine (benchmarks/traffic/
# chat-open.json): 64 slots, 3,073 blocks of 16 tokens
DIM, HEADS, VOCAB, MAX_LEN = 1024, 16, 50257, 1024
SLOTS, BLOCK, POOL_BLOCKS, BUCKET = 64, 16, 3073, 256
LAYERS = 2


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


@pytest.fixture(scope="module")
def programs(topo, no_cache):
    """{'decode' | 'prefill': (entry instructions, module header)} of
    the compiled programs, the decode program's whole text, and the
    leaf's shape."""
    from bigdl_tpu.models.transformer import TransformerConfig, \
        TransformerLM
    from bigdl_tpu.serving import engine as eng

    one = SingleDeviceSharding(topo.devices[0])
    model = TransformerLM(TransformerConfig(
        vocab_size=VOCAB, max_len=MAX_LEN, dim=DIM, num_heads=HEADS,
        num_layers=LAYERS, mlp_ratio=4))

    def on(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            tree)

    def vec(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype)

    params = jax.eval_shape(lambda: model.serving_params(
        model.init(jax.random.PRNGKey(0))))
    pools = jax.eval_shape(lambda: model.init_block_pool(
        POOL_BLOCKS, BLOCK, jnp.float32))
    per_slot = MAX_LEN // BLOCK
    i32, f32 = jnp.int32, jnp.float32
    dec = on((params, pools, vec(i32, SLOTS), vec(i32, SLOTS),
              vec(i32, SLOTS), vec(i32, SLOTS), vec(f32, SLOTS),
              vec(i32, SLOTS), vec(f32, SLOTS), vec(jnp.bool_, SLOTS),
              vec(i32, SLOTS, per_slot)))
    pre = on((params, pools, vec(i32, 1, BUCKET), vec(i32),
              vec(i32, BUCKET // BLOCK), vec(i32, 1, per_slot)))
    decode_text = eng._decode_step.lower(model, *dec) \
        .compile().as_text()
    return {
        "leaf": pools[0]["k"].shape,
        "decode": _entry(decode_text),
        "decode_text": decode_text,
        "prefill": _entry(eng._prefill_step.lower(model, *pre)
                          .compile().as_text()),
    }


_INSTR = re.compile(
    r"^\s*(?:ROOT )?%?(?P<name>\S+) = (?P<dtype>\w+)\[(?P<dims>[\d,]*)\]"
    r"(?:\{(?P<layout>[\d,]*)[^}]*\})? (?P<op>[\w-]+)\(")


def _entry(text):
    """(The entry computation's instructions: name, dims, minor-to-major
    layout, opcode, and the line; the module's header line)."""
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


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_pool_parameters_are_block_major(programs, program):
    leaf = programs["leaf"]
    params = [(name, layout) for name, dims, layout, op, _
              in programs[program][0]
              if op == "parameter" and dims == leaf]
    assert len(params) == 2 * LAYERS, params
    for name, layout in params:
        # minor-to-major: the block index (dimension 0) comes last
        assert layout[-1] == 0, (
            f"{name}: device layout {layout} of a {leaf} leaf does not "
            "keep the block dimension major-most")


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_nothing_relayouts_a_whole_leaf(programs, program):
    """Nothing in the entry computation produces a leaf-sized result
    except the scatter that updates the donated leaf in place: no copy,
    no transpose, no fusion that rewrites the pool."""
    leaf = programs["leaf"]
    n = int(np.prod(leaf))
    entry = programs[program][0]
    whole = [(name, dims, op, line) for name, dims, _, op, line in entry
             if op != "parameter" and sorted(dims) == sorted(leaf)]
    assert len(whole) == 2 * LAYERS, [w[:3] for w in whole]
    for name, dims, op, line in whole:
        assert dims == leaf and op == "fusion" and "/scatter" in line, (
            f"{name} = {op} -> {dims}: a whole pool leaf ({n} elements) "
            f"is produced by something else than the scatter: {line[:200]}")
    # nor a leaf under other dimensions
    for name, dims, _, op, line in entry:
        assert not (op in ("copy", "transpose", "copy-start")
                    and int(np.prod(dims or (1,))) == n), line[:200]


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_every_pool_leaf_is_donated_in_place(programs, program):
    entry, header = programs[program]
    leaf = programs["leaf"]
    pool_params = {
        int(re.search(r"parameter\((\d+)\)", line).group(1))
        for _, dims, _, op, line in entry
        if op == "parameter" and dims == leaf}
    aliased = {int(p) for p in re.findall(
        r"\{[\d,\s]*\}:\s*\((\d+),\s*\{[\d,\s]*\},\s*(?:may|must)-alias\)",
        header)}
    assert len(pool_params) == 2 * LAYERS
    assert pool_params <= aliased, (
        f"pool parameters {sorted(pool_params - aliased)} are not in "
        f"input_output_alias ({sorted(aliased)})")


def _results(text):
    """(dtype, dims, opcode) of every instruction of the module: the
    entry computation, the fusions' bodies and the conditionals'
    branches alike."""
    return [(m[1], tuple(int(d) for d in m[2].split(",")), m[3])
            for m in re.finditer(
                r"= (\w+)\[([\d,]+)\](?:\{[^}]*\})? ([\w-]+)\(", text)]


def test_decode_never_makes_the_head_the_minor_dimension(programs):
    """gpt2-medium's 16 x 64 lanes take the rows form: no instruction
    of the decode program, inside a fusion or out, has a cache-sized
    result (a sixteenth of the gathered table, the smallest read, or
    more) whose minor dimension is one head's 64 (`f32[64,1024,16,64]`,
    which the device pads to 128 lanes: the head-split form's two
    reshapes a layer)."""
    assert paged_attention_form(HEADS, DIM // HEADS) == "rows"
    smallest = SLOTS * MAX_LEN * DIM // 16
    shapes = {dims for _, dims, _ in _results(programs["decode_text"])}
    assert any(s[-1] == DIM and int(np.prod(s)) >= smallest
               for s in shapes)                     # it gathers rows
    split = sorted(s for s in shapes if s[-1] == DIM // HEADS
                   and int(np.prod(s)) >= smallest)
    assert not split, split


def test_decode_reads_live_chunks_and_holds_no_second_pool(programs):
    """The decode read is ragged (ISSUE 32): the live chunks of the
    batch, at most half of all chunks in one pass. So (a) nothing in
    the program, in a branch of the read's conditional or out, has a
    result over the full gathered extent (64 slots x 1,024 rows of
    1,024 lanes); (b) nothing is as large as a pool leaf but the leaf
    itself, float32 as it is stored, handed on or scattered into in
    place: no bfloat16 copy of a pool, which is what the compiler makes
    when the chunks are a loop (it rounds the WHOLE pool for the dot
    and hoists that out); (c) the program has no loop to carry one."""
    text = programs["decode_text"]
    leaf = programs["leaf"]
    n, gathered = int(np.prod(leaf)), SLOTS * MAX_LEN * DIM
    results = _results(text)
    over = sorted({(d, dims, op) for d, dims, op in results
                   if int(np.prod(dims)) >= gathered})
    assert not over, over
    handed_on = {"parameter", "get-tuple-element", "bitcast", "tuple"}
    second = sorted({(d, dims, op) for d, dims, op in results
                     if int(np.prod(dims)) == n
                     and not (d == "f32" and dims == leaf
                              and op in handed_on | {"scatter", "fusion"})})
    assert not second, second
    written = [op for d, dims, op in results
               if dims == leaf and op == "scatter"]
    assert len(written) == 2 * LAYERS, len(written)
    assert " while(" not in text
    # the read is one conditional a layer; the sampler has three of its
    # own (all rows greedy, a candidates row, a full-sort row: ISSUE 37)
    assert LAYERS <= text.count(" conditional(") <= LAYERS + 3


def test_rows_form_holds_less_than_the_head_split_form(topo, no_cache):
    """One layer's decode attention at the cells' shapes, both forms
    compiled side by side: the rows form's temporaries are the smaller
    (ISSUE 29's sizing: 0.27 against 0.84 GB)."""
    one = SingleDeviceSharding(topo.devices[0])
    per_slot = MAX_LEN // BLOCK

    def on(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    f32, i32 = jnp.float32, jnp.int32
    args = (on(f32, SLOTS, HEADS, 1, DIM // HEADS),
            on(f32, POOL_BLOCKS, BLOCK, DIM), on(f32, POOL_BLOCKS, BLOCK, DIM),
            on(i32, SLOTS, per_slot), on(i32, SLOTS))
    temp = {f.__name__: jax.jit(f).lower(*args).compile()
            .memory_analysis().temp_size_in_bytes
            for f in (paged_attention_rows, paged_attention_heads)}
    assert temp["paged_attention_rows"] < 0.5 * temp["paged_attention_heads"], temp


# ------------------------------------------------------------- CPU cases

@pytest.mark.parametrize("heads,head_dim,block", [
    (2, 4, 4),          # H*D = 8: the tiny test models
    (3, 20, 4),         # 60: not a multiple of 8 either
    (5, 24, 8),         # 120: just short of a tile
    (2, 96, 16),        # 192: one tile and a half
    (16, 64, 16),       # 1,024: gpt2-medium's row, whole tiles
], ids=lambda v: str(v))
def test_any_widths_round_trip_bitwise(heads, head_dim, block):
    """write -> gather is the identity on the bits, prompt and decode
    writes alike, whatever the row's width."""
    rng = np.random.RandomState(heads * 1000 + head_dim)
    nb, slots = 3, 2
    s = nb * block - 1                      # a ragged last block
    kp, vp = init_block_pool(1 + slots * nb, heads, block, head_dim)
    assert kp.shape[0] == 1 + slots * nb    # blocks are axis 0
    table = np.arange(1, 1 + slots * nb, dtype=np.int32).reshape(slots, nb)
    want_k = rng.randn(slots, heads, nb * block, head_dim).astype(np.float32)
    want_v = rng.randn(slots, heads, nb * block, head_dim).astype(np.float32)
    for r in range(slots):
        kp, vp = write_prompt_blocks(
            kp, vp, jnp.asarray(want_k[r:r + 1, :, :s]),
            jnp.asarray(want_v[r:r + 1, :, :s]), jnp.asarray(table[r]))
    # the last position of every row by a decode write
    pos = np.full((slots,), s, np.int32)
    kp, vp = write_decode_blocks(
        kp, vp, jnp.asarray(want_k[:, :, s:s + 1]),
        jnp.asarray(want_v[:, :, s:s + 1]),
        jnp.asarray(table[np.arange(slots), pos // block]),
        jnp.asarray(pos % block))
    got_k = np.asarray(gather_block_cache(kp, jnp.asarray(table), heads))
    got_v = np.asarray(gather_block_cache(vp, jnp.asarray(table), heads))
    np.testing.assert_array_equal(got_k, want_k)
    np.testing.assert_array_equal(got_v, want_v)
    assert not np.asarray(kp[0]).any()      # the scratch block untouched
