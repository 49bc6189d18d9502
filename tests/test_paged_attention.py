"""`ops/kv_cache.paged_attention`, the one way the decode step attends
the paged cache (ISSUE 31), against a plain reference.

The reference is float32 NumPy attention over each slot's OWN contiguous
rows `[0, pos]`, written here: it never sees a pool, a block table or
anything of `ops/kv_cache`. The case builder lays those rows out into a
pool through shuffled disjoint chains (block 0, the scratch block, in no
chain) and the function under test has to find them again. Both operand
layouts are held to it, each at widths where `paged_attention_form`
chooses it from the shape: "heads" at the toy widths of the CPU suites
and at a 128-wide head, "rows" where a row is whole 128-lane tiles and a
head is not (gpt2-medium's 16 x 64). What the two forms owe each other is
tests/test_rows_attention.py's. CPU, float32 unless a test says so."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.ops.kv_cache import (attended_blocks, decode_read,
                                    init_block_pool, paged_attention,
                                    paged_attention_form, ragged_read_sizes)

# one (b, h, nb, bs, d) per form for the tests that take `form`
SHAPE_OF = {"heads": (3, 2, 4, 4, 8), "rows": (3, 2, 4, 4, 64)}
FORMS = sorted(SHAPE_OF)


def _case(b, h, nb, bs, d, seed=0, pos=None, dtype=jnp.float32,
          unowned=None, tails_to_scratch=False, beyond=None):
    """Dense per-slot keys and values (b, nb*bs, h*d) and the same rows
    laid into a pool through shuffled disjoint chains. `unowned` fills
    every block no visible row lives in (block 0 always among them);
    `tails_to_scratch` points the table beyond a slot's clock at block
    0, as the engine's table rows do; `beyond` fills the rows after a
    slot's clock, those of its last live block too. A clock of -1 is a
    row that is NOT SEATED: its table is all scratch, as the engine's
    free slots are (it is handed clock 0), and the reference gives it
    zeros. Returns (paged_attention's arguments, the dense q, k, v and
    clocks the reference takes)."""
    rng = np.random.RandomState(seed)
    n = b * nb + 1
    dense_k = rng.randn(b, nb * bs, h * d).astype(np.float32)
    dense_v = rng.randn(b, nb * bs, h * d).astype(np.float32)
    q = rng.randn(b, h, 1, d).astype(np.float32)
    if pos is None:
        pos = rng.randint(0, nb * bs, size=b)
    pos = np.asarray(pos, np.int32)
    table = rng.permutation(np.arange(1, n)).reshape(b, nb).astype(np.int32)
    if beyond is not None:
        for slot in range(b):
            dense_k[slot, pos[slot] + 1:] = beyond
            dense_v[slot, pos[slot] + 1:] = beyond
    if dtype != jnp.float32:        # the reference sees what the pool holds
        dense_k, dense_v, q = (
            np.asarray(jnp.asarray(a, dtype).astype(jnp.float32))
            for a in (dense_k, dense_v, q))
    k_pool, v_pool = (np.array(p) for p in init_block_pool(n, h, bs, d))
    assert k_pool.shape == (n, bs, h * d)
    if unowned is not None:
        k_pool[:] = v_pool[:] = unowned
    for slot in range(b):
        live = int(pos[slot]) // bs + 1 if tails_to_scratch else nb
        for j in range(live):
            k_pool[table[slot, j]] = dense_k[slot, j * bs:(j + 1) * bs]
            v_pool[table[slot, j]] = dense_v[slot, j * bs:(j + 1) * bs]
        table[slot, live:] = 0
        if pos[slot] < 0:
            table[slot] = 0
    args = (jnp.asarray(q, dtype), jnp.asarray(k_pool, dtype),
            jnp.asarray(v_pool, dtype), jnp.asarray(table),
            jnp.asarray(np.maximum(pos, 0)))
    return args, (q, dense_k, dense_v, pos)


def _reference(q, dense_k, dense_v, pos, sm_scale=None):
    """Softmax attention, one slot and one head at a time, over the
    slot's rows [0, pos]: (b, h, 1, d) float32."""
    b, h, _, d = q.shape
    scale = 1.0 / np.sqrt(d) if sm_scale is None else sm_scale
    out = np.zeros((b, h, 1, d), np.float32)
    for slot in range(b):
        seen = int(pos[slot]) + 1
        if not seen:                    # not seated: zeros
            continue
        for head in range(h):
            lanes = slice(head * d, (head + 1) * d)
            k = dense_k[slot, :seen, lanes]
            v = dense_v[slot, :seen, lanes]
            s = (k @ q[slot, head, 0]) * np.float32(scale)
            p = np.exp(s - s.max())
            out[slot, head, 0] = (p / p.sum()) @ v
    return out


def _close(got, want, tol=1e-5):
    np.testing.assert_allclose(np.asarray(got, np.float32), want, rtol=tol,
                               atol=tol * np.abs(want).max())


@pytest.mark.parametrize("b,h,nb,bs,d,form", [
    (1, 1, 1, 4, 8, "heads"),       # one slot, one head, one block
    (2, 2, 4, 4, 8, "heads"),
    (3, 4, 4, 4, 16, "heads"),      # odd batch
    (1, 4, 4, 4, 8, "heads"),
    (4, 8, 8, 16, 64, "rows"),      # the 43M model of the engine suites
    (2, 1, 4, 4, 8, "heads"),       # one head, several slots
    (2, 2, 4, 4, 128, "heads"),     # a head is a whole tile: splits unpadded
    (2, 16, 4, 16, 64, "rows"),     # gpt2-medium's widths
], ids=lambda v: str(v))
def test_paged_attention_equals_the_plain_reference(b, h, nb, bs, d, form):
    assert paged_attention_form(h, d) == form
    args, dense = _case(b, h, nb, bs, d)
    got = paged_attention(*args)
    assert got.shape == (b, h, 1, d) and got.dtype == jnp.float32
    _close(got, _reference(*dense))


@pytest.mark.parametrize("form", FORMS)
def test_ragged_clocks(form):
    """Clocks at 0, mid-block, at a block boundary, at the last
    position: the visible extent is [0, pos], no more and no less."""
    b, h, nb, bs, d = SHAPE_OF[form]
    assert paged_attention_form(h, d) == form
    for pos in ([0, 2, bs], [bs - 1, 2 * bs, nb * bs - 1]):
        args, dense = _case(b, h, nb, bs, d, pos=pos)
        _close(paged_attention(*args), _reference(*dense))


@pytest.mark.parametrize("form", FORMS)
def test_custom_sm_scale(form):
    args, dense = _case(*SHAPE_OF[form])
    got = paged_attention(*args, 0.25)
    _close(got, _reference(*dense, sm_scale=0.25))
    assert not np.allclose(np.asarray(got), np.asarray(paged_attention(*args)))


@pytest.mark.parametrize("form", FORMS)
def test_jit_equals_eager(form):
    """The engine runs it inside a jitted step, the tests above eagerly:
    the same numbers to rounding (fusion may reorder a sum)."""
    args, dense = _case(*SHAPE_OF[form])
    got = jax.jit(paged_attention)(*args)
    _close(got, np.asarray(paged_attention(*args)), tol=1e-6)
    _close(got, _reference(*dense))


@pytest.mark.parametrize("form", FORMS)
def test_a_nan_scratch_block_and_table_tail_are_never_read(form):
    """Block 0 and every block that holds no visible row are NaN, and
    each table row points at block 0 beyond its clock, as the engine's
    do: nothing of that reaches the result (scores are masked after the
    contraction, value rows zeroed before theirs)."""
    b, h, nb, bs, d = SHAPE_OF[form]
    args, dense = _case(b, h, nb, bs, d, pos=[1, bs, 2 * bs + 1],
                        unowned=np.nan, tails_to_scratch=True)
    assert np.isnan(np.asarray(args[1][0])).all()
    assert (np.asarray(args[3])[:, -1] == 0).all()
    got = np.asarray(paged_attention(*args))
    assert np.isfinite(got).all()
    _close(got, _reference(*dense))


def test_bf16_pool_heads_form_within_the_bf16_tolerance():
    """A bfloat16 pool: the head-split form widens the gathered rows to
    float32 and rounds once, on the way out (the rows form's own bf16
    bound is tests/test_rows_attention.py's)."""
    args, dense = _case(2, 4, 4, 4, 16, dtype=jnp.bfloat16)
    got = paged_attention(*args)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got.astype(jnp.float32)),
                               _reference(*dense), atol=2e-2, rtol=2e-2)


def test_rejects_a_query_of_more_than_one_row():
    (q, *rest), _ = _case(2, 2, 4, 4, 8)
    with pytest.raises(ValueError, match="one row"):
        paged_attention(jnp.concatenate([q, q], axis=2), *rest)


# ------------------------------------------- the ragged read (ISSUE 32)
# The rows form reads each slot's own live chunks. A table of 20 blocks
# of 4 rows is read in chunks of 3 blocks = 12 rows (7 a slot, the last
# one a block short of whole), six slots: 42 chunks, compiled for 3 of
# them (a batch that is mostly empty seats), for 21 (half: full seats at
# mixed depths, ISSUE 39) or for all 42.
RAGGED = (6, 2, 20, 4, 64)
_FULL = RAGGED[2] * RAGGED[3] - 1


def test_the_ragged_shape_reads_in_chunks_of_three_blocks():
    assert paged_attention_form(RAGGED[1], RAGGED[4]) == "rows"
    assert ragged_read_sizes(RAGGED[0], RAGGED[2]) == (
        3, (3, 21, 42))


@pytest.mark.parametrize("pos", [
    [0, 11, 12, 23, 24, _FULL],         # chunk edges: last row, first row
    [_FULL] * 6,                        # a full table: the largest read
    [0, 0, 0, 0, 0, 0],                 # the first row of each
    [5, -1, 40, -1, -1, _FULL],         # unseated rows between seated ones
    [-1, -1, -1, -1, -1, 7],            # one seated slot, the last
    [-1] * 6,                           # nobody seated: zeros, finite
    [35, 2, 70, 13, 47, 60],
], ids=["chunk-edges", "full-table", "clock-zero", "unseated-between",
        "one-seated", "none-seated", "mixed"])
def test_ragged_read_equals_the_plain_reference(pos):
    args, dense = _case(*RAGGED, pos=pos, tails_to_scratch=True)
    got = np.asarray(paged_attention(*args))
    assert np.isfinite(got).all()
    _close(got, _reference(*dense))
    unseated = np.asarray(pos) < 0
    assert not got[unseated].any()


@pytest.mark.parametrize("pos", [
    [0, 11, 12, 23, 24, _FULL - 1],
    [5, -1, 40, -1, -1, 30],
    [35, 2, 70, 13, 47, 60],
], ids=["chunk-edges", "unseated-between", "mixed"])
def test_nothing_beyond_a_clock_is_read_not_in_its_last_live_chunk_either(
        pos):
    """NaN in the scratch block, in every block that holds no visible
    row, in the table's tail (it points at the scratch block) and in
    the rows after the clock INSIDE the last live chunk: the result is
    the clean pool's bit for bit, and the reference's."""
    clean, dense = _case(*RAGGED, pos=pos, tails_to_scratch=True)
    dirty, _ = _case(*RAGGED, pos=pos, tails_to_scratch=True,
                     unowned=np.nan, beyond=np.nan)
    assert np.isnan(np.asarray(dirty[1][0])).all()
    want = np.asarray(paged_attention(*clean))
    got = np.asarray(paged_attention(*dirty))
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, want)
    _close(got, _reference(*dense))


@pytest.mark.parametrize("first", [0, 9, 21, _FULL - 3])
def test_a_verify_shaped_call_equals_its_rows_called_one_at_a_time(first):
    """The speculative verify's shape: k + 1 rows of ONE slot at
    consecutive clocks, all pointing at that slot's table (9..12 and
    21..24 cross a chunk's edge). Each row's result is bitwise what a
    call of that row alone gives: its chunks hang on its own clock."""
    (q, k_pool, v_pool, table, _), _ = _case(*RAGGED)
    rows = 4
    clocks = jnp.arange(first, first + rows, dtype=jnp.int32)
    shared = jnp.broadcast_to(table[2], (rows, table.shape[1]))
    together = np.asarray(paged_attention(q[:rows], k_pool, v_pool, shared,
                                          clocks))
    for j in range(rows):
        alone = np.asarray(paged_attention(
            q[j:j + 1], k_pool, v_pool, shared[:1], clocks[j:j + 1]))
        np.testing.assert_array_equal(together[j:j + 1], alone)


# the five neighbours' clock beside slot 2 at clock 30 (3 chunks): the
# batch's live chunks are 3, 13, 18, 23 and 38 of 42, so alone it takes
# the read compiled for 3, beside short or equal neighbours the one for
# 21, beside longer ones the one for all
NEIGHBOURS = (-1, 13, 30, 40, _FULL)


def test_the_neighbours_clocks_take_all_three_compiled_sizes():
    table = np.tile(np.arange(1, RAGGED[2] + 1), (RAGGED[0], 1))
    reads = []
    for others in NEIGHBOURS:
        pos = np.full(RAGGED[0], others)
        pos[2] = 30
        reads.append(decode_read(np.maximum(pos, 0),
                                 table * (pos >= 0)[:, None], RAGGED[3]))
    assert reads == [("1/16", 9), ("1/2", 63), ("1/2", 63), ("1", 120),
                     ("1", 120)]


def test_a_slots_result_does_not_hang_on_the_other_slots_clocks():
    """Slot 2 at clock 30 beside empty, short, equal, longer and full
    neighbours: the batch takes each of the three compiled reads, and
    slot 2's result is the same bits in all of them."""
    results = []
    for others in NEIGHBOURS:
        pos = [others] * RAGGED[0]
        pos[2] = 30
        args, dense = _case(*RAGGED, pos=pos, tails_to_scratch=True)
        got = np.asarray(paged_attention(*args))
        _close(got, _reference(*dense))
        results.append(got[2])
    for other in results[1:]:
        np.testing.assert_array_equal(other, results[0])


@pytest.mark.parametrize("window", [None, 9])
def test_a_grouped_slots_result_does_not_hang_on_the_other_slots_clocks(
        window):
    """The same pin for the grouped form (4 query heads over 2
    key-value heads of 32), without and with a lower bound of
    visibility `lo` (a window of 9 rows that ends at the clock)."""
    from bigdl_tpu.ops.kv_cache import grouped_paged_attention

    b, g, nb, bs, d = RAGGED[0], 2, RAGGED[2], RAGGED[3], 32
    (_, k_pool, v_pool, table, _), _ = _case(b, g, nb, bs, d)
    q = jnp.asarray(np.random.RandomState(5).randn(b, 4, d), jnp.float32)
    results = []
    for others in NEIGHBOURS:
        pos = np.full(b, others, np.int32)
        pos[2] = 30
        seated = jnp.asarray(pos >= 0)
        clocks = jnp.asarray(np.maximum(pos, 0), jnp.int32)
        lo = None if window is None else clocks - (window - 1)
        got = np.asarray(grouped_paged_attention(
            q, k_pool, v_pool, table * seated[:, None], clocks, g,
            d ** -0.5, lo=lo))
        assert np.isfinite(got).all() and got[2].any()
        assert not got[~np.asarray(seated)].any()
        results.append(got[2])
    for other in results[1:]:
        np.testing.assert_array_equal(other, results[0])
    if window is not None:          # the window is seen, not the prefix
        whole = np.asarray(grouped_paged_attention(
            q, k_pool, v_pool, table, jnp.full(b, 30, jnp.int32), g,
            d ** -0.5))[2]
        assert np.abs(whole - results[0]).max() > 1e-3


@pytest.mark.parametrize("pos,chunks,read,blocks", [
    # blocks 1, 3, 4, 6, 7, 20 -> chunks 1, 1, 2, 2, 3, 7 = 16: half, 21
    ([0, 11, 12, 23, 24, _FULL], 16, 21, 63),
    # six full slots: all 42, whose 126 blocks hold 6 of padding (a
    # seventh chunk is 2 blocks): never more than the table's 120
    ([_FULL] * 6, 42, 42, 120),
    # two seated: blocks 2 and 4 -> chunks 1 + 2 = 3, the small read
    ([5, -1, 13, -1, -1, -1], 3, 3, 9),
    # two seated: blocks 2 and 11 -> chunks 1 + 4 = 5, read as half: 21
    ([5, -1, 40, -1, -1, -1], 5, 21, 63),
    # blocks 9, 1, 18, 4, 12, 16 -> chunks 3, 1, 6, 2, 4, 6 = 22, one over
    # half: read as all 42
    ([35, 2, 70, 13, 47, 60], 22, 42, 120),
    # the same but slot 4 a chunk shorter (blocks 9): 21, the half read whole
    ([35, 2, 70, 13, 35, 60], 21, 21, 63),
    # one seated slot in its first block: 1 chunk, read as 3
    ([-1, -1, 2, -1, -1, -1], 1, 3, 9),
], ids=["chunk-edges", "full-table", "two-seated-short", "two-seated",
        "mixed-22",
        "mixed-21", "one-seated"])
def test_attended_blocks_is_the_count_made_by_hand(pos, chunks, read,
                                                   blocks):
    """What the engine hangs on its `decode_step` span: live chunks
    (a slot's blocks rounded up to chunks of 3), rounded up to the read
    compiled for them, in blocks, and never more than the table's 120
    (its share cannot read above 1)."""
    (_, _, _, table, clocks), _ = _case(*RAGGED, pos=pos,
                                        tails_to_scratch=True)
    b, _, nb, bs, _ = RAGGED
    chunk_blocks, sizes = ragged_read_sizes(b, nb)
    seated = [p for p in pos if p >= 0]
    assert sum(-(-(p // bs + 1) // chunk_blocks) for p in seated) == chunks
    assert read in sizes
    assert attended_blocks(np.asarray(clocks), np.asarray(table), bs) \
        == min(read * chunk_blocks, b * nb) == blocks


@pytest.mark.parametrize("pos,want", [
    ([0, -1], ("1/2", 1)),          # 1 chunk of 2: sizes (1, 1, 2)
    ([0, 3], ("1", 2)),
    ([-1, -1], ("1/2", 1)),         # nobody seated: the smallest read
], ids=["one-of-two", "both", "none"])
def test_a_small_tables_read_goes_by_the_larger_shares_name(pos, want):
    """Two slots of one block: a sixteenth and a half of 2 chunks both
    round up to 1, and a read of 1 chunk IS half of the table."""
    pos = np.asarray(pos)
    table = np.array([[1], [2]]) * (pos >= 0)[:, None]
    assert ragged_read_sizes(2, 1) == (1, (1, 2))
    assert decode_read(np.maximum(pos, 0), table, 4) == want
