"""The rows form of `ops/kv_cache.paged_attention` (ISSUE 29): the
decode step contracts over the gathered rows as the pool stores them,
heads side by side in the lanes, with a block-diagonal query.

It is the head-split form's mathematics in another operand layout, so it
is held to that form: to rounding in float32, to the existing bf16
tolerance for a bf16 pool, with the same hygiene (rows beyond a slot's
clock never reach the result, a non-finite visible row poisons its own
slot only). Which form runs is decided by the shape and nothing else.
Pins that compare a path with itself (warm == cold) hold in either form:
an engine at widths where the rows form engages serves them bitwise and
says which form it runs. CPU, small sizes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import obs
from bigdl_tpu.ops.kv_cache import (paged_attention, paged_attention_form,
                                    paged_attention_heads,
                                    paged_attention_rows)

BLOCK = 8
# widths where the rows form engages: a row is whole 128-lane tiles
# (one, one, and the four of the 43M model the engine suites serve)
ROWS_WIDTHS = [(2, 64), (4, 32), (8, 64)]


def _case(h, d, dtype=jnp.float32, seed=0, b=3, nb=4):
    """A pool, shuffled disjoint block chains (block 0 never in a
    table), ragged clocks: mid-block, a block boundary, nearly full."""
    rng = np.random.RandomState(seed + 17 * h + d)
    n = b * nb + 1
    k_pool = rng.randn(n, BLOCK, h * d).astype(np.float32)
    v_pool = rng.randn(n, BLOCK, h * d).astype(np.float32)
    table = rng.permutation(np.arange(1, n))[:b * nb].reshape(b, nb)
    pos = np.array([3, 2 * BLOCK - 1, nb * BLOCK - 2], np.int32)[:b]
    q = rng.randn(b, h, 1, d).astype(np.float32)
    return (jnp.asarray(q, dtype), jnp.asarray(k_pool, dtype),
            jnp.asarray(v_pool, dtype), jnp.asarray(table, jnp.int32),
            jnp.asarray(pos))


def _row_of(table, slot, position):
    """(block, offset) of a slot's logical position."""
    return int(table[slot, position // BLOCK]), position % BLOCK


@pytest.mark.parametrize("h,d", ROWS_WIDTHS)
def test_rows_form_equals_head_split_form_fp32(h, d):
    args = _case(h, d)
    want = np.asarray(paged_attention_heads(*args))
    got = paged_attention_rows(*args)
    assert got.shape == want.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                               atol=1e-5 * np.abs(want).max())


@pytest.mark.parametrize("h,d", ROWS_WIDTHS)
def test_rows_form_bf16_pool_within_the_bf16_tolerance(h, d):
    """The rows stay bfloat16 into the dots (float32 accumulation); the
    head-split form widens them first: the tolerance a bf16 pool has
    against the plain reference (tests/test_paged_attention.py)."""
    args = _case(h, d, dtype=jnp.bfloat16)
    want = paged_attention_heads(*args)
    got = paged_attention_rows(*args)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got.astype(jnp.float32)),
        np.asarray(want.astype(jnp.float32)), atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("bad", [np.nan, np.inf], ids=["nan", "inf"])
@pytest.mark.parametrize("h,d", ROWS_WIDTHS)
def test_rows_beyond_the_clock_change_nothing(h, d, bad):
    """Non-finite K and V rows beyond a slot's clock (the rest of its
    current block, a whole later block, the scratch block): the result
    is the clean pool's, bit for bit."""
    q, k_pool, v_pool, table, pos = _case(h, d)
    clean = np.asarray(paged_attention_rows(q, k_pool, v_pool, table, pos))
    k, v = np.array(k_pool), np.array(v_pool)
    tab, clk = np.asarray(table), np.asarray(pos)
    k[0] = v[0] = bad                               # scratch block
    for slot in range(tab.shape[0]):
        for position in range(int(clk[slot]) + 1, tab.shape[1] * BLOCK):
            blk, off = _row_of(tab, slot, position)
            k[blk, off] = v[blk, off] = bad
    got = np.asarray(paged_attention_rows(
        q, jnp.asarray(k), jnp.asarray(v), table, pos))
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, clean)


@pytest.mark.parametrize("leaf", ["k", "v"])
@pytest.mark.parametrize("h,d", ROWS_WIDTHS)
def test_a_non_finite_visible_row_poisons_its_own_slot_only(h, d, leaf):
    """One NaN in ONE head's lanes of a visible row of slot 1: slot 1's
    output is non-finite (the engine evicts by slot), slots 0 and 2 are
    the clean pool's bit for bit."""
    q, k_pool, v_pool, table, pos = _case(h, d)
    clean = np.asarray(paged_attention_rows(q, k_pool, v_pool, table, pos))
    pools = {"k": np.array(k_pool), "v": np.array(v_pool)}
    blk, off = _row_of(np.asarray(table), 1, 5)     # 5 <= clock 15
    pools[leaf][blk, off, d + 1] = np.nan           # head 1's lanes
    got = np.asarray(paged_attention_rows(
        q, jnp.asarray(pools["k"]), jnp.asarray(pools["v"]), table, pos))
    assert not np.isfinite(got[1]).all()
    np.testing.assert_array_equal(got[[0, 2]], clean[[0, 2]])


@pytest.mark.parametrize("h,d,form", [
    (2, 64, "rows"),        # one tile a row, half a tile a head
    (4, 32, "rows"),
    (2, 128, "heads"),      # a head is a whole tile: splits unpadded
    (4, 8, "heads"),        # the toy models: rows are no whole tile
], ids=lambda v: str(v))
def test_the_form_is_chosen_by_the_shape_alone(h, d, form):
    assert paged_attention_form(h, d) == form
    args = _case(h, d)
    chosen = {"rows": paged_attention_rows,
              "heads": paged_attention_heads}[form]
    np.testing.assert_array_equal(np.asarray(paged_attention(*args)),
                                  np.asarray(chosen(*args)))


# ------------------------------------------------------------- the engine

@pytest.fixture
def traced():
    prev = obs.set_enabled(True)
    obs.reset_all()
    obs.set_tracer(obs.SpanTracer(enabled=True))
    yield
    obs.reset_all()
    obs.set_enabled(prev)


def test_engine_at_tile_widths_serves_warm_equal_cold_in_the_rows_form(
        traced):
    """dim 128 over 2 heads: the decode program attends the rows as
    stored. Warm == cold compares the path with itself, so it holds bit
    for bit; `health()` and the `round` span say which form ran."""
    from bigdl_tpu.models.transformer import build_lm
    from bigdl_tpu.serving import InferenceEngine, Request

    m = build_lm(vocab_size=61, dim=128, num_heads=2, num_layers=2,
                 max_len=64)
    m.build(jax.random.PRNGKey(3))
    A = dict(prompt=[5, 9, 3, 7, 2, 8, 4, 6, 1, 3, 9, 2, 7],
             max_new_tokens=6, temperature=0.8, seed=11)
    S = dict(prompt=[30, 31, 32], max_new_tokens=6, temperature=0.9,
             seed=4)

    def engine():
        return InferenceEngine(m, slots=2, prefill_buckets=(8, 16),
                               block_size=4)

    eng = engine()
    assert eng.health()["attn_form"] == "rows"
    cold = eng.run([Request(**A)])[0]
    assert eng.stats["prefix_hits"] == 0
    warm, stranger = eng.run([Request(**A), Request(**S)])
    assert eng.stats["prefix_hits"] == 1
    assert warm.tokens == cold.tokens
    # how far the ragged read engaged (ISSUE 32): every `decode_step`
    # span says what its read gathered of the 2 x 16 table, in chunks of
    # 2 blocks: reads of 1 chunk, of half (8, ISSUE 39) or of all 16.
    # One slot of 13 + 6 tokens reaches into 3 chunks, two slots into 5
    # at most: the half read
    steps = [e["args"] for e in obs.get_tracer().events("decode_step")
             if e["ph"] == "X"]
    assert steps and {a["table_blocks"] for a in steps} == {32}
    assert {a["attended_blocks"] for a in steps} <= {2, 16, 32}
    assert 16 in {a["attended_blocks"] for a in steps}
    assert eng.health()["read_share_steps"]["1"] == 0.0
    assert eng.health()["attended_share"] == 2 / 32    # nobody seated
    assert stranger.tokens == engine().run([Request(**S)])[0].tokens
    rounds = [e for e in obs.get_tracer().events("round") if e["ph"] == "X"]
    assert rounds and {r["args"]["attn_form"] for r in rounds} == {"rows"}
    # the toy widths of the other suites keep the head-split form
    tiny = build_lm(vocab_size=61, dim=32, num_heads=2, num_layers=1,
                    max_len=64)
    tiny.build(jax.random.PRNGKey(0))
    heads = InferenceEngine(tiny, slots=2)
    assert heads.health()["attn_form"] == "heads"
    assert heads.health()["attended_share"] == 1.0      # the full table
