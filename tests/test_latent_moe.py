"""`models/latent_moe.LatentMoELM` and its two mechanisms on the CPU:
the dropless sigmoid-routed expert layer against a dense oracle, the
latent (MLA) cache row through the paged-pool primitives, the absorbed
decode attention against the naive form, and the serving engine's
whole surface over a pool whose leaves are not called 'k' and 'v'
(prefix hit, spill, handoff, migration, scrub, refusals). The model
against its plain reference, logits for logits, is
tests/bench/test_mla_moe.py.
"""

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import obs
from bigdl_tpu.models.latent_moe import (LatentMoEConfig, LatentMoELM,
                                         rope_interleaved)
from bigdl_tpu.ops.kv_cache import (block_attention, gather_block_rows,
                                    init_row_pool,
                                    latent_paged_attention,
                                    write_decode_rows, write_prompt_rows)
from bigdl_tpu.parallel.moe import DroplessMoE
from bigdl_tpu.serving import (EngineRouter, InferenceEngine, Request,
                               SpeculativeEngine)

CFG = LatentMoEConfig(
    layers=("dense", "moe", "moe"), vocab_size=97, hidden_size=32,
    num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
    intermediate_size=64, moe_intermediate_size=16, n_routed_experts=8,
    num_experts_per_tok=2, routed_scaling_factor=2.5, rope_theta=1e4,
    max_position_embeddings=64)


@pytest.fixture(scope="module")
def lm():
    model = LatentMoELM(CFG)
    return model, model.init(jax.random.PRNGKey(0))


def _engine(lm, **kw):
    model, variables = lm
    args = dict(slots=2, max_len=32, prefill_buckets=(8, 16),
                block_size=4)
    args.update(kw)
    return InferenceEngine(model, variables, **args)


def _reqs():
    rng = np.random.RandomState(4)
    return [Request(prompt=rng.randint(0, 97, n).tolist(),
                    max_new_tokens=4, temperature=0.7, seed=n)
            for n in (5, 13, 9, 11)]


# ------------------------------------------------------------ the experts

@pytest.mark.parametrize("tokens", [1, 5, 64])
def test_dropless_moe_equals_a_dense_sum_over_the_chosen(tokens):
    """Every token gets exactly its top-k experts by s + b, weighted
    by scale * s / sum(s), plus the shared expert: none dropped,
    whatever the batch (one expert may take every token)."""
    moe = DroplessMoE(16, 8, 8, 2, shared_hidden=8, scale=2.5)
    p = moe.init(jax.random.PRNGKey(0))["params"]
    p["router_bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(2), (8,))
    x = jax.random.normal(jax.random.PRNGKey(1), (tokens, 16))
    y, counts = moe.forward(p, x)
    assert int(counts.sum()) == 2 * tokens

    def ffn(v, g, u, d):
        return (jax.nn.silu(v @ g) * (v @ u)) @ d

    s = np.asarray(jax.nn.sigmoid(x @ p["router"]))
    chosen = np.argsort(-(s + np.asarray(p["router_bias"])), -1)[:, :2]
    want = np.zeros((tokens, 16), np.float32)
    for t in range(tokens):
        w = 2.5 * s[t, chosen[t]] / s[t, chosen[t]].sum()
        for wi, e in zip(w, chosen[t]):
            want[t] += wi * np.asarray(ffn(x[t], p["w_gate"][e],
                                           p["w_up"][e], p["w_down"][e]))
        want[t] += np.asarray(ffn(x[t], p["ws_gate"], p["ws_up"],
                                  p["ws_down"]))
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-6)
    assert np.bincount(chosen.ravel(), minlength=8).tolist() == \
        counts.tolist()
    # the bias selects and does not weigh: a bias that picks the same
    # experts leaves the output where it was
    same, _ = moe.forward(dict(p, router_bias=p["router_bias"] + 7.0), x)
    np.testing.assert_allclose(np.asarray(same), np.asarray(y), atol=1e-6)


def test_dropless_moe_module_surface():
    moe = DroplessMoE(16, 8, 4, 4)         # top-k = every expert, no shared
    v = moe.init(jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 3, 16))
    y, _ = moe.apply(v, x)
    assert y.shape == x.shape and bool(jnp.all(jnp.isfinite(y)))
    with pytest.raises(ValueError, match="top_k"):
        DroplessMoE(16, 8, 4, 5)


# ------------------------------------------------- the row and its pool

@pytest.mark.parametrize("width", [20, 128, 640])
def test_rows_of_any_width_round_trip_bitwise(width):
    rng = np.random.RandomState(width)
    block, nb, slots = 4, 3, 2
    pool = init_row_pool(1 + slots * nb, block, width)
    assert pool.shape == (1 + slots * nb, block, width)
    table = np.arange(1, 1 + slots * nb, dtype=np.int32).reshape(slots, nb)
    want = rng.randn(slots, nb * block, width).astype(np.float32)
    s = nb * block - 2                      # a ragged last block
    for b in range(slots):
        pool = write_prompt_rows(pool, jnp.asarray(want[b, :s]),
                                 jnp.asarray(table[b]))
    for t in (s, s + 1):                    # then a token at a time
        pool = write_decode_rows(
            pool, jnp.asarray(want[:, t]),
            jnp.asarray(table[:, t // block]),
            jnp.full((slots,), t % block, jnp.int32))
    got = gather_block_rows(pool, jnp.asarray(table))
    np.testing.assert_array_equal(np.asarray(got), want)
    with pytest.raises(ValueError, match="cannot hold"):
        write_prompt_rows(pool, jnp.zeros((13, width)),
                          jnp.asarray(table[0]))


def test_rope_rotates_the_pairs_and_keeps_relative_position():
    x = jax.random.normal(jax.random.PRNGKey(0), (6, 3, 8))
    pos = jnp.arange(6)
    y = rope_interleaved(x, pos, 1e4)
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(x[0]),
                               atol=1e-6)           # position 0: identity
    np.testing.assert_allclose(            # a rotation: pair norms kept
        np.asarray(y[..., 0::2] ** 2 + y[..., 1::2] ** 2),
        np.asarray(x[..., 0::2] ** 2 + x[..., 1::2] ** 2), rtol=1e-5)
    # q_i . k_j depends on i - j only
    q = rope_interleaved(jnp.broadcast_to(x[:1], x.shape), pos, 1e4)
    k = rope_interleaved(jnp.broadcast_to(x[1:2], x.shape), pos, 1e4)
    dots = np.asarray(jnp.einsum("ihd,jhd->ijh", q, k))
    np.testing.assert_allclose(dots[3, 1], dots[5, 3], atol=1e-5)
    np.testing.assert_allclose(dots[2, 2], dots[4, 4], atol=1e-5)


def test_absorbed_decode_attention_equals_the_naive_form():
    """`[q_nope W_UK^T ; q_rope] . [c_kv ; k_rope]` then W_UV on the
    output, against per-head keys and values expanded from every row."""
    rank, rope, nope, vd, heads = 16, 4, 8, 8, 3
    b, block, nb = 2, 4, 3
    keys = jax.random.split(jax.random.PRNGKey(7), 5)
    w_ukv = jax.random.normal(keys[0], (rank, heads, nope + vd)) * 0.3
    q_nope = jax.random.normal(keys[1], (b, heads, nope))
    q_rope = jax.random.normal(keys[2], (b, heads, rope))
    rows = jax.random.normal(keys[3], (b, nb * block, rank + rope))
    pad = jnp.zeros((b, nb * block, 12))    # a 32-wide pool row
    pool = jnp.concatenate([
        jnp.full((1, block, 32), jnp.nan),  # scratch holds garbage
        jnp.concatenate([rows, pad], -1).reshape(b * nb, block, 32)])
    table = jnp.arange(1, 1 + b * nb).reshape(b, nb)
    pos = jnp.asarray([5, 10])
    scale = (nope + rope) ** -0.5
    q_lat = jnp.einsum("bhn,chn->bhc", q_nope, w_ukv[..., :nope])
    o_lat = latent_paged_attention(q_lat, q_rope, pool, table, pos, rank,
                                   scale)
    got = jnp.einsum("bhc,chv->bhv", o_lat, w_ukv[..., nope:])
    kv = jnp.einsum("bsc,chd->bhsd", rows[..., :rank], w_ukv)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        rows[:, None, :, rank:], (b, heads, nb * block, rope))], -1)
    q = jnp.concatenate([q_nope, q_rope], -1)[:, :, None, :]
    visible = jnp.arange(nb * block)[None, :] <= pos[:, None]
    want = block_attention(q, k, kv[..., nope:], visible[:, None, :],
                           visible, scale)[:, :, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6)
    # rows beyond the clock never reach the output, garbage or not
    poisoned = pool.at[2, 3].set(jnp.nan)   # slot 0, position 7 > 5
    again = latent_paged_attention(q_lat, q_rope, poisoned, table, pos,
                                   rank, scale)
    np.testing.assert_array_equal(np.asarray(again[0]),
                                  np.asarray(o_lat[0]))


def _latent_case(pos, nb=20, block=4, rank=16, rope=4, width=32, heads=3,
                 seed=0, beyond=None, unowned=0.0):
    """Dense latent rows (b, nb*block, rank+rope) and the same laid into
    a `width`-lane pool through shuffled chains; the table points at the
    scratch block beyond a slot's last live block, and a clock of -1 is
    a row that is not seated (table all scratch, handed clock 0).
    Returns (latent_paged_attention's arguments, the float32 NumPy
    reference (b, heads, rank): zeros for a row that is not seated)."""
    rng = np.random.RandomState(seed)
    pos = np.asarray(pos)
    b = len(pos)
    q_lat = rng.randn(b, heads, rank).astype(np.float32)
    q_rope = rng.randn(b, heads, rope).astype(np.float32)
    dense = rng.randn(b, nb * block, rank + rope).astype(np.float32)
    scale = np.float32((8 + rope) ** -0.5)
    want = np.zeros((b, heads, rank), np.float32)
    q = np.concatenate([q_lat, q_rope], -1)
    for slot in np.flatnonzero(pos >= 0):
        rows = dense[slot, :pos[slot] + 1]
        for head in range(heads):
            sc = (rows @ q[slot, head]) * scale
            p = np.exp(sc - sc.max())
            want[slot, head] = (p / p.sum()) @ rows[:, :rank]
    if beyond is not None:
        for slot in range(b):
            dense[slot, max(pos[slot], 0) + 1:] = beyond
    pool = np.full((1 + b * nb, block, width), unowned, np.float32)
    table = rng.permutation(np.arange(1, 1 + b * nb)).reshape(b, nb)
    for slot in range(b):
        live = pos[slot] // block + 1 if pos[slot] >= 0 else 0
        for j in range(live):
            pool[table[slot, j], :, :rank + rope] = \
                dense[slot, j * block:(j + 1) * block]
            pool[table[slot, j], :, rank + rope:] = 0.0
        table[slot, live:] = 0
    args = (jnp.asarray(q_lat), jnp.asarray(q_rope), jnp.asarray(pool),
            jnp.asarray(table, jnp.int32),
            jnp.asarray(np.maximum(pos, 0), jnp.int32), rank, scale)
    return args, want


# a table of 20 blocks of 4 rows is read in chunks of 3 blocks = 12 rows
@pytest.mark.parametrize("pos", [
    [0, 11, 12, 23, 24, 79],            # chunk edges, a full table
    [5, -1, 40, -1, -1, 79],            # unseated rows between seated ones
    [-1, -1, -1, -1, -1, 7],
    [35, 2, 70, 13, 47, 60],
], ids=["chunk-edges", "unseated-between", "one-seated", "mixed"])
def test_the_latent_read_is_ragged_and_equals_a_plain_reference(pos):
    """`latent_paged_attention` reads each slot's own live chunks (the
    core it shares with `paged_attention_rows`): against float32 NumPy
    attention over each slot's rows [0, pos]; NaN in the scratch block,
    in every block no visible row lives in and after the clock inside
    the last live chunk changes no bit."""
    args, want = _latent_case(pos)
    got = np.asarray(latent_paged_attention(*args))
    assert got.shape == want.shape and got.dtype == np.float32
    np.testing.assert_allclose(got, want, rtol=1e-5,
                               atol=1e-5 * np.abs(want).max())
    assert not got[np.asarray(pos) < 0].any()
    dirty, _ = _latent_case(pos, beyond=np.nan, unowned=np.nan)
    assert np.isnan(np.asarray(dirty[2][0])).all()
    np.testing.assert_array_equal(
        np.asarray(latent_paged_attention(*dirty)), got)


def test_a_latent_slots_result_does_not_hang_on_the_other_slots_clocks():
    """Slot 2 beside neighbours whose clocks make the batch's live
    chunks 3, 13, 18, 23 and 38 of 42: the reads compiled for 3, for
    half (21, twice) and for all (twice); the same bits in each."""
    results = []
    for others in (-1, 13, 30, 40, 79):
        pos = [others] * 6
        pos[2] = 30
        args, want = _latent_case(pos)
        got = np.asarray(latent_paged_attention(*args))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        results.append(got[2])
    for other in results[1:]:
        np.testing.assert_array_equal(other, results[0])


# ------------------------------------------------- the model as a config

def test_the_model_is_a_list_of_layer_kinds():
    source = dict(
        vocab_size=97, hidden_size=32, num_attention_heads=4,
        q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=4, v_head_dim=8, intermediate_size=64,
        moe_intermediate_size=16, n_routed_experts=8,
        num_experts_per_tok=2, n_shared_experts=1,
        routed_scaling_factor=2.5, rope_theta=1e4,
        max_position_embeddings=64, first_k_dense_replace=2,
        num_hidden_layers=5, num_nextn_predict_layers=0)
    cfg = LatentMoEConfig.from_source(source)
    assert cfg.layers == ("dense", "dense", "moe", "moe", "moe")
    assert cfg.max_len == 64 and cfg.routed_scaling_factor == 2.5
    with pytest.raises(NotImplementedError, match="multi-token"):
        LatentMoEConfig.from_source(dict(source,
                                         num_nextn_predict_layers=1))
    with pytest.raises(NotImplementedError, match="rope_scaling"):
        LatentMoEConfig.from_source(dict(source,
                                         rope_scaling={"factor": 4}))
    with pytest.raises(ValueError, match="layers"):
        LatentMoEConfig(**dict(CFG.__dict__, layers=("dense", "ssm")))
    model = LatentMoELM(CFG)
    pools = model.init_block_pool(9, 4, jnp.bfloat16)
    assert len(pools) == 3 and list(pools[0]) == ["kv"]
    assert pools[0]["kv"].shape == (9, 4, 128)   # 20 -> whole lanes
    assert pools[0]["kv"].dtype == jnp.bfloat16


def test_the_engine_makes_no_second_copy_of_the_weights(lm):
    eng = _engine(lm)
    mine = jax.tree_util.tree_leaves(eng._params)
    given = jax.tree_util.tree_leaves(lm[1]["params"])
    assert len(mine) == len(given)
    assert all(a is b for a, b in zip(mine, given))


# ------------------------------------------ the engine's surface, whole

def test_a_warm_prefix_hit_serves_the_cold_tokens(lm):
    shared = list(range(3, 16))             # 13 tokens: 3 whole blocks
    reqs = [Request(prompt=shared + tail, max_new_tokens=5,
                    temperature=0.8, seed=9)
            for tail in ([40, 41], [50], [60, 61, 62])]
    cold = _engine(lm, prefix_cache=False).run(
        [Request(**r.__dict__) for r in reqs])
    eng = _engine(lm)
    warm = [eng.run([Request(**r.__dict__)])[0] for r in reqs]
    assert eng.stats["prefix_hits"] == 2
    assert eng.stats["prefix_tokens_saved"] == 24
    assert [w.tokens for w in warm] == [c.tokens for c in cold]
    # 2 buckets + 1 decode: the engine's compile contract, unchanged
    assert eng.stats["decode_traces"] <= 1
    assert eng.stats["prefill_traces"] <= 2


def test_spill_handoff_migration_and_scrub_on_a_latent_pool(lm):
    """Every holder of a pool that indexes it by block works on leaves
    called 'kv': nothing reads `pool[0]["k"]`."""
    want = _engine(lm).run(_reqs())
    # disaggregated prefill: export, route, import
    pf, de = _engine(lm, role="prefill"), _engine(lm)
    router = EngineRouter([de], prefill_engines=[pf], handoff_len=7)
    got = router.run(_reqs())
    assert [g.tokens for g in got] == [w.tokens for w in want]
    assert de.stats["handoffs_in"] == 3 and pf.stats["handoffs_out"] == 3
    # a package of another geometry is refused by shape, not by a KeyError
    pf.submit(_reqs()[1])
    pf.step()
    (pkg,) = pf.take_handoffs()
    assert list(pkg.kv[0]) == ["kv"]
    with pytest.raises(ValueError, match="block_size"):
        _engine(lm, block_size=8).import_handoff(pkg)
    with pytest.raises(ValueError, match="cache_dtype"):
        _engine(lm, cache_dtype=jnp.bfloat16).import_handoff(pkg)
    # spill under pool pressure, re-admit, same tokens
    P, F = _reqs()[1], _reqs()[3]
    eng = _engine(lm, slots=1, max_len=20, pool_blocks=6, spill=True,
                  host_blocks=8)
    first = eng.run([Request(**P.__dict__)])[0]
    eng.run([Request(**F.__dict__)])
    assert eng.stats["kv_spill_blocks"] >= 1
    again = eng.run([Request(**P.__dict__)])[0]
    assert eng.stats["kv_readmit_blocks"] >= 1
    assert again.tokens == first.tokens == want[1].tokens
    # migration: the tree's blocks into a survivor's host tier
    entries = eng.export_tree()
    assert entries and list(entries[0]["kv"][0]) == ["kv"]
    survivor = _engine(lm, spill=True, host_blocks=8)
    assert survivor.import_tree(entries) == len(entries)
    with pytest.raises(ValueError, match="same-layout"):
        _engine(lm, spill=True, host_blocks=8,
                cache_dtype=jnp.bfloat16).import_tree(entries)
    # scrub: zeroes whatever the leaves are called
    eng._scrub_blocks([1, 2])
    assert not np.asarray(eng.pool[1]["kv"][1:3]).any()


def test_what_the_model_does_not_serve_is_refused_by_name(lm):
    model, variables = lm
    with pytest.raises(NotImplementedError, match="int8"):
        _engine(lm, weight_dtype="int8")
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("model",))
    with pytest.raises(NotImplementedError, match="tp_mesh"):
        _engine(lm, tp_mesh=mesh)
    with pytest.raises(NotImplementedError, match="SpeculativeEngine"):
        SpeculativeEngine(_engine(lm), _engine(lm), k=2)


def test_aux_is_fetched_only_while_the_tracer_records(lm):
    eng = _engine(lm)
    # tracer off: no fetch of the model's aux
    with mock.patch.object(jax, "device_get", side_effect=AssertionError):
        eng.run(_reqs()[:2])
    assert eng._aux is None
    obs.set_tracer(obs.SpanTracer(enabled=True))
    try:
        eng.run(_reqs()[2:])
        steps = obs.get_tracer().events("decode_step")
        prefills = obs.get_tracer().events("prefill")
    finally:
        obs.set_tracer(None)    # set_tracer returns the NEW tracer
    assert steps and all(
        len(e["args"]["experts_touched"]) == 2
        and len(e["args"]["expert_load_max_over_mean"]) == 2
        and e["args"]["cached_tokens"] >= e["args"]["active"]
        for e in steps)
    # 2 slots x 2 experts a token x 2 expert layers, every step
    assert all(e["args"]["moe_assignments"] == 8 for e in steps)
    assert all(e["args"]["moe_assignments"] == 2 * e["args"]["bucket"]
               for e in prefills)
    assert eng.stats["decode_traces"] <= 1   # the same program either way
