"""`models/loop_lm.LoopLM`: what is the model's and not the benchmark's.

One set of weights whatever the passes; T x L row sets behind L entries at
the benchmark cell's sizes (as shapes); a prefix HIT (the first list model
that serves one): the second request's suffix prefilled from `start` > 0
over the first's blocks, logits against the plain reference's full forward,
and through the engine warm against cold; the spill tier and
`SpeculativeEngine` over its pools; the handoff roles refused by name.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import loop_lm as fam
from benchmarks.reference import loop_lm as ref
from bigdl_tpu.models.loop_lm import LoopLM, LoopLMConfig
from bigdl_tpu.serving import (EngineRouter, InferenceEngine, Request,
                               SpeculativeEngine)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BLOCK = 4
TIGHT = 5e-5        # tests/bench/test_loop_lm.py says what it is made of


def _json(rel):
    with open(os.path.join(REPO, rel)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def lm():
    cfg = _json("tests/bench/tiny_loop_lm/configs/tiny-loop-lm.json")
    return (cfg, fam.program_model(cfg), fam.make_variables(5, cfg),
            fam.reference_params(5, cfg))


def _engine(lm, **kw):
    args = dict(slots=2, max_len=64, prefill_buckets=(8, 16, 32),
                block_size=BLOCK)
    args.update(kw)
    return InferenceEngine(lm[1], lm[2], **args)


@pytest.mark.parametrize("passes", [1, 2, 4])
def test_the_weights_are_held_once_whatever_the_passes(lm, passes):
    cfg = lm[0]
    model = LoopLM(LoopLMConfig.from_source(dict(cfg, total_ut_steps=passes)))
    shapes = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0)))

    def count(tree):
        return sum(int(np.prod(s.shape))
                   for s in jax.tree_util.tree_leaves(tree))

    one = LoopLM(LoopLMConfig.from_source(dict(cfg, total_ut_steps=1)))
    assert count(shapes) == count(jax.eval_shape(
        lambda: one.init_params(jax.random.PRNGKey(0))))
    # no leaf has an axis of the passes, and there is one set of layers
    assert len(shapes["layers"]) == cfg["num_hidden_layers"]
    assert all(len(s.shape) <= 2 for s in jax.tree_util.tree_leaves(shapes))
    # the cache is `passes` times as deep as the weights
    pools = jax.eval_shape(lambda: model.init_block_pool(9, BLOCK))
    assert len(pools) == cfg["num_hidden_layers"]
    assert pools[0]["k"].shape == (9, passes, BLOCK, 32)
    assert model.cache_entries == passes * cfg["num_hidden_layers"]
    assert model.cache_kinds() == ("table",) * cfg["num_hidden_layers"]


def test_the_cells_pools_are_192_row_sets_behind_48_weight_sets():
    cfg = _json("benchmarks/configs/ouro-2.6b.json")
    e = _json("benchmarks/traffic/shortreason-backlog.json")["engine"]
    model = fam.program_model(cfg)
    pools = jax.eval_shape(lambda: model.init_block_pool(
        e["pool_blocks"], e["block_size"], jnp.bfloat16, slots=e["slots"]))
    assert len(pools) == 48 and model.cache_entries == 192
    assert {leaf.shape for entry in pools for leaf in entry.values()} \
        == {(385, 4, 16, 2048)}
    held = sum(leaf.dtype.itemsize * int(np.prod(leaf.shape))
               for entry in pools for leaf in entry.values())
    assert 9.68e9 < held < 9.70e9
    # a token's rows, all row sets: what the engine's gauge divides by
    assert held // 385 // 16 == 1_572_864
    params = jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), dtype=jnp.bfloat16))
    weights = sum(s.dtype.itemsize * int(np.prod(s.shape))
                  for s in jax.tree_util.tree_leaves(params))
    assert 5.33e9 < weights < 5.35e9


def test_a_suffix_prefilled_over_a_prefix_equals_the_references_forward(lm):
    """Two prompts that share three whole blocks. The first is prefilled
    cold; the second's SUFFIX is prefilled from `start` = 12 into fresh
    blocks, its table naming the first's three blocks before them, and
    then decoded: logits against the reference's full forward over the
    second sequence, which knows no cache."""
    cfg, model, variables, params = lm
    rng = np.random.RandomState(2)
    shared = rng.randint(0, cfg["vocab_size"], 12)
    first = np.concatenate([shared, rng.randint(0, cfg["vocab_size"], 5)])
    second = np.concatenate([shared, rng.randint(0, cfg["vocab_size"], 15)])
    prefill = jax.jit(model.prefill_paged)
    step = jax.jit(model.decode_step_paged)
    pools = model.init_block_pool(20, BLOCK)

    def padded(toks, bucket):
        out = np.zeros((1, bucket), np.int32)
        out[0, :len(toks)] = toks
        return jnp.asarray(out)

    with jax.default_matmul_precision("highest"):
        table = np.zeros((1, 16), np.int32)
        table[0, :8] = np.arange(1, 9)
        pools = prefill(variables, padded(first, 32), pools,
                        jnp.asarray(table), jnp.asarray(table[0, :8]), 0)
        # the second request: blocks 1..3 are the first's, 10..13 its own
        # (a bucket of 16 from position 12), and a prompt of 20 tokens
        table = np.zeros((1, 16), np.int32)
        table[0, :3], table[0, 3:8] = [1, 2, 3], np.arange(10, 15)
        prompt_len = 20
        pools = prefill(variables, padded(second[12:prompt_len], 16), pools,
                        jnp.asarray(table), jnp.asarray(table[0, 3:7]), 12)
        got = []
        for t in range(prompt_len - 1, len(second)):
            lg, pools = step(variables, jnp.asarray(second[t:t + 1]),
                             jnp.asarray([t]), pools, jnp.asarray(table))
            got.append(lg[0])
        want = ref.logits(params, jnp.asarray(second[None]), cfg)[0]
    assert float(jnp.max(jnp.abs(
        jnp.stack(got) - want[prompt_len - 1:]))) < TIGHT


def test_a_warm_prefix_hit_serves_the_cold_tokens(lm):
    shared = list(range(3, 16))             # 13 tokens: 3 whole blocks
    reqs = [Request(prompt=shared + tail, max_new_tokens=5,
                    temperature=0.8, seed=9)
            for tail in ([40, 41], [50], [60, 61, 62])]
    cold = _engine(lm, prefix_cache=False).run(
        [Request(**r.__dict__) for r in reqs])
    eng = _engine(lm, prefix_cache=True)
    warm = [eng.run([Request(**r.__dict__)])[0] for r in reqs]
    assert eng.stats["prefix_hits"] == 2
    assert eng.stats["prefix_tokens_saved"] == 24
    # a token saved is its rows in every pass of every layer
    assert eng.stats["prefix_bytes_saved"] == 24 * 12 * 2 * 32 * 4
    assert [w.tokens for w in warm] == [c.tokens for c in cold]
    assert eng.stats["decode_traces"] <= 1
    assert eng.stats["prefill_traces"] <= 3


def test_spill_and_scrub_on_pools_that_hold_every_pass(lm):
    """What indexes a pool by block works on leaves with the passes
    inside a block: spill under pool pressure, re-admit, the same tokens;
    a scrubbed block is zero in every pass."""
    P = Request(prompt=list(range(5, 18)), max_new_tokens=4)
    F = Request(prompt=list(range(30, 49)), max_new_tokens=4)
    want = _engine(lm, prefix_cache=False).run([Request(**P.__dict__)])[0]
    eng = _engine(lm, slots=1, max_len=24, pool_blocks=7, spill=True,
                  prefix_cache=True, host_blocks=8,
                  prefill_buckets=(8, 16, 24))
    first = eng.run([Request(**P.__dict__)])[0]
    eng.run([Request(**F.__dict__)])
    assert eng.stats["kv_spill_blocks"] >= 1
    again = eng.run([Request(**P.__dict__)])[0]
    assert eng.stats["kv_readmit_blocks"] >= 1
    assert again.tokens == first.tokens == want.tokens
    assert np.asarray(eng.pool[1]["k"][1:3]).any()
    eng._scrub_blocks([1, 2])
    assert not np.asarray(eng.pool[1]["k"][1:3]).any()
    assert eng.pool[1]["k"][1:3].shape == (2, 4, BLOCK, 32)


def test_speculation_over_a_looped_target_serves_the_targets_tokens(lm):
    reqs = [Request(prompt=list(range(7, 7 + n)), max_new_tokens=m,
                    temperature=t, seed=3)
            for n, m, t in ((9, 8, 0.0), (3, 6, 0.7))]
    alone = _engine(lm, prefix_cache=False).run(
        [Request(**r.__dict__) for r in reqs])
    spec = SpeculativeEngine(_engine(lm, prefix_cache=False),
                             _engine(lm, prefix_cache=False), k=2)
    got = spec.run([Request(**r.__dict__) for r in reqs])
    assert [g.tokens for g in got] == [a.tokens for a in alone]


def test_the_handoff_roles_are_refused_by_name(lm):
    for role in ("prefill", "decode"):
        with pytest.raises(NotImplementedError, match="second axis") as e:
            _engine(lm, role=role)
        assert f"LoopLM does not serve with role='{role}'" in str(e.value)
    with pytest.raises(NotImplementedError, match="LoopLM does not serve"):
        EngineRouter([_engine(lm)],
                     prefill_engines=[_engine(lm, role="prefill")])


def test_the_longest_request_asks_for_no_block_past_its_positions(lm):
    """A pool of exactly slots x ceil((prompt + answer) / block) blocks
    and the scratch one, every seat taken by the longest request (the
    benchmark cell's 385 = 16 x 24 + 1 in small): each ends `done` at its
    full count, none waits for a block, nothing is evicted."""
    prompt, answer, slots = 16, 16, 4
    blocks = -(-(prompt + answer) // BLOCK)
    eng = _engine(lm, slots=slots, max_len=2 * blocks * BLOCK,
                  prefill_buckets=(16,), pool_blocks=slots * blocks + 1,
                  prefix_cache=False)
    done = eng.run([Request(prompt=list(range(1, prompt + 1)),
                            max_new_tokens=answer) for _ in range(9)])
    assert {(r.status, r.finish_reason, len(r.tokens)) for r in done} \
        == {("done", "max_tokens", answer)}
    assert eng.stats["pool_evictions"] == 0
    assert eng.stats["admit_requeue_exhausted"] == 0
