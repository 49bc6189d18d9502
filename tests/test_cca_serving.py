"""`models/cca_moe.CCAMoELM` (the `zaya` family) and the engine's third
cache kind, "state", on the CPU at tiny widths with seeded random weights:
what tells the model's pieces apart (the partial rotation, a source value
that is not implemented); the slot's state (set by the prefill, rewritten
by the decode step for the seated slots only, left at release for the next
prefill to rewrite whole, scrubbed when a poisoned request is evicted); the spans, the gauge and the `health()` key,
recorded only while the tracer is on; and every option a model with a
"state" entry refuses, by name. The comparison with the plain reference,
logits for logits, is tests/bench/test_cca_moe.py's.
"""

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import cca_moe as fam
from benchmarks.reference import cca_moe as ref
from bigdl_tpu import obs
from bigdl_tpu.models.cca_moe import CCAMoEConfig
from bigdl_tpu.serving import (InferenceEngine, Request, SpeculativeEngine)

TIGHT = 2e-5
BLOCK = 4
ROPE = {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
        "rope_type": "default"}
SOURCE = {
    "layer_types": ["hybrid"] * 4, "kept_layers": [0, 1],
    "num_hidden_layers": 2, "vocab_size": 211, "hidden_size": 32,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 8,
    "cca_time0": 2, "cca_time1": 2, "moe_intermediate_size": 16,
    "num_experts": 16, "num_experts_per_tok": 1, "router_hidden_size": 16,
    "partial_rotary_factor": 0.5, "rope_parameters": {"hybrid": ROPE},
    "rms_norm_eps": 1e-5, "hidden_act": "silu", "attention_bias": False,
    "lm_head_bias": False, "sliding_window": None,
    "tie_word_embeddings": True, "max_position_embeddings": 128,
    "initializer_range": 0.18,
    "router_balance": {"sequences": 4, "positions": 32, "prompt": 8},
    "dtype": {"weights": "float32", "cache": "float32"}}


@pytest.fixture(scope="module")
def lm():
    """(the reference's params, the program's model and variables)."""
    return (fam.reference_params(3, SOURCE), fam.program_model(SOURCE),
            fam.make_variables(3, SOURCE))


def _engine(lm, **kw):
    _, model, variables = lm
    args = dict(slots=3, max_len=64, prefill_buckets=(16, 32),
                block_size=BLOCK, prefix_cache=False)
    args.update(kw)
    return InferenceEngine(model, variables, **args)


def _prompts(lengths, seed=4):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, SOURCE["vocab_size"], n).tolist()
            for n in lengths]


def _states(eng):
    """(layers, slots, state_width): every "state" entry of the pools."""
    return np.stack([np.asarray(entry["s"]) for entry in eng.pool[1::2]])


def test_the_model_is_a_list_of_kinds_with_two_cache_entries_a_layer(lm):
    _, model, _ = lm
    assert model.cfg.layers == ("hybrid", "hybrid")
    assert model.cache_kinds() == ("table", "state") * 2
    pools = model.init_block_pool(9, BLOCK, jnp.bfloat16, slots=3)
    assert [sorted(entry) for entry in pools] == [["k", "v"], ["s"]] * 2
    # rows in the cache's dtype, 2 heads of 8; the state float32 whatever
    # the cache's: z and a of 10 heads of 8 each, and half a value row
    assert pools[0]["k"].shape == (9, BLOCK, 16)
    assert pools[0]["k"].dtype == jnp.bfloat16
    assert pools[1]["s"].shape == (3, 2 * 80 + 8)
    assert pools[1]["s"].dtype == jnp.float32
    assert model.slot_state_bytes() == 2 * 168 * 4
    with pytest.raises(ValueError, match="layers"):
        CCAMoEConfig.from_source(dict(SOURCE, layer_types=["full"] * 2))
    with pytest.raises(ValueError, match="layer_types"):
        CCAMoEConfig.from_source(dict(SOURCE, num_hidden_layers=3))


@pytest.mark.parametrize("key,value", [
    ("cca_time0", 4), ("cca_time1", 3), ("num_experts_per_tok", 2),
    ("tie_word_embeddings", False), ("attention_bias", True),
    ("sliding_window", 4096), ("hidden_act", "gelu")])
def test_a_source_value_that_is_not_implemented_is_refused(key, value):
    with pytest.raises(NotImplementedError, match=key):
        CCAMoEConfig.from_source(dict(SOURCE, **{key: value}))


def test_half_of_a_head_is_rotated_and_keeps_relative_position(lm):
    _, model, _ = lm
    x = jax.random.normal(jax.random.PRNGKey(0), (6, 2, 8))
    rot = model._rope(x, jnp.arange(6) + 3)
    np.testing.assert_array_equal(rot[..., 4:], x[..., 4:])     # they pass
    assert float(jnp.abs(rot[..., :4] - x[..., :4]).max()) > 0.1
    # the pairs are (i, i + 2) of the first four: a pair's length is kept
    np.testing.assert_allclose(rot[..., 0] ** 2 + rot[..., 2] ** 2,
                               x[..., 0] ** 2 + x[..., 2] ** 2, rtol=1e-5)
    # scores depend on the distance only
    q, k = x[:1], x[1:2]
    def score(pq, pk):
        return jnp.sum(model._rope(q, jnp.asarray([pq]))
                       * model._rope(k, jnp.asarray([pk])))
    assert abs(float(score(9, 4) - score(105, 100))) < 1e-5
    assert abs(float(score(9, 4) - score(9, 5))) > 1e-4


def test_the_read_report_counts_rows_seen_and_rows_gathered(lm):
    """`decode_read_report` under `WindowMoELM`'s names: no window layer,
    the seated slots' visible rows in one layer, and what the ragged read
    gathers over both layers by its own roundings."""
    from bigdl_tpu.ops.kv_cache import attended_blocks

    _, model, _ = lm
    pos = np.array([9, 0, 30], np.int32)
    table = np.zeros((3, 16), np.int32)
    table[0, :3], table[2, :8] = [1, 2, 3], np.arange(4, 12)
    got = model.decode_read_report(pos, table, BLOCK)
    assert got["window_rows"] == 0 and got["full_rows"] == 10 + 31
    assert got["attended_rows"] \
        == 2 * BLOCK * attended_blocks(pos, table, BLOCK) >= 2 * 41


def test_the_state_is_the_previous_tokens_and_only_a_seated_slots(lm):
    """After a prompt of n tokens and m decode steps the slot's state is
    that of position n + m - 2 (the last token the model was fed), which a
    prefill of those n + m - 1 tokens alone would also leave; a slot
    nobody sits in keeps zeros through every step."""
    _, model, _ = lm
    (prompt,) = _prompts((9,))
    eng = _engine(lm)
    eng.submit(Request(prompt=prompt, max_new_tokens=6))
    eng.step()
    after_prefill = _states(eng)
    for _ in range(3):
        eng.step()
    got = _states(eng)
    assert not got[:, 1:].any()                     # slots 1, 2: empty
    assert np.abs(got[:, 0]).min() > 0
    assert np.abs(got[:, 0] - after_prefill[:, 0]).max() > 1e-3
    fed = prompt + eng._gen[0][:-1]     # the last token is yet to be fed
    other = _engine(lm, slots=1)
    other.submit(Request(prompt=fed + [0], max_new_tokens=2))
    other._admit()                      # the prefill alone, no decode step
    np.testing.assert_allclose(_states(other)[:, 0], got[:, 0], atol=TIGHT)


def test_a_reused_slot_reads_as_fresh_whatever_the_last_request_left(lm):
    """A sound release launches nothing: the row stays until the next
    prefill rewrites the whole of it (zeros for a prompt of one token),
    and no result reads it meanwhile (a decode step rewrites seated
    slots only). So the reused slot is fed the worst a row can hold."""
    params = lm[0]
    first, second = _prompts((13, 6))
    eng = _engine(lm, slots=1)
    eng.run([Request(prompt=first, max_new_tokens=9)])
    assert _states(eng).any()       # released, and no program ran for it
    assert eng.health()["slot_state_bytes"] == 0
    eng.pool = tuple(
        {"s": jnp.full_like(entry["s"], jnp.nan)} if kind == "state"
        else entry for kind, entry in zip(eng._cache_kinds, eng.pool))
    (one,) = eng.run([Request(prompt=second[:1], max_new_tokens=3)])
    (fresh_one,) = _engine(lm, slots=1).run(
        [Request(prompt=second[:1], max_new_tokens=3)])
    assert one.status == "done" and one.tokens == fresh_one.tokens
    (again,) = eng.run([Request(prompt=second, max_new_tokens=9)])
    (fresh,) = _engine(lm, slots=1).run(
        [Request(prompt=second, max_new_tokens=9)])
    assert again.tokens == fresh.tokens
    with jax.default_matmul_precision("highest"):
        lg = ref.logits(params, jnp.asarray([(second + again.tokens)[:-1]]),
                        SOURCE)[0, 5:]
    gap = jnp.max(lg, -1) - lg[jnp.arange(9), jnp.asarray(again.tokens)]
    assert float(gap.max()) < TIGHT


def test_a_poisoned_slot_is_evicted_alone_and_its_state_is_scrubbed(lm):
    from bigdl_tpu.utils import faults

    prompts = _prompts((12, 9))
    faults.set_plan(faults.FaultPlan("serve_nan@2"))
    try:
        eng = _engine(lm, slots=2)
        for p in prompts:
            eng.submit(Request(prompt=p, max_new_tokens=10))
        done = []
        while not done:
            done = eng.step()
    finally:
        faults.set_plan(None)
    (bad,) = done
    assert bad.status == "poisoned"
    held = _states(eng)
    assert not held[:, 0].any() and np.abs(held[:, 1]).min() > 0
    (good,) = [r for r in eng.run() if r.status == "done"]
    (alone,) = _engine(lm, slots=1).run(
        [Request(prompt=prompts[1], max_new_tokens=10)])
    assert good.tokens == alone.tokens


REFUSED = [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(prefix_cache=True, spill=True), "prefix_cache"),
    (dict(role="prefill"), "role='prefill'"),
    (dict(role="decode"), "role='decode'"),
    (dict(weight_dtype="int8"), "int8"),
    (dict(tp_mesh=True), "tp_mesh"),
    ("speculative", "SpeculativeEngine"),
    ("import_handoff", "state leaves"),
]


def _table_only_lm():
    from bigdl_tpu.models.transformer import build_lm

    model = build_lm(vocab_size=50, dim=16, num_heads=2, num_layers=1,
                     max_len=32)
    return model, model.init(jax.random.PRNGKey(0))


@pytest.mark.parametrize("options,named", REFUSED,
                         ids=[named for _, named in REFUSED])
def test_what_a_model_with_a_state_does_not_serve_is_refused_by_name(
        lm, options, named):
    with pytest.raises(NotImplementedError, match=named) as e:
        if options == "speculative":
            SpeculativeEngine(_engine(lm), _engine(lm), k=2)
        elif options == "import_handoff":
            pf = InferenceEngine(*_table_only_lm(), slots=1, max_len=32,
                                 prefill_buckets=(8,), block_size=BLOCK,
                                 role="prefill")
            pf.submit(Request(prompt=[1, 2, 3, 4, 5], max_new_tokens=2))
            pf.step()
            _engine(lm).import_handoff(pf.take_handoffs()[0])
        else:
            if "tp_mesh" in options:
                options = dict(tp_mesh=jax.sharding.Mesh(
                    np.array(jax.devices()[:1]), ("model",)))
            _engine(lm, **options)
    if options != "import_handoff":
        assert "CCAMoELM does not serve with" in str(e.value)
        assert len(str(e.value).split(": ", 1)[1]) > 20     # and says why


def test_the_spans_the_gauge_and_health_say_what_the_slots_keep(lm):
    model = lm[1]
    eng = _engine(lm)
    # tracer off: no fetch of the model's aux
    with mock.patch.object(jax, "device_get", side_effect=AssertionError):
        eng.run([Request(prompt=p, max_new_tokens=3)
                 for p in _prompts((5, 9))])
    assert eng._aux is None
    obs.set_tracer(obs.SpanTracer(enabled=True))
    try:
        for p in _prompts((7, 11)):
            eng.submit(Request(prompt=p, max_new_tokens=4))
        eng.step()
        assert eng.health()["slot_state_bytes"] \
            == 2 * model.slot_state_bytes() == 2 * 2 * 168 * 4
        gauge = obs.get_registry().gauge(
            "serving_slot_state_bytes", "", labelnames=("engine",)).labels(
                engine=eng.obs_name)
        assert gauge.value == 2 * model.slot_state_bytes()
        eng.run()
        assert gauge.value == 0 == eng.health()["slot_state_bytes"]
        steps = obs.get_tracer().events("decode_step")
        prefills = obs.get_tracer().events("prefill")
    finally:
        obs.set_tracer(None)
    assert steps and all(
        len(e["args"]["experts_touched"]) == 2
        and len(e["args"]["expert_load_max_over_mean"]) == 2
        and len(e["args"]["skipped_rows"]) == 2
        # every row of the batch is routed once a layer, seated or not
        and e["args"]["routed_rows"] == 3 * 2
        and all(t <= 3 for t in e["args"]["experts_touched"])
        for e in steps)
    # the rows that reached an expert: all the routers placed less
    # those they sent to none
    assert all(e["args"]["moe_assignments"] == e["args"]["routed_rows"]
               - sum(e["args"]["skipped_rows"]) for e in steps)
    assert all(e["args"]["moe_assignments"] == e["args"]["bucket"]
               for e in prefills)
    assert eng.stats["decode_traces"] <= 1   # the same program either way
    assert eng.health()["kv_rows_held"] == {"window": 0, "full": 0}
