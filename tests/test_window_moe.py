"""`models/window_moe.WindowMoELM` (the `afmoe` family) on the CPU at tiny
widths, seeded random weights: the full forward and prefill + decode
THROUGH `InferenceEngine` against the plain reference
(benchmarks/reference/afmoe.py, which imports nothing of the program),
logits for logits; what tells a sliding layer from a full one; the
ring a sliding layer keeps (never more than window + one block a slot,
rewritten whole by the next occupant's prefill); the spans and the
gauge; and every option the model refuses, by name.

TIGHT = 2e-5 on logits of order 1: both sides compute in float32 on the
same bfloat16-valued weights, so what is left between them is the
order of summation (measured 1e-6 or less); the smallest term that a
test below leaves out moves a logit by a thousand times that.
"""

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import afmoe as fam
from benchmarks.reference import afmoe as ref
from bigdl_tpu import obs
from bigdl_tpu.models.window_moe import WindowMoEConfig, rope_half_split
from bigdl_tpu.serving import (EngineRouter, InferenceEngine, Request,
                               SpeculativeEngine)

TIGHT = 2e-5
WINDOW, BLOCK = 8, 4
RING_ROWS = WINDOW + BLOCK              # window + one block
SOURCE = {
    "layer_types": ["sliding_attention", "sliding_attention",
                    "sliding_attention", "full_attention"] * 2,
    "kept_layers": [0, 4, 5, 6, 7], "num_hidden_layers": 5,
    "num_dense_layers": 1, "vocab_size": 211, "hidden_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "sliding_window": WINDOW, "intermediate_size": 64,
    "moe_intermediate_size": 16, "num_experts": 8,
    "num_experts_per_tok": 2, "num_shared_experts": 1,
    "route_scale": 2.826, "route_norm": True, "mup_enabled": True,
    "score_func": "sigmoid", "hidden_act": "silu", "rope_theta": 10000,
    "rms_norm_eps": 1e-5, "rope_scaling": None,
    "tie_word_embeddings": False, "max_position_embeddings": 128,
    "expert_bias_std": 0.1, "dtype": {"weights": "float32",
                                      "cache": "float32"}}


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


def _reference_logits(params, seq, cfg=SOURCE):
    with jax.default_matmul_precision("highest"):
        return ref.logits(params, jnp.asarray([seq]), cfg)[0]


# ------------------------------------------------------------ the model

def test_the_model_is_a_list_of_attention_and_ffn_kinds(lm):
    _, model, variables = lm
    assert model.cfg.layers == (
        ("sliding_attention", "dense"), ("sliding_attention", "moe"),
        ("sliding_attention", "moe"), ("sliding_attention", "moe"),
        ("full_attention", "moe"))
    assert model.cache_kinds() == ("ring",) * 4 + ("table",)
    assert model.ring_blocks(BLOCK) * BLOCK == RING_ROWS
    lp = variables["params"]["layers"]
    assert "w_gate" in lp[0] and "moe" in lp[1] and "moe" not in lp[0]
    with pytest.raises(ValueError, match="layers"):
        WindowMoEConfig.from_source(dict(SOURCE, layer_types=["local"] * 5))
    with pytest.raises(ValueError, match="layer_types"):
        WindowMoEConfig.from_source(SOURCE)     # 8 types, 5 layers


@pytest.mark.parametrize("key,value", [
    ("rope_scaling", {"type": "yarn", "factor": 4.0}), ("n_group", 2),
    ("topk_group", 2), ("num_expert_groups", 4), ("num_limited_groups", 2),
    ("score_func", "softmax"), ("hidden_act", "gelu"),
    ("tie_word_embeddings", True)])
def test_a_source_value_that_is_not_implemented_is_refused(key, value):
    cfg = dict(SOURCE, layer_types=SOURCE["layer_types"][:5], **{key: value})
    with pytest.raises(NotImplementedError, match=key):
        WindowMoEConfig.from_source(cfg)


def test_rope_rotates_half_split_pairs_and_keeps_relative_position():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 1, 8))
    at3 = np.asarray(rope_half_split(x, jnp.asarray([3]), 1e4))[0, 0]
    a, b = np.asarray(x)[0, 0, :4], np.asarray(x)[0, 0, 4:]
    ang = 3 * 1e4 ** (-np.arange(0, 8, 2) / 8)
    np.testing.assert_allclose(at3[:4], a * np.cos(ang) - b * np.sin(ang),
                               atol=1e-6)
    np.testing.assert_allclose(at3[4:], a * np.sin(ang) + b * np.cos(ang),
                               atol=1e-6)
    q, k = jax.random.normal(jax.random.PRNGKey(1), (2, 1, 1, 8))
    dots = [float(jnp.sum(rope_half_split(q, jnp.asarray([i]), 1e4)
                          * rope_half_split(k, jnp.asarray([j]), 1e4)))
            for i, j in ((5, 2), (40, 37))]
    assert abs(dots[0] - dots[1]) < 1e-5


def test_full_forward_equals_the_reference(lm):
    params, model, variables = lm
    toks = np.random.RandomState(0).randint(0, 211, (2, 40))
    with jax.default_matmul_precision("highest"):
        got, _ = model.apply(variables, jnp.asarray(toks))
    for b in range(2):
        want = _reference_logits(params, toks[b].tolist())
        assert float(jnp.max(jnp.abs(got[b] - want))) < TIGHT


def _one_layer(kind, **changes):
    cfg = dict(SOURCE, layer_types=[kind], kept_layers=[0],
               num_hidden_layers=1, num_dense_layers=0, **changes)
    return fam.program_model(cfg), fam.make_variables(3, cfg)


def test_a_window_masks_exactly_the_keys_it_does_not_reach():
    """One sliding layer against the same layer with a window wider than
    the sequence (everything else, RoPE included, the same): identical
    logits at every position i < window, where no key has
    i - j >= window, different at every later one."""
    toks = jnp.asarray(np.random.RandomState(1).randint(0, 211, (1, 24)))
    narrow, nv = _one_layer("sliding_attention")
    wide, wv = _one_layer("sliding_attention", sliding_window=1000)
    with jax.default_matmul_precision("highest"):
        a, b = narrow.apply(nv, toks)[0][0], wide.apply(wv, toks)[0][0]
    gap = np.asarray(jnp.max(jnp.abs(a - b), -1))
    assert (gap[:WINDOW] < TIGHT).all() and (gap[WINDOW:] > 1e-3).all()


def test_a_full_layer_is_blind_to_position_and_a_sliding_one_is_not():
    """No rotation on a full layer: the last position's logits hang on
    the SET of earlier tokens, not on their order. A sliding layer's
    (window wider than the sequence, so the same set) change."""
    rng = np.random.RandomState(2)
    toks = rng.randint(0, 211, (1, 24))
    mixed = toks.copy()
    mixed[0, :-1] = toks[0, :-1][rng.permutation(23)]
    for kind, blind in (("full_attention", True),
                        ("sliding_attention", False)):
        model, variables = _one_layer(kind, sliding_window=1000)
        with jax.default_matmul_precision("highest"):
            a = model.apply(variables, jnp.asarray(toks))[0][0, -1]
            b = model.apply(variables, jnp.asarray(mixed))[0][0, -1]
        gap = float(jnp.max(jnp.abs(a - b)))
        assert (gap < TIGHT) if blind else (gap > 1e-3), (kind, gap)


# ------------------------------------------------- through the engine

def _prompts(lengths, seed=4):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, 211, n).tolist() for n in lengths]


def test_prefill_then_decode_through_the_engine_equals_the_reference(lm):
    """Greedy through `EngineRouter([InferenceEngine])`, continuous
    batching over 3 slots: every served token is the reference's best at
    its position to within TIGHT (the reference's full forward over
    prompt + answer; logits, not tokens, are what is compared). A prompt
    of 30 is longer than the window of 8; 30 answer tokens on top carry
    every context past the window by more than one wrap of the 12-row
    ring."""
    params = lm[0]
    prompts = _prompts((5, 13, 30, 16, 9, 27))
    eng = _engine(lm)
    results = EngineRouter([eng]).run(
        [Request(prompt=p, max_new_tokens=30) for p in prompts])
    assert [r.status for r in results] == ["done"] * len(prompts)
    assert eng.stats["prefill_traces"] <= 2 and eng.stats["decode_traces"] <= 1
    for p, r in zip(prompts, results):
        assert len(r.tokens) == 30
        lg = _reference_logits(params, (p + r.tokens)[:-1])[len(p) - 1:]
        gap = jnp.max(lg, -1) - lg[jnp.arange(30), jnp.asarray(r.tokens)]
        assert float(gap.max()) < TIGHT


def test_sampled_requests_finish_and_replay_bit_for_bit(lm):
    """Sampling, a stop id and a deadline work as for the other models:
    the engine's, not the model's."""
    def reqs():
        return [Request(prompt=p, max_new_tokens=12, temperature=0.8,
                        top_k=40, top_p=0.95, seed=i)
                for i, p in enumerate(_prompts((7, 21, 12, 30)))]

    first, again = _engine(lm).run(reqs()), _engine(lm, slots=2).run(reqs())
    assert [r.status for r in first] == ["done"] * 4
    assert [r.tokens for r in first] == [r.tokens for r in again]
    clock = iter(np.arange(0, 1000, 0.5))
    timed = _engine(lm, clock=lambda: float(next(clock)))
    (late,) = timed.run([Request(prompt=_prompts((9,))[0], max_new_tokens=30,
                                 deadline_s=4.0)])
    assert late.status == "expired" and 0 < len(late.tokens) < 30


def test_a_ring_never_holds_more_than_the_window_and_one_block(lm):
    """A slot decodes 3 x window tokens and more: the sliding layers'
    leaves are `1 + slots * 3` blocks whatever the pool's size, and the
    rows a sliding layer holds and reads stay at or under window + one
    block a slot, while the full layer's grow."""
    eng = _engine(lm, pool_blocks=60)
    shapes = [layer["k"].shape for layer in eng.pool]
    assert shapes == [(1 + 3 * 3, BLOCK, 16)] * 4 + [(60, BLOCK, 16)]
    obs.set_tracer(obs.SpanTracer(enabled=True))
    try:
        for p in _prompts((6, 30, 14)):
            eng.submit(Request(prompt=p, max_new_tokens=4 * WINDOW))
        held = []
        while not eng.idle:
            eng.step()
            held.append((eng.slots_active, eng.health()["kv_rows_held"]))
        steps = [e["args"] for e in obs.get_tracer().events("decode_step")]
    finally:
        obs.set_tracer(None)
    assert all(h["window"] <= n * RING_ROWS for n, h in held)
    assert max(h["window"] for _, h in held) == 3 * RING_ROWS
    assert max(h["full"] for _, h in held) > 3 * 40     # they grow
    for a in steps:
        assert a["window_rows"] <= a["active"] * WINDOW
        assert a["window_rows"] <= a["full_rows"] == a["cached_tokens"]
        # 4 sliding layers read at most their rings (whole chunks of
        # them), the full layer at most its table
        assert 4 * a["window_rows"] + a["full_rows"] <= a["attended_rows"] \
            <= BLOCK * (4 * a["active"] * 3 + a["table_blocks"])
    last = steps[-1]                # every slot long past its window
    assert last["window_rows"] == last["active"] * WINDOW \
        < last["full_rows"]
    text = obs.get_registry().render_prometheus()
    assert 'serving_kv_rows_held{engine="%s",kind="window"}' % eng.obs_name \
        in text


def test_the_next_occupants_prefill_rewrites_the_whole_ring(lm):
    """Nothing releases a ring; the next prefill writes every block of
    the slot's region, zeros where its prompt has no rows: no row of the
    finished occupant is left when the next one starts."""
    eng = _engine(lm, slots=1)
    eng.run([Request(prompt=_prompts((30,))[0], max_new_tokens=20)])
    ring = np.asarray(eng.pool[1]["k"][1:])         # the slot's 3 blocks
    assert (np.abs(ring).sum(-1) > 0).all()         # every row written
    eng.submit(Request(prompt=_prompts((5,))[0], max_new_tokens=8))
    eng.step()                          # prefill 5 rows, decode row 4
    for layer in eng.pool[:4]:
        for leaf in layer.values():
            rows = np.abs(np.asarray(leaf[1:])).sum(-1).reshape(-1)
            # rows 0-4 the prompt's, rows 5-7 the padded bucket's (beyond
            # the clock), the other two blocks zeros
            assert (rows[:5] > 0).all() and not rows[8:].any()


def test_a_poisoned_slot_is_evicted_alone_and_leaves_nothing_behind(lm):
    """Poison isolation as for the other models: the poisoned request is
    evicted alone, its neighbour serves the reference's tokens, and the
    slot's next occupant finds none of its rows: its table blocks are
    scrubbed at release, its rings rewritten whole by the next prefill."""
    from bigdl_tpu.utils import faults

    params = lm[0]
    prompts = _prompts((12, 9))
    faults.set_plan(faults.FaultPlan("serve_nan@2"))
    try:
        eng = _engine(lm, slots=2)
        bad, good = eng.run([Request(prompt=p, max_new_tokens=10)
                             for p in prompts])
    finally:
        faults.set_plan(None)
    assert bad.status == "poisoned" and good.status == "done"
    lg = _reference_logits(params, (prompts[1] + good.tokens)[:-1])[8:]
    gap = jnp.max(lg, -1) - lg[jnp.arange(10), jnp.asarray(good.tokens)]
    assert float(gap.max()) < TIGHT
    (after,) = eng.run([Request(prompt=prompts[1], max_new_tokens=10)])
    assert after.tokens == good.tokens      # in the poisoned slot's seat


REFUSED = [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(prefix_cache=True, spill=True), "prefix_cache"),
    (dict(role="prefill"), "role='prefill'"),
    (dict(role="decode"), "role='decode'"),
    (dict(weight_dtype="int8"), "int8"),
    (dict(tp_mesh=True), "tp_mesh"),
]


@pytest.mark.parametrize("options,named", REFUSED,
                         ids=[named for _, named in REFUSED])
def test_what_the_model_does_not_serve_is_refused_by_name(lm, options,
                                                          named):
    if "tp_mesh" in options:
        options = dict(tp_mesh=jax.sharding.Mesh(
            np.array(jax.devices()[:1]), ("model",)))
    with pytest.raises(NotImplementedError, match=named) as e:
        _engine(lm, **options)
    assert "WindowMoELM does not serve with" in str(e.value)
    assert len(str(e.value).split(": ", 1)[1]) > 20     # and says why


def test_speculation_and_handoff_import_are_refused(lm):
    with pytest.raises(NotImplementedError, match="SpeculativeEngine"):
        SpeculativeEngine(_engine(lm), _engine(lm), k=2)
    pf = InferenceEngine(*_table_only_lm(), slots=1, max_len=32,
                         prefill_buckets=(8,), block_size=BLOCK,
                         role="prefill")
    pf.submit(Request(prompt=[1, 2, 3, 4, 5], max_new_tokens=2))
    pf.step()
    (pkg,) = pf.take_handoffs()
    with pytest.raises(NotImplementedError, match="ring"):
        _engine(lm).import_handoff(pkg)


def _table_only_lm():
    from bigdl_tpu.models.transformer import build_lm

    model = build_lm(vocab_size=50, dim=16, num_heads=2, num_layers=1,
                     max_len=32)
    return model, model.init(jax.random.PRNGKey(0))


def test_the_spans_say_what_a_step_touched(lm):
    eng = _engine(lm)
    # tracer off: no fetch of the model's aux
    with mock.patch.object(jax, "device_get", side_effect=AssertionError):
        eng.run([Request(prompt=p, max_new_tokens=3)
                 for p in _prompts((5, 9))])
    assert eng._aux is None
    obs.set_tracer(obs.SpanTracer(enabled=True))
    try:
        eng.run([Request(prompt=p, max_new_tokens=3)
                 for p in _prompts((7, 11, 6))])
        steps = obs.get_tracer().events("decode_step")
        prefills = obs.get_tracer().events("prefill")
    finally:
        obs.set_tracer(None)
    assert steps and all(
        len(e["args"]["experts_touched"]) == 4
        and len(e["args"]["expert_load_max_over_mean"]) == 4
        and {"window_rows", "full_rows", "attended_rows"} <= set(e["args"])
        for e in steps)
    # 3 slots x 2 experts a token x 4 expert layers, every step
    assert all(e["args"]["moe_assignments"] == 24 for e in steps)
    assert all(e["args"]["moe_assignments"] == 2 * e["args"]["bucket"]
               for e in prefills)
    assert eng.stats["decode_traces"] <= 1   # the same program either way
