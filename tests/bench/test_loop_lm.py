"""The `loop_lm` family at a toy size on the CPU: the plain reference (a
Python loop over passes and layers, no cache) against the program (one
`lax.scan` over the pass, the pools carried), LOGITS against logits, through
the full forward (the four exit gates too), through prefill then decode at
prompt lengths from one token to a full bucket, and through the engine with
requests that join and leave mid-way; three faults that only a looped model
can have, each in a copy of the reference, fail the comparison; what a
slot's second tenant reads; a poisoned slot; the float8 control; what
`from_source` and `check_serving_options` refuse; the counts against the
program's parameters at the cell's configuration; the cell's driver end to
end with the control failing where the program passes, and both new readers
returning a number.

The toy configuration (tests/bench/tiny_loop_lm/) has every mechanism of
benchmarks/configs/ouro-2.6b.json at widths of a few dozen, the ratios kept:
as many key-value heads as query heads (4 and 4), an MLP 2.75 times the
stream, an untied head, FOUR passes over three layers (twelve row sets
behind three weight sets). Its weights are the reference's bfloat16-valued
ones held in float32 and both sides compute in float32, so what is left
between them is the order of summation:

  TIGHT = 5e-5 on logits of order 1 (standard deviation 1.1, largest 4.2;
  measured at most 1.1e-5 over the full forward and every prompt length
  below): under a thousandth of what the mildest of LOOP_FAULTS moves.

The toy initialiser is normal(0, 0.2) where the real one is 0.02: at 32
lanes that gives sublayer outputs the norms can tell apart, and logits whose
order the float8 control changes.
"""

import inspect
import json
import os
import shutil
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_helpers import REPO, run_tiny, tree_hashes
from benchmarks.families import loop_lm as fam
from benchmarks.harness import manifest as mf
from benchmarks.reference import loop_lm as ref

TINY = os.path.join(REPO, "tests", "bench", "tiny_loop_lm")
CELL, REAL_CELL = "tiny-loop-lm-backlog", "ouro-2.6b-serve-backlog"
TIGHT = 5e-5
BUCKET, BLOCK = 32, 4
PASSES, LAYERS = 4, 3


def _cfg():
    with open(os.path.join(TINY, "configs", "tiny-loop-lm.json")) as f:
        return json.load(f)


def _real_cfg():
    with open(os.path.join(REPO, "benchmarks/configs/ouro-2.6b.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def toy():
    """(cfg, reference params, program model, program variables, tokens,
    the reference's logits, its exit gates): everything from seed 5. The
    exit gate is zero as the initialiser leaves it: the gates' own test
    gives it values."""
    cfg = _cfg()
    assert (cfg["total_ut_steps"], cfg["num_hidden_layers"]) \
        == (PASSES, LAYERS)
    params = fam.reference_params(5, cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0,
                              cfg["vocab_size"])
    with jax.default_matmul_precision("highest"):
        want, gates = ref.forward(params, toks, cfg)
    return (cfg, params, fam.program_model(cfg), fam.make_variables(5, cfg),
            toks, want, gates)


_JITTED = {}


def _jitted(model):
    """The model's prefill and decode step, each compiled once a shape (as
    the engine runs them)."""
    if id(model) not in _JITTED:
        _JITTED[id(model)] = (model, jax.jit(model.prefill_paged),
                              jax.jit(model.decode_step_paged))
    return _JITTED[id(model)][1:]


def _paged_logits(model, variables, toks, prompt_len, block=BLOCK,
                  bucket=BUCKET, cache=jnp.float32, rows=False):
    """Prefill `prompt_len` tokens of one sequence (padded to `bucket`),
    then decode the rest a token at a time: the logits of every position
    from `prompt_len - 1` on, as the engine produces them (it re-decodes
    the last prompt token). With `rows`: the pools after the prefill
    instead."""
    n = toks.shape[0]
    nb = -(-max(n, bucket) // block)
    pools = model.init_block_pool(1 + nb, block, cache)
    table = np.arange(1, 1 + nb, dtype=np.int32)[None]
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :prompt_len] = toks[:prompt_len]
    prefill, step = _jitted(model)
    pools = prefill(variables, jnp.asarray(padded), pools,
                    jnp.asarray(table), jnp.asarray(
                        table[0, :bucket // block]), 0)
    if rows:
        return pools
    out = []
    for t in range(prompt_len - 1, n):
        lg, pools = step(variables, toks[t:t + 1], jnp.asarray([t]), pools,
                         jnp.asarray(table))
        out.append(lg[0])
    return jnp.stack(out)


def test_the_weights_are_the_references(toy):
    cfg, params, model, variables, _, _, _ = toy
    p = variables["params"]
    assert p["layers"][1]["wq"].dtype == jnp.float32
    np.testing.assert_array_equal(p["layers"][1]["wq"],
                                  params["layers"][1]["w_q"])
    np.testing.assert_array_equal(p["layers"][2]["w_down"],
                                  params["layers"][2]["w_d"])
    np.testing.assert_array_equal(p["head"], params["head"])
    assert not np.asarray(p["exit_w"]).any()
    # the made-a-layer-at-a-time tree is `ref.init`'s
    whole = jax.jit(lambda s: ref.init(s, cfg))(jnp.uint32(5))
    assert jax.tree_util.tree_structure(whole) \
        == jax.tree_util.tree_structure(params)
    for a, b in zip(jax.tree_util.tree_leaves(whole),
                    jax.tree_util.tree_leaves(params)):
        np.testing.assert_array_equal(a, b)
    # ONE set a layer on both sides, whatever the passes; and the program
    # has the reference's shapes under its own names
    assert len(params["layers"]) == len(p["layers"]) == LAYERS
    shapes = jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0)))
    assert jax.tree_util.tree_map(lambda s: s.shape, shapes) \
        == jax.tree_util.tree_map(lambda s: s.shape, p)


def test_full_forward_equals_the_reference_logits_and_gates(toy):
    """The exit gate given values (the initialiser leaves it zero, where
    every lambda is a half): the four lambda_t of every position too."""
    cfg, params, model, variables, toks, want, _ = toy
    key = jax.random.PRNGKey(3)
    gate = {"exit_w": jax.random.normal(key, (cfg["hidden_size"],)) * 0.3,
            "exit_b": jnp.float32(0.1)}
    with jax.default_matmul_precision("highest"):
        _, gates = ref.forward({**params, **gate}, toks, cfg)
        got, lam = jax.jit(model.forward)(
            {**variables["params"], **gate}, toks)
        plain, _ = model.apply(variables, toks)
    assert float(jnp.max(jnp.abs(got - want))) < TIGHT
    assert float(jnp.max(jnp.abs(plain - want))) < TIGHT
    assert lam.shape == gates.shape == (2, PASSES, 40)
    assert float(jnp.max(jnp.abs(lam - gates))) < TIGHT
    assert float(jnp.std(gates)) > 0.05         # and they say something
    # the exit distribution of a position adds up, the last pass the rest
    prob = ref.exit_probabilities(gates[0])
    np.testing.assert_allclose(np.asarray(prob.sum(0)), 1.0, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(prob[1]), np.asarray(gates[0, 1] * (1 - gates[0, 0])),
        atol=1e-6)


@pytest.mark.parametrize("prompt_len", [1, 2, BLOCK, BLOCK + 1, 17,
                                        BUCKET - 1, BUCKET])
def test_prefill_then_decode_equals_the_references_full_forward(
        toy, prompt_len):
    """The rows of every pass come from the prefill, the logits from the
    decode step, which reads them a pass at a time: both against the
    reference, which keeps nothing."""
    _, _, model, variables, toks, want, _ = toy
    with jax.default_matmul_precision("highest"):
        got = _paged_logits(model, variables, toks[0], prompt_len)
    assert float(jnp.max(jnp.abs(got - want[0, prompt_len - 1:]))) < TIGHT


def test_the_same_prompt_in_either_bucket_leaves_the_same_rows(toy):
    """A prompt of 9 tokens prefilled in the bucket of 16 and in the bucket
    of 32: the rows of its own positions in every pass of every layer are
    the same (what lies behind them in the bucket is padding's)."""
    _, _, model, variables, toks, _, _ = toy
    with jax.default_matmul_precision("highest"):
        small = _paged_logits(model, variables, toks[1], 9, bucket=16,
                              rows=True)
        large = _paged_logits(model, variables, toks[1], 9, bucket=32,
                              rows=True)
    assert len(small) == LAYERS
    for a, b in zip(small, large):
        for n in ("k", "v"):
            # blocks 1..3 hold positions 0..11: the prompt's are 0..8
            mine_a = np.asarray(a[n][1:4]).transpose(1, 0, 2, 3).reshape(
                PASSES, 12, -1)[:, :9]
            mine_b = np.asarray(b[n][1:4]).transpose(1, 0, 2, 3).reshape(
                PASSES, 12, -1)[:, :9]
            assert np.abs(mine_a).max() > 0.01
            np.testing.assert_allclose(mine_a, mine_b, atol=TIGHT)


def _engine(toy, **kw):
    from bigdl_tpu.serving import InferenceEngine

    _, _, model, variables, _, _, _ = toy
    args = dict(slots=3, max_len=64, prefill_buckets=(16, 32),
                block_size=BLOCK, prefix_cache=False)
    args.update(kw)
    return InferenceEngine(model, variables, **args)


def _prompts(cfg, lengths, seed=3):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg["vocab_size"], n).tolist() for n in lengths]


def _served_gap(toy, prompt, tokens):
    """How far each served token's reference logit lies under the
    reference's best at its position (logits, not shares)."""
    cfg, params = toy[0], toy[1]
    with jax.default_matmul_precision("highest"):
        lg = ref.logits(params, jnp.asarray([(prompt + tokens)[:-1]]),
                        cfg)[0, len(prompt) - 1:]
    return float(jnp.max(jnp.max(lg, -1) - lg[
        jnp.arange(len(tokens)), jnp.asarray(tokens)]))


def test_the_engine_serves_what_the_reference_puts_first(toy):
    """Through `InferenceEngine` + `EngineRouter`, greedy, eight requests
    over three slots, so that requests join and leave while others decode
    and every slot is used again by a later request; among them a prompt of
    one token and one that fills its bucket: every served token is the
    reference's best at its position, to within TIGHT."""
    from bigdl_tpu.serving import EngineRouter, Request

    cfg = toy[0]
    engine = _engine(toy)
    prompts = _prompts(cfg, (5, 1, 30, 16, 9, 2, 12, 8))
    results = EngineRouter([engine]).run(
        [Request(prompt=p, max_new_tokens=m)
         for p, m in zip(prompts, (21, 7, 30, 11, 25, 16, 9, 13))])
    assert [r.status for r in results] == ["done"] * len(prompts)
    assert engine.stats["prefill_calls"] == 8 > engine.slots
    for p, r in zip(prompts, results):
        assert _served_gap(toy, p, r.tokens) < TIGHT


def test_a_slots_second_tenant_reads_as_a_first(toy):
    """A release launches nothing: the first tenant's rows stay in the
    blocks, in every pass. The blocks are then fed the worst a row can hold
    (NaN, every pass of every layer), and the second tenant, seated in the
    same blocks, serves what a fresh engine serves: its prefill rewrites
    its own positions and the reads mask the rest."""
    from bigdl_tpu.serving import Request

    cfg = toy[0]
    first, second = _prompts(cfg, (13, 6), seed=4)
    eng = _engine(toy, slots=1)
    eng.run([Request(prompt=first, max_new_tokens=9)])
    assert np.asarray(eng.pool[0]["k"][1:]).any()
    eng.pool = jax.tree_util.tree_map(
        lambda leaf: jnp.full_like(leaf, jnp.nan), eng.pool)
    (again,) = eng.run([Request(prompt=second, max_new_tokens=9)])
    (fresh,) = _engine(toy, slots=1).run(
        [Request(prompt=second, max_new_tokens=9)])
    assert again.status == "done" and again.tokens == fresh.tokens
    assert _served_gap(toy, second, again.tokens) < TIGHT


def test_a_poisoned_slot_is_evicted_alone(toy):
    from bigdl_tpu.serving import Request
    from bigdl_tpu.utils import faults

    prompts = _prompts(toy[0], (12, 9), seed=4)
    faults.set_plan(faults.FaultPlan("serve_nan@2"))
    try:
        eng = _engine(toy, slots=2)
        for p in prompts:
            eng.submit(Request(prompt=p, max_new_tokens=10))
        done = []
        while not done:
            done = eng.step()
    finally:
        faults.set_plan(None)
    (bad,) = done
    assert bad.status == "poisoned"
    (good,) = [r for r in eng.run() if r.status == "done"]
    (alone,) = _engine(toy, slots=1).run(
        [Request(prompt=prompts[1], max_new_tokens=10)])
    assert good.tokens == alone.tokens


def test_the_spans_and_health_say_what_the_loop_reads(toy):
    """`decode_step` spans say what the step reads of rows (one row set's
    visible rows, the twelve row sets' gathered rows), of the loop (passes,
    row sets) and of the weights (the layers' bytes once a PASS, the head
    once); `prefill` spans the passes and the same bytes less the head's;
    `health()` the passes and the row sets."""
    from bigdl_tpu import obs
    from bigdl_tpu.ops.kv_cache import attended_blocks
    from bigdl_tpu.serving import Request

    cfg, _, model, variables, _, _, _ = toy
    eng = _engine(toy)
    assert eng.health()["ut_steps"] == PASSES
    assert eng.health()["cache_entries"] == PASSES * LAYERS == 12
    assert eng.health()["attn_form"] == model.decode_attn_form() == "rows"
    p = variables["params"]

    def nbytes(tree):
        return sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(tree))

    streamed = PASSES * nbytes((p["layers"], p["norm"])) + nbytes(p["head"])
    assert model.weight_bytes_streamed == streamed
    # the embedding's rows are gathered, the exit gate is not computed
    assert streamed < PASSES * nbytes(p)
    obs.set_tracer(obs.SpanTracer(enabled=True))
    try:
        eng.run([Request(prompt=pr, max_new_tokens=4)
                 for pr in _prompts(cfg, (7, 20))])
        steps = obs.get_tracer().events("decode_step")
        prefills = obs.get_tracer().events("prefill")
    finally:
        obs.set_tracer(None)
    assert len(prefills) == 2 and all(
        e["args"]["ut_steps"] == PASSES
        and e["args"]["weight_bytes_streamed"] == streamed - nbytes(
            p["head"]) for e in prefills)
    assert steps and all(
        e["args"]["ut_steps"] == PASSES and e["args"]["cache_entries"] == 12
        and e["args"]["weight_bytes_streamed"] == streamed
        and e["args"]["window_rows"] == 0
        and e["args"]["full_rows"] == e["args"]["cached_tokens"]
        and e["args"]["attended_rows"] >= 12 * e["args"]["full_rows"]
        and e["args"]["attended_rows"] == 12 * BLOCK
        * e["args"]["attended_blocks"] for e in steps)
    pos = np.array([9, 0, 30], np.int32)
    table = np.zeros((3, 16), np.int32)
    table[0, :3], table[2, :8] = [1, 2, 3], np.arange(4, 12)
    got = model.decode_read_report(pos, table, BLOCK)
    assert got == {"window_rows": 0, "full_rows": 10 + 31,
                   "attended_rows": 12 * BLOCK * attended_blocks(
                       pos, table, BLOCK),
                   "ut_steps": PASSES, "cache_entries": 12,
                   "weight_bytes_streamed": streamed}
    assert eng.stats["decode_traces"] <= 1   # the same program either way


def test_the_scopes_name_the_loops_parts_in_the_program(toy):
    """`loop_body`, `attention`, `mlp` and `loop_norm` in the decode
    program's op names, and ONE body of layers whatever the passes: the
    lowered text at four passes is no longer than at one, but for the
    scan's own few lines."""
    _, _, model, variables, _, _, _ = toy
    from bigdl_tpu.models.loop_lm import LoopLM, LoopLMConfig

    def text(m):
        pools = jax.eval_shape(lambda: m.init_block_pool(9, BLOCK))
        i32 = jnp.int32
        return jax.jit(m.decode_step_paged).lower(
            variables, jax.ShapeDtypeStruct((2,), i32),
            jax.ShapeDtypeStruct((2,), i32), pools,
            jax.ShapeDtypeStruct((2, 16), i32)).as_text(debug_info=True)

    four = text(model)
    for scope in ("loop_body", "attention", "mlp", "loop_norm"):
        assert f"{scope}" in four, scope
    assert "loop_body/attention" in four and "loop_body/loop_norm" in four
    one = text(LoopLM(LoopLMConfig.from_source(
        dict(_cfg(), total_ut_steps=1))))
    assert four.count("dot_general") == one.count("dot_general")
    assert len(four.splitlines()) < 1.05 * len(one.splitlines())


# (the fault, the line of the reference, what stands there instead)
LOOP_FAULTS = [
    ("three_passes_for_four",
     "for t in range(cfg[\"total_ut_steps\"]):",
     "for t in range(cfg[\"total_ut_steps\"] - 1):"),
    ("a_pass_attends_the_pass_befores_rows",
     "k, v = rows[t, l]       # this pass's own: never another's",
     "k, v = rows[max(t - 1, 0), l]"),
    ("the_final_norm_once_after_the_last_pass",
     "x = final(params, x, cfg)   # every pass ends in the final norm",
     "x = final(params, x, cfg) if t == cfg[\"total_ut_steps\"] - 1 else x"),
]
# and terms of the mathematics that any model of the family has
ABLATIONS = LOOP_FAULTS + [
    ("the_rotation", "return jnp.concatenate([a * jnp.cos(ang) - b * "
     "jnp.sin(ang),\n                            a * jnp.sin(ang) + b * "
     "jnp.cos(ang)], -1)", "return x"),
    ("the_norm_on_the_attentions_output",
     "x = x + _rms(_mm(o, _f32(lp[\"w_o\"]), precision), lp[\"norm_2\"], eps)",
     "x = x + _mm(o, _f32(lp[\"w_o\"]), precision)"),
    ("the_norm_on_the_mlps_output",
     "return x + _rms(f, lp[\"norm_4\"], eps)", "return x + f"),
    ("the_causal_mask",
     "causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]",
     "causal = jnp.arange(s)[None, :] <= s"),
    ("the_scale_of_the_scores", "* dh ** -0.5", ""),
]


@pytest.mark.parametrize("name,line,instead", ABLATIONS,
                         ids=[a[0] for a in ABLATIONS])
def test_a_reference_with_a_fault_of_the_loop_fails_the_comparison(
        toy, name, line, instead):
    """A COPY of the reference's source with one line changed: the program
    no longer agrees with it, by a hundred times the tolerance or more, so
    the comparison would catch the same fault in the program."""
    cfg, params, model, variables, toks, want, _ = toy
    source = inspect.getsource(ref)
    assert source.count(line) == 1, f"the reference no longer has: {line}"
    copy = types.ModuleType(f"loop_lm_with_{name}")
    exec(compile(source.replace(line, instead), copy.__name__, "exec"),
         copy.__dict__)
    with jax.default_matmul_precision("highest"):
        faulty = copy.logits(params, toks, cfg)
        got = _paged_logits(model, variables, toks[0], 17)  # compiled once
    assert float(jnp.max(jnp.abs(faulty - want))) > 100 * TIGHT
    assert float(jnp.max(jnp.abs(got - faulty[0, 16:]))) > 100 * TIGHT


def test_lower_precisions_differ_from_the_reference(toy):
    """The float8 control, and the program computing in bfloat16 where the
    file says float32, both miss the reference by far more than TIGHT."""
    cfg, params, _, _, toks, want, _ = toy
    with jax.default_matmul_precision("highest"):
        fp8 = ref.logits(params, toks, cfg, "fp8")
        low = dict(cfg, dtype={"weights": "bfloat16", "cache": "bfloat16"})
        bf16 = _paged_logits(fam.program_model(low),
                             fam.make_variables(5, low), toks[0], 17,
                             cache=jnp.bfloat16)
    assert 50 * TIGHT < float(jnp.max(jnp.abs(bf16 - want[0, 16:]))) < 1.0
    assert 500 * TIGHT < float(jnp.max(jnp.abs(fp8 - want))) < 4.0
    with pytest.raises(ValueError, match="precision"):
        ref.logits(params, toks, cfg, "bf16")


@pytest.mark.parametrize("key,value", [
    ("rope_scaling", {"type": "linear", "factor": 2.0}),
    ("use_sliding_window", True), ("tie_word_embeddings", True),
    ("early_exit_threshold", 0.9), ("hidden_act", "gelu"),
    ("attention_bias", True),
    ("layer_types", ["full_attention", "sliding_attention",
                     "full_attention"])])
def test_a_source_value_that_is_not_built_is_refused_by_name(key, value):
    from bigdl_tpu.models.loop_lm import LoopLMConfig

    with pytest.raises(NotImplementedError, match=key):
        LoopLMConfig.from_source(dict(_cfg(), **{key: value}))


def test_a_source_that_does_not_add_up_is_refused():
    from bigdl_tpu.models.loop_lm import LoopLMConfig

    cfg = _cfg()
    with pytest.raises(ValueError, match="layer_types"):
        LoopLMConfig.from_source(dict(cfg, num_hidden_layers=4))
    with pytest.raises(ValueError, match="num_key_value_heads"):
        LoopLMConfig.from_source(dict(cfg, num_key_value_heads=3))
    with pytest.raises(ValueError, match="total_ut_steps"):
        LoopLMConfig.from_source(dict(cfg, total_ut_steps=0))
    with pytest.raises(ValueError, match="layer types"):
        ref.layer_plan(dict(cfg, layer_types=["sliding_attention"] * 3))
    got = LoopLMConfig.from_source(_real_cfg())
    assert (got.num_hidden_layers, got.total_ut_steps, got.hidden_size,
            got.num_attention_heads, got.num_key_value_heads, got.head_dim,
            got.intermediate_size, got.vocab_size) \
        == (48, 4, 2048, 16, 16, 128, 5632, 49152)
    assert (got.rope_theta, got.rms_norm_eps, got.early_exit_threshold,
            got.max_len) == (1000000, 1e-06, 1, 65536)
    # fewer key-value heads than query heads is built (the grouped read)
    LoopLMConfig.from_source(dict(cfg, num_key_value_heads=2))


REFUSED = [
    (dict(role="prefill"), "role='prefill'"),
    (dict(role="decode"), "role='decode'"),
    (dict(weight_dtype="int8"), "int8"),
    (dict(tp_mesh=True), "tp_mesh"),
]


@pytest.mark.parametrize("options,named", REFUSED,
                         ids=[named for _, named in REFUSED])
def test_what_the_model_does_not_serve_is_refused_by_the_protocols_table(
        toy, options, named):
    if "tp_mesh" in options:
        options = dict(tp_mesh=jax.sharding.Mesh(
            np.array(jax.devices()[:1]), ("model",)))
    with pytest.raises(NotImplementedError, match=named) as e:
        _engine(toy, **options)
    assert "LoopLM does not serve with" in str(e.value)
    assert len(str(e.value).split(": ", 1)[1]) > 20     # and says why
    # nothing is refused for what the pool holds: every entry is a table
    model = toy[2]
    assert set(model.cache_kinds()) == {"table"}
    assert set(model.serving_refusals()) == {"weight_dtype", "tp", "role"}


def test_counts_match_the_program_at_the_cells_configuration():
    from benchmarks.counts import loop_lm as counts

    cfg = _real_cfg()
    assert cfg["reduced"] == [] and cfg["num_hidden_layers"] == 48
    assert cfg["total_ut_steps"] == 4
    assert cfg["dtype"] == {"weights": "bfloat16", "cache": "bfloat16"}
    model = fam.program_model(cfg)
    shapes = jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), dtype=jnp.bfloat16))
    leaves = jax.tree_util.tree_leaves(shapes)
    n = sum(int(np.prod(s.shape)) for s in leaves)
    ref_shapes = jax.eval_shape(lambda: ref.init(0, cfg))
    assert n == counts.params_held(cfg) == sum(
        int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(ref_shapes))
    assert n == 2_667_974_657                   # ISSUE 49's table
    n32 = sum(int(np.prod(s.shape)) for s in leaves
              if s.dtype == jnp.float32)
    assert n32 == counts.float32_params(cfg) == 48 * 4 * 2048 + 4097
    assert counts.layer_matrix_params(cfg) + 4 * 2048 == 51_388_416
    # ONE set, whatever the passes; the plan is the row sets
    assert counts.params_held(dict(cfg, total_ut_steps=1)) == n
    plan = counts.layer_plan(cfg)
    assert len(plan) == 192 == model.cache_entries
    assert set(plan) == {("full_attention", "dense")}
    assert counts.cache_row_bytes(cfg) * 192 == 1_572_864
    # what a step streams of the weights is what the program says it does
    model.serving_params({"params": shapes})
    assert counts.weight_bytes_per_step(cfg) == model.weight_bytes_streamed
    assert 19.93e9 < counts.weight_bytes_per_step(cfg) < 19.94e9
    none = counts.decode_bytes_per_step(cfg, 0, 0)
    assert none == counts.weight_bytes_per_step(cfg)
    assert counts.decode_bytes_per_step(cfg, 1000, 16) - none \
        == 1_572_864_000
    # a prefill streams the same layers, without the head, and writes its
    # rows; at the cell's buckets the bytes are the roof
    assert counts.prefill_bytes(cfg, 64) == none - 2 * 2048 * 49152 \
        + 64 * 1_572_864
    for bucket in (64, 128):
        assert counts.prefill_bytes(cfg, bucket) / 819e9 \
            > counts.prefill_flops(cfg, bucket) / 197e12
    needed = 192 * counts.layer_matrix_params(cfg)
    assert 0.99 * 2 * needed * 128 < counts.prefill_flops(cfg, 128) \
        < 1.02 * 2 * needed * 128


# ------------------------------------------------- the driver, end to end

@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """benchmarks/ copied, the toy configuration and traffic laid beside
    the real files, and a manifest with one cell that reports what the
    real cell reports."""
    root = str(tmp_path_factory.mktemp("loop_lm_root"))
    shutil.copytree(os.path.join(REPO, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    before = tree_hashes(os.path.join(root, "benchmarks"))
    for sub in ("configs", "traffic"):
        for f in os.listdir(os.path.join(TINY, sub)):
            dst = os.path.join(root, "benchmarks", sub, f)
            assert not os.path.exists(dst), f"{f} would replace a file"
            shutil.copy(os.path.join(TINY, sub, f), dst)
    real = mf.load(REPO)
    manifest = dict(real, run_seconds=1, configs=[{
        "name": "tiny-loop-lm", "source": "none: a toy size",
        "file": "benchmarks/configs/tiny-loop-lm.json", "reduced": [],
        "why": "tests only"}], workloads=[{
            "name": CELL, "config": "tiny-loop-lm",
            "traffic": "tiny-shortreason-backlog", "chips": 1,
            "why": "CPU rehearsal; no number of it is a measurement"}])
    for group in ("end_to_end", "per_layer"):
        manifest[group] = [
            dict(m, workloads=[CELL]) if "workloads" in m else m
            for m in real[group]
            if REAL_CELL in m.get("workloads", [REAL_CELL])]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    yield root, before
    from jax.experimental.compilation_cache import compilation_cache

    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
    from bigdl_tpu import obs

    obs.set_tracer(None)


def test_the_cell_is_files_only(tiny_root):
    root, before = tiny_root
    after = tree_hashes(os.path.join(root, "benchmarks"))
    assert {k: after[k] for k in before} == before
    assert mf.problems(mf.load(root), root) == []
    real = mf.load(REPO)
    assert mf.problems(real, REPO) == []
    cell = mf.cell_of(real, REAL_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ouro-2.6b", "shortreason-backlog", 1)
    names = {m["name"] for m in mf.metrics_of(real, cell, "per_layer")}
    assert {"loop_decode_roofline", "loop_prefill_roofline",
            "attn_rows_read_over_visible", "backlog_decode_step_p50",
            "backlog_prefill_share", "backlog_device_idle",
            "backlog_queue_left", "backlog_peak_hbm",
            "backlog_admission_host_ms"} <= names
    assert not {"ssm_decode_roofline", "ssm_prefill_mfu",
                "moe_decode_roofline", "moe_expert_load_max_over_mean"} \
        & names
    assert {m["name"] for m in mf.metrics_of(real, cell, "end_to_end")} \
        == {"serve_throughput", "setup_s"}
    entry = mf.config_of(real, cell)
    cfg = _real_cfg()
    assert entry["reduced"] == [] and entry["source"] == cfg["source"]
    # every [m] line, the initialiser, the lengths and the context
    assert {"modelling_code", "initialiser", "lengths", "context"} \
        <= set(cfg["assumed"])
    with open(os.path.join(REPO, mf.traffic_path(cell))) as f:
        mix = json.load(f)
    assert mix["backlog_requests_per_window_s"] == 22
    e = mix["engine"]
    assert (e["slots"], e["block_size"], e["max_len"], e["pool_blocks"],
            e["prefill_buckets"]) == (16, 16, 768, 385, [64, 128])
    # the longest request asks for no 25th block: 16 slots x 24 + scratch
    longest = mix["prompt_len"]["max"] + mix["output_len"]["max"]
    assert -(-longest // e["block_size"]) * e["slots"] + 1 \
        == e["pool_blocks"]


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 12])
def test_the_control_fails_where_the_program_passes(tiny_root, seed):
    root, _ = tiny_root
    result, lines = run_tiny(root, CELL, seed=seed, control="fp8")
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"serve_throughput", "setup_s"}
    widest, mean = (float(next(l for l in lines if l.startswith(
        "control fp8:")).split(word)[1].split(" ")[0])
        for word in ("widest ", "mean "))
    limits = _cfg()["limits"]["serve"]
    assert widest > 3 * limits["token_gap"]
    assert mean > 3 * limits["token_gap_mean"]


def test_a_traced_run_reads_the_new_spans_and_both_readers_answer(
        tiny_root):
    """On the CPU there is no device trace and no peak, so the run leaves
    both shares out; what the spans carry is read, and each reader, handed
    the device's part (a main program's time, the peaks), returns a share
    from the same spans."""
    from benchmarks.harness.runner import load_part
    from bigdl_tpu import obs

    root, _ = tiny_root
    result, lines = run_tiny(root, CELL, seed=13, trace=True)
    assert result["correct"] is True
    assert "loop_decode_roofline" not in result["metrics"]
    assert "loop_prefill_roofline" not in result["metrics"]
    assert 0 < result["metrics"]["backlog_queue_left"]["value"] < 100
    assert result["metrics"]["backlog_prefill_share"]["value"] > 0
    # twelve row sets gathered in whole chunks over one row set's visible
    # rows, twelve times: never under 1
    assert result["metrics"]["attn_rows_read_over_visible"]["value"] >= 1
    steps = [e for e in obs.get_tracer().events("decode_step")]
    prefills = [e for e in obs.get_tracer().events("prefill")]
    assert steps and all(e["args"]["cache_entries"] == 12 for e in steps)
    assert prefills and all(e["args"]["ut_steps"] == 4 for e in prefills)
    cfg = _cfg()
    t0 = steps[0]["ts"] / 1e6
    t1 = (steps[-1]["ts"] + steps[-1]["dur"]) / 1e6
    ctx = types.SimpleNamespace(
        config=cfg, device={"platform": "tpu"}, out=lines.append,
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        record={"window": (t0, t1)},
        trace_summary={"devices": 1, "main_module": {
            "name": "jit__decode_step", "runs": len(steps),
            "time_s": 1e-3 * len(steps)}},
        trace_window=types.SimpleNamespace(begin_host=t0, end_host=t1),
        counts=lambda: load_part(root, "counts", "loop_lm"))
    share = load_part(root, "layer_metrics", "loop_decode_roofline").read(ctx)
    assert 0.0 < share < 100.0
    assert any(l.startswith("loop_decode_roofline:") for l in lines)
    roof = load_part(root, "layer_metrics", "loop_prefill_roofline").read(ctx)
    assert 0.0 < roof < 100.0
    assert any(l.startswith("loop_prefill_roofline:") for l in lines)
    # a family whose counts know no loop: nothing to read
    ctx.counts = lambda: load_part(root, "counts", "granite_hybrid")
    for name in ("loop_decode_roofline", "loop_prefill_roofline"):
        assert load_part(root, "layer_metrics", name).read(ctx) is None
    # no peaks (the CPU): nothing to read
    ctx.counts = lambda: load_part(root, "counts", "loop_lm")
    ctx.peaks = None
    for name in ("loop_decode_roofline", "loop_prefill_roofline"):
        assert load_part(root, "layer_metrics", name).read(ctx) is None
