"""The `granite_hybrid` family at a toy size on the CPU: the plain reference
(its recurrence a `lax.scan` over positions) against the program (a chunked
scan at prefill, a one-token update at decode), LOGITS against logits,
through `apply`, through prefill then decode at prompt lengths that put the
kept position before the sequence, at its start, at a chunk's edge and at
the bucket's end, and through the engine with requests that join and leave
mid-way; what a slot's state does between tenants; each term of the
mathematics left out of a copy of the reference fails the comparison; what
`from_source` and `check_serving_options` refuse; the counts against the
program's parameters at the cell's configuration; the cell's driver end to
end with the float8 control failing where the program passes, and both new
readers returning a number.

The toy configuration (tests/bench/tiny_granite_hybrid/) has every
mechanism of benchmarks/configs/granite-4.0-h-micro.json at widths of a few
dozen, the ratios kept: one period of ten layers with its one attention
layer, 8 query heads over 2 key-value heads, 8 state-space heads of 8 with a
state of 16, 4 taps, the four multipliers, a tied head; the scan's chunk is
8, the buckets 16 and 32. Its weights are the reference's bfloat16-valued
ones held in float32 and both sides compute in float32, so what is left
between them is the order of summation (the chunked scan sums a chunk's
positions in a matmul, the reference one after another):

  TIGHT = 3e-5 on logits of order 1 (standard deviation 0.72, largest 3.0;
  measured at most 7.2e-6 over the full forward and every prompt length
  below): four times the rounding seen, and under a four-thousandth of the
  smallest left-out term of ABLATIONS (the attention's multiplier: 0.13).

The toy initialiser is normal(0, 1) where the width would suggest 0.18: at
0.2 the tied head puts the token just read first at every position (its
own embedding, times 12, stands over ten layers' small additions), a
greedy request is a run of one token whose lead no rounding moves, and the
float8 control reads 0.0; at 1 the layers outweigh the embedding and the
served tokens vary.
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
from benchmarks.families import granite_hybrid as fam
from benchmarks.harness import manifest as mf
from benchmarks.reference import granite_hybrid as ref

TINY = os.path.join(REPO, "tests", "bench", "tiny_granite_hybrid")
CELL, REAL_CELL = "tiny-granite-hybrid-backlog", \
    "granite-4.0-h-micro-serve-backlog"
TIGHT = 3e-5
CHUNK, BUCKET, BLOCK = 8, 32, 4


def _cfg():
    with open(os.path.join(TINY, "configs", "tiny-granite-hybrid.json")) as f:
        return json.load(f)


def _real_cfg():
    with open(os.path.join(
            REPO, "benchmarks/configs/granite-4.0-h-micro.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def toy():
    """(cfg, reference params, program model, program variables, tokens,
    the reference's logits): everything from seed 5."""
    cfg = _cfg()
    assert cfg["mamba_chunk_size"] == CHUNK
    params = fam.reference_params(5, cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0,
                              cfg["vocab_size"])
    with jax.default_matmul_precision("highest"):
        want = ref.logits(params, toks, cfg)
    return (cfg, params, fam.program_model(cfg), fam.make_variables(5, cfg),
            toks, want)


def _paged_logits(model, variables, toks, prompt_len, block=BLOCK,
                  bucket=BUCKET, cache=jnp.float32):
    """Prefill `prompt_len` tokens of one sequence (padded to `bucket`)
    into slot 0, then decode the rest a token at a time: the logits of
    every position from `prompt_len - 1` on, as the engine produces them
    (it re-decodes the last prompt token)."""
    n = toks.shape[0]
    nb = -(-n // block)
    pools = model.init_block_pool(1 + nb, block, cache, slots=1)
    table = np.arange(1, 1 + nb, dtype=np.int32)[None]
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :prompt_len] = toks[:prompt_len]
    ids = {"table": jnp.asarray(table[0, :bucket // block]),
           "state": {"slot": np.int32(0), "keep": np.int32(prompt_len - 2)}}
    prefill, step = _jitted(model)
    pools = prefill(variables, jnp.asarray(padded), pools,
                    jnp.asarray(table), ids, 0)
    out = []
    for t in range(prompt_len - 1, n):
        lg, pools = step(variables, toks[t:t + 1], jnp.asarray([t]), pools,
                         jnp.asarray(table))
        out.append(lg[0])
    return jnp.stack(out)


_JITTED = {}


def _jitted(model):
    """The model's prefill and decode step, each compiled once a shape
    (as the engine runs them), and not dispatched an operation at a time
    forty times a case."""
    if id(model) not in _JITTED:
        _JITTED[id(model)] = (model, jax.jit(model.prefill_paged),
                              jax.jit(model.decode_step_paged))
    return _JITTED[id(model)][1:]


def test_the_weights_are_the_references(toy):
    cfg, params, model, variables, _, _ = toy
    p = variables["params"]
    mamba, attn = params["layers"][2], params["layers"][5]
    assert p["layers"][2]["in_proj"].dtype == jnp.float32
    np.testing.assert_array_equal(p["layers"][2]["in_proj"],
                                  mamba["in_proj"])
    np.testing.assert_array_equal(p["layers"][2]["conv_w"], mamba["conv"])
    np.testing.assert_array_equal(p["layers"][5]["wk"], attn["w_k"])
    np.testing.assert_array_equal(p["embed"], params["embed"])
    # the made-a-layer-at-a-time tree is `ref.init`'s
    whole = jax.jit(lambda s: ref.init(s, cfg))(jnp.uint32(5))
    assert jax.tree_util.tree_structure(whole) \
        == jax.tree_util.tree_structure(params)
    for a, b in zip(jax.tree_util.tree_leaves(whole),
                    jax.tree_util.tree_leaves(params)):
        np.testing.assert_array_equal(a, b)
    # Mamba-2's own initialisers (`assumed` in the configuration's file)
    a = np.exp(np.asarray(mamba["A_log"]))
    assert a.min() >= 1.0 and a.max() <= 16.0
    dt = np.asarray(jax.nn.softplus(mamba["dt_bias"]))
    assert dt.min() >= 0.999e-3 and dt.max() <= 1.001e-1
    assert (np.asarray(mamba["D"]) == 1.0).all()
    # and the program has the reference's shapes under its own names
    shapes = jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0)))
    assert jax.tree_util.tree_map(lambda s: s.shape, shapes) \
        == jax.tree_util.tree_map(lambda s: s.shape, p)


def test_full_forward_equals_the_reference(toy):
    _, _, model, variables, toks, want = toy
    with jax.default_matmul_precision("highest"):
        got, _ = model.apply(variables, toks)
    assert float(jnp.max(jnp.abs(got - want))) < TIGHT


@pytest.mark.parametrize("prompt_len", [
    1, 2, CHUNK - 1, CHUNK, CHUNK + 1, CHUNK + 2, BUCKET - 1, BUCKET])
def test_prefill_then_decode_equals_the_references_full_forward(
        toy, prompt_len):
    """The rows and the slot's state come from the prefill's chunked scan,
    the logits from the decode step, which reads the state and rewrites
    it: both against the reference, which has neither and scans a position
    at a time. The state is kept after position `prompt_len - 2`: -1 (a
    prompt of ONE token: zeros), 0, the last but one of a chunk, a chunk's
    last position (CHUNK + 1), the next chunk's first (CHUNK + 2), and the
    bucket's last but one (a prompt that fills its bucket: no padding)."""
    _, _, model, variables, toks, want = toy
    with jax.default_matmul_precision("highest"):
        got = _paged_logits(model, variables, toks[0], prompt_len)
    assert float(jnp.max(jnp.abs(got - want[0, prompt_len - 1:]))) < TIGHT


def test_the_same_prompt_in_either_bucket_leaves_the_same_state(toy):
    """What lies behind `keep` in the bucket does not move the state: the
    logits after a prompt of 9 tokens prefilled in the bucket of 16 and in
    the bucket of 32."""
    _, _, model, variables, toks, _ = toy
    with jax.default_matmul_precision("highest"):
        small = _paged_logits(model, variables, toks[1], 9, bucket=16)
        large = _paged_logits(model, variables, toks[1], 9, bucket=32)
    assert float(jnp.max(jnp.abs(small - large))) < TIGHT


def _engine(toy, **kw):
    from bigdl_tpu.serving import InferenceEngine

    _, _, model, variables, _, _ = toy
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
    (continuous batching over the recurrent state) and every slot is used
    again by a later request; among them a prompt of one token, one of a
    whole chunk and one that fills its bucket: every served token is the
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


def _state(eng, leaf):
    """(mamba layers, slots, ...): one leaf of every "state" entry."""
    return np.stack([np.asarray(entry[leaf]) for kind, entry in zip(
        eng._cache_kinds, eng.pool) if kind == "state"])


def test_the_pools_are_one_entry_a_layer_of_its_kind(toy):
    cfg, _, model, _, _, _ = toy
    assert model.cache_kinds() == ("state",) * 5 + ("table",) \
        + ("state",) * 4
    pools = model.init_block_pool(9, BLOCK, jnp.bfloat16, slots=3)
    assert [sorted(e) for e in pools] == [["h", "taps"]] * 5 \
        + [["k", "v"]] + [["h", "taps"]] * 4
    # the recurrence's state is float32 whatever the cache's dtype; the
    # taps and the rows follow it
    assert pools[0]["h"].shape == (3, 8, 8, 16)
    assert pools[0]["h"].dtype == jnp.float32
    assert pools[0]["taps"].shape == (3, 3, 64 + 2 * 16)
    assert pools[0]["taps"].dtype == pools[5]["k"].dtype == jnp.bfloat16
    assert pools[5]["k"].shape == (9, BLOCK, 2 * 4)
    assert model.slot_state_bytes(jnp.bfloat16) \
        == 9 * (4 * 8 * 8 * 16 + 2 * 3 * 96)
    assert model.slot_state_bytes(jnp.float32) \
        == 9 * 4 * (8 * 8 * 16 + 3 * 96)
    eng = _engine(toy)
    assert eng._slot_state_bytes == model.slot_state_bytes(jnp.float32) \
        == sum(leaf.nbytes // 3 for kind, entry in zip(
            eng._cache_kinds, eng.pool) if kind == "state"
            for leaf in entry.values())


def test_an_unseated_slots_state_keeps_its_bits_through_a_step(toy):
    """Slot 0 decodes; slots 1 and 2 hold a marker that no program may
    touch: the decode step rewrites the seated slots' rows only, and the
    prefill the one slot it is given."""
    from bigdl_tpu.serving import Request

    cfg = toy[0]
    eng = _engine(toy)
    marker = {"h": 7.25, "taps": -3.5}
    eng.pool = tuple(
        {n: leaf.at[1:].set(marker[n]) for n, leaf in entry.items()}
        if kind == "state" else entry
        for kind, entry in zip(eng._cache_kinds, eng.pool))
    (prompt,) = _prompts(cfg, (9,))
    eng.submit(Request(prompt=prompt, max_new_tokens=6))
    eng.step()
    after_prefill = _state(eng, "h")
    for _ in range(3):
        eng.step()
    for leaf, value in marker.items():
        got = _state(eng, leaf)
        assert (got[:, 1:] == value).all()          # bit for bit
        assert np.abs(got[:, 0]).max() > 0 and not (got[:, 0] == value).all()
    assert np.abs(_state(eng, "h")[:, 0] - after_prefill[:, 0]).max() > 1e-4
    # the state after n prompt tokens and m steps is the one a prefill of
    # the n + m - 1 tokens fed so far would leave
    fed = prompt + eng._gen[0][:-1]
    other = _engine(toy, slots=1)
    other.submit(Request(prompt=fed + [0], max_new_tokens=2))
    other._admit()                      # the prefill alone, no decode step
    # (a state's entries are sums over positions, not logits of order 1:
    # TIGHT of the leaf's largest)
    for leaf in marker:
        want = _state(eng, leaf)[:, 0]
        np.testing.assert_allclose(_state(other, leaf)[:, 0], want,
                                   atol=TIGHT * np.abs(want).max())


def test_a_slots_second_tenant_reads_as_a_first(toy):
    """A sound release launches nothing: the rows of both leaves stay
    until the next prefill rewrites the whole of them, starting its scan
    from ZERO and not from what is there. So the reused slot is fed the
    worst a row can hold."""
    from bigdl_tpu.serving import Request

    cfg = toy[0]
    first, second = _prompts(cfg, (13, 6), seed=4)
    eng = _engine(toy, slots=1)
    eng.run([Request(prompt=first, max_new_tokens=9)])
    assert _state(eng, "h").any()   # released, and no program ran for it
    assert eng.health()["slot_state_bytes"] == 0
    eng.pool = tuple(
        {n: jnp.full_like(leaf, jnp.nan) for n, leaf in entry.items()}
        if kind == "state" else entry
        for kind, entry in zip(eng._cache_kinds, eng.pool))
    (one,) = eng.run([Request(prompt=second[:1], max_new_tokens=3)])
    (fresh_one,) = _engine(toy, slots=1).run(
        [Request(prompt=second[:1], max_new_tokens=3)])
    assert one.status == "done" and one.tokens == fresh_one.tokens
    (again,) = eng.run([Request(prompt=second, max_new_tokens=9)])
    (fresh,) = _engine(toy, slots=1).run(
        [Request(prompt=second, max_new_tokens=9)])
    assert again.tokens == fresh.tokens
    assert _served_gap(toy, second, again.tokens) < TIGHT


def test_a_poisoned_slot_is_evicted_alone_and_its_state_is_scrubbed(toy):
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
    for leaf in ("h", "taps"):
        held = _state(eng, leaf)
        assert not held[:, 0].any() and np.abs(held[:, 1]).max() > 0
    (good,) = [r for r in eng.run() if r.status == "done"]
    (alone,) = _engine(toy, slots=1).run(
        [Request(prompt=prompts[1], max_new_tokens=10)])
    assert good.tokens == alone.tokens


def test_the_spans_the_counter_the_gauge_and_health(toy):
    """`decode_step` spans say what the step reads of rows (one attention
    layer's visible rows, the four... here one table's gathered rows) and
    of state; `prefill` spans the scan's chunks; the counter counts them
    with the tracer OFF too."""
    from bigdl_tpu import obs
    from bigdl_tpu.ops.kv_cache import attended_blocks
    from bigdl_tpu.serving import Request

    cfg, _, model, _, _, _ = toy
    eng = _engine(toy)
    counter = obs.get_registry().counter(
        "serving_prefill_scan_chunks_total", "",
        labelnames=("engine",)).labels(engine=eng.obs_name)
    eng.run([Request(prompt=p, max_new_tokens=3)
             for p in _prompts(cfg, (5, 20))])      # buckets 16 and 32
    assert counter.value == 16 // CHUNK + 32 // CHUNK
    obs.set_tracer(obs.SpanTracer(enabled=True))
    try:
        for p in _prompts(cfg, (7, 11)):
            eng.submit(Request(prompt=p, max_new_tokens=4))
        eng.step()
        per_slot = model.slot_state_bytes(jnp.float32)
        assert eng.health()["slot_state_bytes"] == 2 * per_slot
        gauge = obs.get_registry().gauge(
            "serving_slot_state_bytes", "", labelnames=("engine",)).labels(
                engine=eng.obs_name)
        assert gauge.value == 2 * per_slot
        eng.run()
        assert gauge.value == 0 == eng.health()["slot_state_bytes"]
        steps = obs.get_tracer().events("decode_step")
        prefills = obs.get_tracer().events("prefill")
    finally:
        obs.set_tracer(None)
    assert counter.value == 10 and len(prefills) == 2
    assert all(e["args"]["scan_chunks"] == e["args"]["bucket"] // CHUNK == 2
               for e in prefills)
    assert steps and all(
        e["args"]["state_bytes"] == e["args"]["active"] * per_slot
        and e["args"]["window_rows"] == 0
        and e["args"]["full_rows"] == e["args"]["cached_tokens"]
        # ONE attention layer: whole chunks of its rows, never fewer than
        # the queries may see
        and e["args"]["attended_rows"] >= e["args"]["full_rows"]
        for e in steps)
    pos = np.array([9, 0, 30], np.int32)
    table = np.zeros((3, 16), np.int32)
    table[0, :3], table[2, :8] = [1, 2, 3], np.arange(4, 12)
    got = model.decode_read_report(pos, table, BLOCK)
    assert got == {"window_rows": 0, "full_rows": 10 + 31,
                   "attended_rows": BLOCK * attended_blocks(
                       pos, table, BLOCK)}
    assert model.prefill_span_args(24) == {"scan_chunks": 3}
    assert eng.stats["decode_traces"] <= 1   # the same program either way


# (what is left out, the line of the reference, what stands there instead)
ABLATIONS = [
    ("the_state_handed_on",
     "state = jnp.exp(dt_t * a)[:, None, None] * state \\",
     "state = 0.0 * state \\"),
    ("the_decay",
     "state = jnp.exp(dt_t * a)[:, None, None] * state \\",
     "state = state \\"),
    ("delta_on_the_input",
     "+ (dt_t[:, None] * x_t)[:, :, None] * b_t",
     "+ x_t[:, :, None] * b_t"),
    ("the_d_skip",
     "return state, jnp.sum(state * c_t, -1) + lp[\"D\"][:, None] * x_t",
     "return state, jnp.sum(state * c_t, -1)"),
    ("the_softplus", "dt = jax.nn.softplus(dt + lp[\"dt_bias\"])",
     "dt = jnp.exp(dt + lp[\"dt_bias\"])"),
    ("the_convolutions_oldest_tap",
     "for j in range(taps)) + lp[\"conv_bias\"]",
     "for j in range(1, taps)) + lp[\"conv_bias\"]"),
    ("the_convolutions_bias",
     "for j in range(taps)) + lp[\"conv_bias\"]", "for j in range(taps))"),
    ("the_convolutions_silu", "xbc = jax.nn.silu(xbc)", "pass"),
    ("the_gate_before_the_norm",
     "y = y.reshape(s, inner) * jax.nn.silu(z)                # the gate "
     "first\n    y = _rms(y, lp[\"gate_norm\"], cfg[\"rms_norm_eps\"])",
     "y = _rms(y.reshape(s, inner), lp[\"gate_norm\"], "
     "cfg[\"rms_norm_eps\"]) * jax.nn.silu(z)"),
    ("the_attention_multiplier",
     "* cfg[\"attention_multiplier\"]", "* dh ** -0.5"),
    ("the_group_of_a_query_head",
     "k = jnp.repeat(k, hq // g, axis=1).transpose(1, 2, 0)",
     "k = jnp.tile(k, (1, hq // g, 1)).transpose(1, 2, 0)"),
    ("the_causal_mask",
     "visible = pos[None, :] <= (i * rows + jnp.arange(rows))[:, None]",
     "visible = pos[None, :] <= pos[-1]"),
    ("the_residual_multiplier",
     "x = x + rm * mixer(lp, _rms(x, lp[\"input_norm\"], eps), cfg, "
     "precision)",
     "x = x + mixer(lp, _rms(x, lp[\"input_norm\"], eps), cfg, precision)"),
    ("the_embedding_multiplier",
     "return cfg[\"embedding_multiplier\"] * _f32(params[\"embed\"][tokens])",
     "return _f32(params[\"embed\"][tokens])"),
    ("the_logits_scaling", "/ cfg[\"logits_scaling\"]", ""),
]


@pytest.mark.parametrize("name,line,instead", ABLATIONS,
                         ids=[a[0] for a in ABLATIONS])
def test_a_reference_with_a_term_left_out_fails_the_comparison(
        toy, name, line, instead):
    """A COPY of the reference's source with one line changed: the program
    no longer agrees with it, by a hundred times the tolerance or more,
    so the comparison would catch the term missing from the program."""
    cfg, params, model, variables, toks, want = toy
    source = inspect.getsource(ref)
    assert source.count(line) == 1, f"the reference no longer has: {line}"
    copy = types.ModuleType(f"granite_hybrid_without_{name}")
    exec(compile(source.replace(line, instead), copy.__name__, "exec"),
         copy.__dict__)
    with jax.default_matmul_precision("highest"):
        ablated = copy.logits(params, toks, cfg)
        got = _paged_logits(model, variables, toks[0], 17)  # compiled once
    assert float(jnp.max(jnp.abs(ablated - want))) > 100 * TIGHT
    assert float(jnp.max(jnp.abs(got - ablated[0, 16:]))) > 100 * TIGHT


def test_lower_precisions_differ_from_the_reference(toy):
    """The float8 control, and the program computing in bfloat16 where the
    file says float32, both miss the reference by far more than TIGHT."""
    cfg, params, _, _, toks, want = toy
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
    ("num_local_experts", 8), ("mamba_n_groups", 2),
    ("rope_scaling", {"type": "linear", "factor": 2.0}),
    ("position_embedding_type", "rope"), ("tie_word_embeddings", False),
    ("attention_bias", True), ("mamba_proj_bias", True),
    ("mamba_conv_bias", False), ("hidden_act", "gelu"),
    ("normalization_function", "layernorm"), ("num_experts_per_tok", 2)])
def test_a_source_value_that_is_not_built_is_refused_by_name(key, value):
    from bigdl_tpu.models.hybrid_ssm import HybridSSMConfig

    with pytest.raises(NotImplementedError, match=key):
        HybridSSMConfig.from_source(dict(_cfg(), **{key: value}))


def test_a_source_that_does_not_add_up_is_refused():
    from bigdl_tpu.models.hybrid_ssm import HybridSSMConfig

    cfg = _cfg()
    with pytest.raises(ValueError, match="layer_types"):
        HybridSSMConfig.from_source(dict(cfg, num_hidden_layers=9))
    with pytest.raises(ValueError, match="layers"):
        HybridSSMConfig.from_source(dict(cfg, layer_types=["full"] * 10))
    with pytest.raises(ValueError, match="mamba_expand"):
        HybridSSMConfig.from_source(dict(cfg, mamba_d_head=16))
    got = HybridSSMConfig.from_source(_real_cfg())
    assert got.layers.count("mamba") == 36 and got.layers.count(
        "attention") == 4 and [i for i, k in enumerate(got.layers)
                               if k == "attention"] == [5, 15, 25, 35]
    assert (got.embedding_multiplier, got.residual_multiplier,
            got.attention_multiplier, got.logits_scaling) \
        == (12, 0.22, 0.015625, 8)
    assert got.max_len == 131072 and got.mamba_chunk_size == 256


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


@pytest.mark.parametrize("options,named", REFUSED,
                         ids=[named for _, named in REFUSED])
def test_what_a_model_with_a_scanned_state_does_not_serve_is_refused(
        toy, options, named):
    from bigdl_tpu.models.transformer import build_lm
    from bigdl_tpu.serving import (InferenceEngine, Request,
                                   SpeculativeEngine)

    with pytest.raises(NotImplementedError, match=named) as e:
        if options == "speculative":
            SpeculativeEngine(_engine(toy), _engine(toy), k=2)
        elif options == "import_handoff":
            lm = build_lm(vocab_size=50, dim=16, num_heads=2, num_layers=1,
                          max_len=32)
            pf = InferenceEngine(lm, lm.init(jax.random.PRNGKey(0)),
                                 slots=1, max_len=32, prefill_buckets=(8,),
                                 block_size=BLOCK, role="prefill")
            pf.submit(Request(prompt=[1, 2, 3, 4, 5], max_new_tokens=2))
            pf.step()
            _engine(toy).import_handoff(pf.take_handoffs()[0])
        else:
            if "tp_mesh" in options:
                options = dict(tp_mesh=jax.sharding.Mesh(
                    np.array(jax.devices()[:1]), ("model",)))
            _engine(toy, **options)
    if options != "import_handoff":
        assert "HybridSSMLM does not serve with" in str(e.value)
        assert len(str(e.value).split(": ", 1)[1]) > 20     # and says why


def test_counts_match_the_program_at_the_cells_configuration():
    from benchmarks.counts import granite_hybrid as counts

    cfg = _real_cfg()
    assert cfg["reduced"] == [] and cfg["num_hidden_layers"] == 40
    assert cfg["dtype"] == {"weights": "bfloat16", "cache": "bfloat16",
                            "state": "float32"}
    model = fam.program_model(cfg)
    shapes = jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), dtype=jnp.bfloat16))
    leaves = jax.tree_util.tree_leaves(shapes)
    n = sum(int(np.prod(s.shape)) for s in leaves)
    ref_shapes = jax.eval_shape(lambda: ref.init(0, cfg))
    assert n == counts.params_held(cfg) == sum(
        int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(ref_shapes))
    assert n == 3_191_396_096                   # ISSUE 42's table
    n32 = sum(int(np.prod(s.shape)) for s in leaves
              if s.dtype == jnp.float32)
    assert n32 == counts.float32_params(cfg)
    # a layer's parts, as ISSUE 42 lists them
    assert counts.mamba_matrix_params(cfg) \
        + counts.mamba_float32_params(cfg) - 4096 + 4096 == 25_847_232
    assert counts.attention_matrix_params(cfg) == 10_485_760
    assert counts.mlp_params(cfg) == 50_331_648
    assert counts.layer_plan(cfg).count(("mamba", "mlp")) == 36
    assert counts.slot_state_bytes(cfg) \
        == model.slot_state_bytes(jnp.bfloat16) \
        == 36 * (64 * 64 * 128 * 4 + 3 * 4352 * 2)
    assert counts.cache_row_bytes(cfg) * 4 == 8192
    # a decode step with nobody seated reads every weight once
    none = counts.decode_bytes_per_step(cfg, 0, 0)
    assert none == 2 * (n - n32) + 4 * n32
    # the state is read AND written; a cached position is 8,192 B
    assert counts.decode_bytes_per_step(cfg, 0, 64) - none \
        == 2 * 64 * 76_437_504
    assert counts.decode_bytes_per_step(cfg, 1000, 0) - none == 8_192_000
    # 64 full slots at 1,200 positions: three fifths are the state's
    full = counts.decode_bytes_per_step(cfg, 64 * 1200, 64)
    assert 0.57 < 2 * 64 * 76_437_504 / full < 0.60
    # a prefill: 2 FLOP a matrix parameter a position but the last
    # layer's output projection and MLP and the head, which nothing reads
    needed = n - n32 - cfg["vocab_size"] * 2048 - 4096 * 2048 - 50_331_648
    assert counts.prefill_flops(cfg, 1024) > 2 * needed * 1024
    assert counts.prefill_flops(cfg, 1024) < 1.08 * 2 * needed * 1024
    assert 2.9 < counts.prefill_flops(cfg, 3072) \
        / counts.prefill_flops(cfg, 1024) < 3.2


# ------------------------------------------------- the driver, end to end

@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """benchmarks/ copied, the toy configuration and traffic laid beside
    the real files, and a manifest with one cell that reports what the
    real cell reports."""
    root = str(tmp_path_factory.mktemp("granite_hybrid_root"))
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
        "name": "tiny-granite-hybrid", "source": "none: a toy size",
        "file": "benchmarks/configs/tiny-granite-hybrid.json", "reduced": [],
        "why": "tests only"}], workloads=[{
            "name": CELL, "config": "tiny-granite-hybrid",
            "traffic": "tiny-ragtool-backlog", "chips": 1,
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
    cell = mf.cell_of(real, REAL_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "granite-4.0-h-micro", "ragtool-backlog", 1)
    names = {m["name"] for m in mf.metrics_of(real, cell, "per_layer")}
    assert {"ssm_decode_roofline", "ssm_prefill_mfu",
            "backlog_decode_step_p50", "backlog_prefill_share",
            "backlog_device_idle", "backlog_queue_left",
            "backlog_peak_hbm", "backlog_admission_host_ms"} <= names
    # its layer plan has 36 layers that keep no rows: the reader that
    # multiplies by the plan's layers is not asked
    assert not {"attn_rows_read_over_visible", "cca_moe_decode_roofline",
                "moe_decode_roofline", "swa_moe_decode_roofline",
                "moe_expert_load_max_over_mean"} & names
    assert {m["name"] for m in mf.metrics_of(real, cell, "end_to_end")} \
        == {"serve_throughput", "setup_s"}
    entry = mf.config_of(real, cell)
    assert entry["reduced"] == [] and entry["source"] == _real_cfg()["source"]
    with open(os.path.join(REPO, mf.traffic_path(cell))) as f:
        mix = json.load(f)
    assert mix["backlog_requests_per_window_s"] == 12
    assert mix["engine"]["prefill_buckets"] == [512, 1024, 2048, 3072]
    assert all(b % 256 == 0 for b in mix["engine"]["prefill_buckets"])


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
    assert "ssm_decode_roofline" not in result["metrics"]
    assert "ssm_prefill_mfu" not in result["metrics"]
    assert 0 < result["metrics"]["backlog_queue_left"]["value"] < 100
    assert result["metrics"]["backlog_prefill_share"]["value"] > 0
    steps = [e for e in obs.get_tracer().events("decode_step")]
    prefills = [e for e in obs.get_tracer().events("prefill")]
    assert steps and all(e["args"]["state_bytes"] > 0 for e in steps)
    assert prefills and all(e["args"]["scan_chunks"] in (2, 4)
                            for e in prefills)
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
        counts=lambda: load_part(root, "counts", "granite_hybrid"))
    share = load_part(root, "layer_metrics", "ssm_decode_roofline").read(ctx)
    assert 0.0 < share < 100.0
    mfu = load_part(root, "layer_metrics", "ssm_prefill_mfu").read(ctx)
    assert 0.0 < mfu < 100.0
    assert any(l.startswith("ssm_prefill_mfu:") for l in lines)
    # a family whose counts know no prefill: nothing to read
    ctx.counts = lambda: load_part(root, "counts", "cca_moe")
    for name in ("ssm_decode_roofline", "ssm_prefill_mfu"):
        assert load_part(root, "layer_metrics", name).read(ctx) is None
    # no peaks (the CPU): nothing to read
    ctx.counts = lambda: load_part(root, "counts", "granite_hybrid")
    ctx.peaks = None
    for name in ("ssm_decode_roofline", "ssm_prefill_mfu"):
        assert load_part(root, "layer_metrics", name).read(ctx) is None
