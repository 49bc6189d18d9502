"""`serving/protocol.ServedModel`: what the engine asks of a model, asked
of each of the seven classes that implement it, at toy sizes.

Every name of the protocol answers with the documented type; the cache
kinds, the ring and the state agree with the pool the model builds; the
refusals follow from the cache kinds, from quant.py and tp.py, and from
the class's own data; an object that is no `ServedModel` is refused by
name; and `serving/engine.py` and `serving/speculative.py` never reach
a model through `getattr` or `hasattr`.
"""

import ast
import importlib
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.serving import InferenceEngine, ServedModel, SpeculativeEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BLOCK, SLOTS, BLOCKS = 4, 2, 9
FAMILIES = {"mla_moe": "LatentMoELM", "afmoe": "WindowMoELM",
            "cca_moe": "CCAMoELM", "granite_hybrid": "HybridSSMLM",
            "loop_lm": "LoopLM"}
MODELS = ["gpt2", "gpt2_tp2", *FAMILIES]
# what shares, moves or rolls back TABLE blocks
BLOCK_OPTIONS = {"speculative", "prefix_cache", "spill", "role"}


def _built(name):
    """(model, variables as shapes) of one of the seven at a toy size."""
    if name.startswith("gpt2"):
        from bigdl_tpu.models.transformer import build_lm
        from bigdl_tpu.parallel import make_mesh
        from bigdl_tpu.serving import tp_serving_model

        lm = build_lm(vocab_size=61, dim=32, num_heads=2, num_layers=2,
                      max_len=64)
        variables = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0)))
        if name == "gpt2_tp2":
            lm = tp_serving_model(lm, make_mesh(
                {"model": 2}, devices=jax.devices()[:2]))
        return lm, variables
    fam = importlib.import_module(f"benchmarks.families.{name}")
    tiny = f"tiny_{name}/configs/tiny-{name.replace('_', '-')}.json"
    with open(os.path.join(REPO, "tests", "bench", tiny)) as f:
        cfg = json.load(f)
    return fam.program_model(cfg), jax.eval_shape(
        lambda: fam.make_variables(5, cfg))


@pytest.fixture(scope="module", params=MODELS)
def served(request):
    if request.param == "gpt2_tp2" and jax.device_count() < 2:
        pytest.skip("the tp wrapper needs two devices")
    return (request.param,) + _built(request.param)


def test_the_seven_classes_derive_from_the_protocol(served):
    name, model, _ = served
    assert isinstance(model, ServedModel)
    assert type(model).__name__ == FAMILIES.get(
        name, {"gpt2": "TransformerLM", "gpt2_tp2": "TPServingLM"}.get(name))
    assert model.tp == (2 if name == "gpt2_tp2" else 1)
    assert model.tp_axis is None
    assert model.cfg.max_len >= 64 and model.cfg.vocab_size > 1


def test_the_cache_kinds_agree_with_the_pool(served):
    _, model, _ = served
    kinds = model.cache_kinds()
    pools = jax.eval_shape(lambda: model.init_block_pool(
        BLOCKS, BLOCK, jnp.float32, slots=SLOTS))
    assert isinstance(kinds, tuple) and len(kinds) == len(pools)
    assert set(kinds) <= {"table", "ring", "state"}
    ring = model.ring_blocks(BLOCK)
    state = model.slot_state_bytes(jnp.float32)
    assert isinstance(ring, int) and isinstance(state, int)
    assert (ring == 0) == ("ring" not in kinds)
    assert (state == 0) == ("state" not in kinds)
    lead = {"table": BLOCKS, "ring": 1 + SLOTS * ring, "state": SLOTS}
    for kind, entry in zip(kinds, pools):
        assert isinstance(entry, dict)
        for leaf in jax.tree_util.tree_leaves(entry):
            assert leaf.shape[0] == lead[kind]
    # what the slots keep in the state entries is what the model says
    held = sum(leaf.size * leaf.dtype.itemsize
               for kind, entry in zip(kinds, pools) if kind == "state"
               for leaf in jax.tree_util.tree_leaves(entry))
    assert held == SLOTS * state
    # a caller that names no slots (the benchmark's own files) gets one
    assert len(jax.eval_shape(
        lambda: model.init_block_pool(BLOCKS, BLOCK))) == len(kinds)
    placed = jax.eval_shape(model.place_pools, pools)
    assert jax.tree_util.tree_structure(placed) \
        == jax.tree_util.tree_structure(pools)


def test_the_weights_and_the_programs_answer(served):
    _, model, variables = served
    params = jax.eval_shape(model.serving_params, variables)
    assert jax.tree_util.tree_leaves(params)
    pools = jax.eval_shape(lambda: model.init_block_pool(
        BLOCKS, BLOCK, jnp.float32, slots=SLOTS))
    i32 = jnp.int32
    out = jax.eval_shape(
        model.decode_step_paged, {"params": params},
        jax.ShapeDtypeStruct((SLOTS,), i32),
        jax.ShapeDtypeStruct((SLOTS,), i32), pools,
        jax.ShapeDtypeStruct((SLOTS, 16), i32))
    assert len(out) in (2, 3)
    logits, new_pools = out[:2]
    assert logits.shape == (SLOTS, model.cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert jax.tree_util.tree_structure(new_pools) \
        == jax.tree_util.tree_structure(pools)
    # the span reports: a dict each, whatever the model has to say
    if len(out) == 3:
        report = model.decode_aux_report(np.ones(out[2].shape, np.int32))
        assert report["moe_assignments"] > 0
        assert model.expert_matmul_form(params, SLOTS) in (
            "stream", "ragged_dot")
        assert model.prefill_span_args(16)["moe_assignments"] >= 16
    else:
        assert model.decode_aux_report(None) == {}
        assert model.expert_matmul_form(params, SLOTS) is None
        assert "moe_assignments" not in model.prefill_span_args(16)
    assert isinstance(model.prefill_span_args(16), dict)
    assert model.decode_attn_form() in ("rows", "heads")
    pos = np.array([5, 0], np.int32)
    table = np.zeros((SLOTS, 16), np.int32)
    table[0, :2] = (1, 2)
    read = model.decode_read_report(pos, table, BLOCK)
    assert isinstance(read, dict)
    rows = {"window_rows", "full_rows", "attended_rows"}
    # a model whose layers run several times says so beside the rows
    loop = {"ut_steps", "cache_entries", "weight_bytes_streamed"}
    assert set(read) in (set(), rows, rows | loop)
    assert all(isinstance(v, int) for v in read.values())
    health = model.health_report()
    assert isinstance(health, dict)
    assert set(health) == ({"ut_steps", "cache_entries"}
                           if loop <= set(read) else set())


def test_the_refusals_follow_from_the_cache_kinds(served):
    name, model, _ = served
    refusals = model.serving_refusals()
    kinds = set(model.cache_kinds())
    if kinds == {"table"}:
        # nothing is refused for what the pool holds: what is left is
        # quant.py's, tp.py's and the class's own
        assert model.kept_outside_blocks() is None
        assert set(refusals) == {
            "gpt2": set(), "gpt2_tp2": {"weight_dtype"},
            "mla_moe": {"weight_dtype", "tp", "speculative"},
            "loop_lm": {"weight_dtype", "tp", "role"}}[name]
        for option in ("speculative", "role"):
            assert refusals.get(option) \
                == type(model).unserved.get(option)
        model.check_serving_options(
            prefix_cache=True, spill=True,
            role="both" if "role" in refusals else "prefill")
        return
    # a ring and a state refuse the same four, each for its own reason
    assert set(refusals) == BLOCK_OPTIONS | {"weight_dtype", "tp"}
    kept = model.kept_outside_blocks()
    assert ("ring" in kept) == ("ring" in kinds)
    for option in BLOCK_OPTIONS:
        assert kept in refusals[option]
    asked = [(dict(prefix_cache=True), "prefix_cache=True"),
             (dict(spill=True), "spill=True"),
             (dict(role="prefill"), "role='prefill'"),
             (dict(role="decode"), "role='decode'"),
             (dict(speculative=True), "SpeculativeEngine"),
             (dict(weight_dtype="int8"), "weight_dtype='int8'"),
             (dict(tp=True), "tp_mesh")]
    for options, what in asked:
        with pytest.raises(NotImplementedError) as e:
            model.check_serving_options(**options)
        head, why = str(e.value).split(": ", 1)
        assert head == f"{type(model).__name__} does not serve with {what}"
        assert len(why) > 20
    model.check_serving_options()       # the defaults are served


def test_the_engine_takes_each_of_the_seven(served):
    name, model, variables = served
    real = jax.tree_util.tree_map(
        lambda leaf: jnp.zeros(leaf.shape, leaf.dtype), variables)
    eng = InferenceEngine(model, real, slots=SLOTS, max_len=64,
                          block_size=BLOCK, prefill_buckets=(16,),
                          prefix_cache=False)
    assert eng._cache_kinds == model.cache_kinds()
    assert len(eng._cache_kinds) == len(eng.pool)
    assert eng._ring_blocks == model.ring_blocks(BLOCK)
    assert eng._slot_state_bytes == model.slot_state_bytes(jnp.float32)
    assert eng.tp == model.tp
    assert ("expert_matmul" in eng.health()) == hasattr(model, "moe")
    assert model.health_report().items() <= eng.health().items()


class _Ducks:
    """The paged trio and nothing of the protocol."""

    def init_block_pool(self, num_blocks, block_size, dtype=jnp.float32,
                        slots=1):
        return ({"k": jnp.zeros((num_blocks, block_size, 8), dtype)},)

    def prefill_paged(self, *a):
        raise AssertionError("never traced")

    decode_step_paged = prefill_paged


def test_what_is_no_served_model_is_refused_by_name():
    with pytest.raises(TypeError, match="_Ducks is not a ServedModel"):
        InferenceEngine(_Ducks(), {"params": {}}, slots=1, max_len=16,
                        block_size=4)


class _Bare(_Ducks, ServedModel):
    """A model with nothing to say beyond its pool: the defaults."""


def test_the_defaults_are_a_table_only_model_with_nothing_to_report():
    bare = _Bare()
    assert bare.cache_kinds() == ("table",)
    assert bare.ring_blocks(BLOCK) == 0
    assert bare.slot_state_bytes() == 0 == bare.slot_state_bytes(jnp.float32)
    assert (bare.tp, bare.tp_axis) == (1, None)
    assert bare.serving_params({"params": {"w": 1}}) == {"w": 1}
    pools = bare.init_block_pool(3, BLOCK)
    assert bare.place_pools(pools) is pools
    assert bare.decode_attn_form() == "rows"
    assert bare.expert_matmul_form({}, 4) is None
    assert bare.prefill_span_args(16) == {} == bare.decode_aux_report(None)
    assert bare.decode_read_report(np.zeros(1), np.zeros((1, 4)), 4) == {}
    assert bare.health_report() == {}
    assert bare.kept_outside_blocks() is None
    # neither quant.py nor tp.py knows its leaves, and both say so
    assert set(bare.serving_refusals()) == {"weight_dtype", "tp"}
    with pytest.raises(NotImplementedError, match="_Bare does not serve "
                                                  "with tp_mesh: serving/tp"):
        bare.check_serving_options(tp=True)
    with pytest.raises(NotImplementedError, match="decode_step_paged"):
        ServedModel().decode_step_paged({}, None, None, (), None)
    with pytest.raises(NotImplementedError, match="init_block_pool"):
        ServedModel().cache_kinds()


def test_speculation_asks_the_same_table():
    model, variables = _built("granite_hybrid")
    real = jax.tree_util.tree_map(
        lambda leaf: jnp.zeros(leaf.shape, leaf.dtype), variables)

    def eng():
        return InferenceEngine(model, real, slots=SLOTS, max_len=64,
                               block_size=BLOCK, prefill_buckets=(16,),
                               prefix_cache=False)

    with pytest.raises(NotImplementedError) as e:
        SpeculativeEngine(eng(), eng(), k=2)
    assert model.serving_refusals()["speculative"] in str(e.value)


def _reaches_the_model(node):
    """getattr(model, ...) / hasattr(self.model, ...) / (eng.model, ...):
    a call of either whose first argument is a model."""
    if not (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
            and node.func.id in ("getattr", "hasattr") and node.args):
        return False
    first = node.args[0]
    return (isinstance(first, ast.Name) and first.id == "model") or (
        isinstance(first, ast.Attribute) and first.attr == "model")


@pytest.mark.parametrize("module", ["engine", "speculative"])
def test_the_engine_asks_a_model_nothing_through_getattr(module):
    path = os.path.join(REPO, "bigdl_tpu", "serving", f"{module}.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    found = [f"{module}.py:{node.lineno}" for node in ast.walk(tree)
             if _reaches_the_model(node)]
    assert not found
    # and the check sees one where there is one
    assert _reaches_the_model(ast.parse(
        "getattr(self.model, 'x', None)").body[0].value)
    assert _reaches_the_model(ast.parse("hasattr(model, 'x')").body[0].value)


def test_the_refusals_and_the_experts_report_are_written_once():
    defs = {}
    for root, _, files in os.walk(os.path.join(REPO, "bigdl_tpu")):
        for name in files:
            if not name.endswith(".py"):
                continue
            with open(os.path.join(root, name)) as f:
                tree = ast.parse(f.read())
            for node in ast.walk(tree):
                if isinstance(node, ast.FunctionDef):
                    defs.setdefault(node.name, []).append(name)
    assert defs["check_serving_options"] == ["protocol.py"]
    assert defs["serving_refusals"] == ["protocol.py"]
    # the protocol's default and the experts' one implementation
    assert sorted(defs["expert_matmul_form"]) == ["moe.py", "protocol.py"]
    assert sorted(defs["decode_aux_report"]) == ["moe.py", "protocol.py"]


@pytest.mark.parametrize("first", [
    "bigdl_tpu.models.transformer", "bigdl_tpu.models.hybrid_ssm",
    "bigdl_tpu.parallel.moe", "bigdl_tpu.serving.tp",
    "bigdl_tpu.serving.protocol"])
def test_a_model_imports_the_protocol_and_no_engine_imports_a_model(first):
    """`models/` imports `serving/protocol.py`, `serving/tp.py` imports
    `models/`: whichever a process imports first, it imports."""
    code = (f"import {first}; import sys; "
            "from bigdl_tpu.serving import TPServingLM, ServedModel; "
            "assert issubclass(TPServingLM, ServedModel); "
            "print('bigdl_tpu.serving.tp' in sys.modules)")
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO})
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == "True"
