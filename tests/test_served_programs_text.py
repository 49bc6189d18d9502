"""The lowered text of the six served families' decode and prefill
programs at toy sizes, pinned by its sha256.

The PREFILL hashes are the parent's parent's (ac7b450) still, letter for
letter: ISSUE 38 (the sigmoid router's path through `DroplessMoE.forward`,
`grouped_prompt_attention` cut out of `WindowMoELM`, the engine's third
cache kind) and ISSUE 39 (a third compiled size of the ragged decode read,
`ops/kv_cache._READ_SHARES`) left the prefill programs byte for byte what
they were: they do not call the ragged core. The DECODE hashes of the
programs that do call it (afmoe, mla_moe, and gpt2 at a width that takes
the rows form) are taken from ISSUE 39's change, on the parent ad46bad: one
more branch of the one `lax.switch` a layer. gpt2's toy decode (dim 32:
the head-split form, no ragged core) is ac7b450's still. The cca_moe and
granite_hybrid hashes (the "state" cache kind) are ISSUE 48's, taken from
its parent 883be96 before the protocol's moves were made. The loop_lm
hashes are ISSUE 49's own, the PR that brought the model (its parent has
none to take): they pin the looped programs for the PRs after it, and the
eleven above did not move under it.

To take the hashes of another checkout (the parent's, say), run this file
there: `cd <checkout> && PYTHONPATH=. python <this file>` prints them as
JSON; it imports of the checkout only what the parent has too.
"""

import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.getcwd() if __name__ == "__main__" else os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))
PARENTS = {
    ("afmoe", "decode"): "03580a61d64d741e",
    ("afmoe", "prefill"): "645e88e11c673841",
    ("mla_moe", "decode"): "c3fdc25b7d148b5a",
    ("mla_moe", "prefill"): "c208b75ce320454f",
    ("gpt2", "decode"): "4fb2103bb9d60ab3",
    ("gpt2", "prefill"): "cc70aade132de162",
    ("gpt2_rows", "decode"): "f15800e0430e101b",
    ("cca_moe", "decode"): "4853094ded5b6eee",
    ("cca_moe", "prefill"): "bc05c35bc0b08507",
    ("granite_hybrid", "decode"): "7805fb1a4f471208",
    ("granite_hybrid", "prefill"): "3cf0aebf9a3e4bb9",
    ("loop_lm", "decode"): "0720d75c11b74fd3",
    ("loop_lm", "prefill"): "01e1f27897b61758",
}


def _vec(dtype, *shape):
    return jax.ShapeDtypeStruct(shape, dtype)


def _toy(family):
    """(model, params, pools, the prefill's block ids) of a family's toy
    size, as shapes: 3 slots of 64 positions in blocks of 4."""
    i32 = jnp.int32
    if family in ("gpt2", "gpt2_rows"):
        from bigdl_tpu.models.transformer import build_lm
        from bigdl_tpu.serving import InferenceEngine

        # dim 128 over 2 heads decodes in the rows form (the ragged core)
        lm = build_lm(vocab_size=61, dim=32 if family == "gpt2" else 128,
                      num_heads=2, num_layers=2, max_len=64)
        lm.build(jax.random.PRNGKey(0))
        eng = InferenceEngine(lm, slots=3, max_len=64, block_size=4,
                              prefill_buckets=(16, 32))
        return (eng.model, jax.eval_shape(lambda: eng._params),
                jax.eval_shape(lambda: eng.pool), _vec(i32, 8))
    import importlib

    fam = importlib.import_module(f"benchmarks.families.{family}")
    tiny = f"tiny_{family}/configs/tiny-{family.replace('_', '-')}.json"
    with open(os.path.join(REPO, "tests", "bench", tiny)) as f:
        cfg = json.load(f)
    model = fam.program_model(cfg)
    params = jax.eval_shape(lambda: fam.make_variables(5, cfg)["params"])
    if family in ("mla_moe", "loop_lm"):    # tables only: plain block ids
        return (model, params, jax.eval_shape(
            lambda: model.init_block_pool(33, 4, jnp.float32)), _vec(i32, 8))
    pools = jax.eval_shape(
        lambda: model.init_block_pool(33, 4, jnp.float32, slots=3))
    if family in ("cca_moe", "granite_hybrid"):
        return model, params, pools, {
            "table": _vec(i32, 8),
            "state": {"slot": _vec(i32), "keep": _vec(i32)}}
    return model, params, pools, {
        "table": _vec(i32, 8),
        "ring": {"slot": _vec(i32),
                 "sources": _vec(i32, model.ring_blocks(4))}}


def lowered_hash(family, program):
    from bigdl_tpu.serving import engine as eng

    model, params, pools, ids = _toy(family)
    i32, f32, slots, per_slot = jnp.int32, jnp.float32, 3, 16
    if program == "decode":
        lowered = eng._decode_step.lower(
            model, params, pools, _vec(i32, slots), _vec(i32, slots),
            _vec(i32, slots), _vec(i32, slots), _vec(f32, slots),
            _vec(i32, slots), _vec(f32, slots), _vec(jnp.bool_, slots),
            _vec(i32, slots, per_slot))
    else:
        lowered = eng._prefill_step.lower(
            model, params, pools, _vec(i32, 1, 32), _vec(i32), ids,
            _vec(i32, 1, per_slot))
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]


@pytest.mark.parametrize("family,program", list(PARENTS),
                         ids=["-".join(k) for k in PARENTS])
def test_a_served_program_lowers_to_the_parents_text(family, program):
    assert lowered_hash(family, program) == PARENTS[family, program]


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(REPO, "tests", "bench"))
    print(json.dumps({"-".join(k): lowered_hash(*k) for k in PARENTS},
                     indent=1))
