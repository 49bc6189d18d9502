"""A serving round and a prefill hand their host operands to the program
in the call that launches it (PR 41): `_dispatch_and_fetch` passes its nine
NumPy arrays to `_decode_step`, `_prepare_seat` / `_admit_into` theirs to
`_prefill_step`, and neither calls `jnp.asarray` or `jax.device_put` for
them. On the CPU, tiny widths, one case a kind of cache the engine keeps:
table rows alone (`TransformerLM`), rings beside them (`WindowMoELM`: the
prefill takes `sources`), a per-slot state beside them (`CCAMoELM`).

What is held: no placement of the engine's own on either path; the tokens
are those of the same programs fed the parent's way, every host operand
placed first and the call made on placed arrays; placed or not, the
operands are one trace (`decode_traces` 1 for the first engine of a model,
0 for the second) and nothing compiles after the first round.
"""

import sys
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import obs
from bigdl_tpu.serving import InferenceEngine, Request
from bigdl_tpu.serving import engine as engine_mod
from test_cca_serving import SOURCE as STATE_SOURCE
from test_window_moe import SOURCE as RING_SOURCE

ROUNDS = 32
LAUNCHERS = ("_dispatch_and_fetch", "_admit_into", "_prepare_seat")


@pytest.fixture(autouse=True)
def _fresh_obs():
    prev = obs.set_enabled(True)
    obs.reset_all()
    yield
    obs.reset_all()
    obs.set_enabled(prev)


def _table_lm():
    from bigdl_tpu.models.transformer import build_lm

    m = build_lm(vocab_size=211, dim=32, num_heads=2, num_layers=2,
                 max_len=64)
    m.build(jax.random.PRNGKey(0))
    return m, None


def _ring_lm():
    from benchmarks.families import afmoe as fam

    return fam.program_model(RING_SOURCE), fam.make_variables(3, RING_SOURCE)


def _state_lm():
    from benchmarks.families import cca_moe as fam

    return (fam.program_model(STATE_SOURCE),
            fam.make_variables(3, STATE_SOURCE))


def _engine(model, variables):
    args = dict(slots=3, max_len=64, prefill_buckets=(16, 32), block_size=4)
    if variables is None:
        return InferenceEngine(model, **args)
    return InferenceEngine(model, variables, prefix_cache=False, **args)


def _requests():
    rng = np.random.RandomState(4)
    greedy, sampled = (rng.randint(1, 211, n).tolist() for n in (13, 21))
    return [Request(prompt=greedy, max_new_tokens=ROUNDS),
            Request(prompt=sampled, max_new_tokens=ROUNDS, temperature=0.8,
                    top_k=40, top_p=0.95, seed=7)]


def _compiles():
    fam = obs.get_registry().snapshot()["metrics"].get("xla_compiles_total")
    return sum(s["value"] for s in fam["series"]) if fam else 0


def _serve(eng):
    """Both requests seated in the first round, then a token a round.
    Returns (tokens by request, compiles after the first round, at the
    end)."""
    before = eng.stats
    for r in _requests():
        eng.submit(r)
    done = eng.step()
    assert eng.stats["prefill_calls"] == before["prefill_calls"] + 2
    warm = _compiles()
    while eng.stats["decode_steps"] < before["decode_steps"] + ROUNDS:
        done += eng.step()
    assert [r.status for r in done] == ["done", "done"]
    return {r.id: r.tokens for r in done}, warm, _compiles()


class _Placements:
    """`jnp.asarray` and `jax.device_put`, counted by who asked: `direct`
    where the caller is one of the engine's launch paths, `under` where one
    of them is anywhere up the stack (a program traced under them places
    constants of its own, so that one is read on warm rounds only)."""

    def __init__(self):
        self.direct, self.under, self.calls = [], [], 0

    def wrap(self, fn):
        def counted(*args, **kwargs):
            self.calls += 1
            frame, depth = sys._getframe(1), 0
            while frame is not None:
                code = frame.f_code
                if code.co_filename == engine_mod.__file__ and any(
                        name in code.co_qualname for name in LAUNCHERS):
                    if depth == 0:
                        self.direct.append(code.co_qualname)
                    self.under.append(code.co_qualname)
                    break
                frame, depth = frame.f_back, depth + 1
            return fn(*args, **kwargs)
        return counted


def _placed_first(step):
    """The parent's order of operations: every host array placed with a
    `jnp.asarray` of its own, then the call on placed arrays."""
    def call(model, params, pools, *operands):
        placed = jax.tree_util.tree_map(
            lambda a: jnp.asarray(a) if isinstance(a, np.ndarray) else a,
            operands)
        return step(model, params, pools, *placed)
    return call


@pytest.mark.parametrize("make", [_table_lm, _ring_lm, _state_lm],
                         ids=["table", "ring", "state"])
def test_a_round_and_a_prefill_place_nothing_of_their_own(make):
    model, variables = make()
    eng = _engine(model, variables)
    seen = _Placements()
    with mock.patch.object(jnp, "asarray", seen.wrap(jnp.asarray)), \
            mock.patch.object(jax, "device_put", seen.wrap(jax.device_put)):
        jnp.asarray(np.zeros(2))                    # the wrapper counts
        tokens, warm, end = _serve(eng)
        under_cold = len(seen.under)
        # every program is compiled by now: two more requests through the
        # same bucket and 32 more rounds, nothing under the launch paths
        again, _, end2 = _serve(eng)
    assert seen.calls >= 1 and seen.direct == []
    assert len(seen.under) == under_cold, seen.under[under_cold:]
    assert again.keys().isdisjoint(tokens)
    assert sorted(again.values()) == sorted(tokens.values())
    assert all(len(t) == ROUNDS for t in tokens.values())
    assert eng.stats["decode_traces"] == 1
    assert eng.stats["prefill_traces"] == 2         # a bucket a prompt
    assert warm == end == end2 > 0

    # the same programs fed the parent's way give the same tokens, and
    # placed operands are the trace the NumPy ones made
    fed = _engine(model, variables)
    with mock.patch.object(engine_mod, "_decode_step",
                           _placed_first(engine_mod._decode_step)), \
            mock.patch.object(engine_mod, "_prefill_step",
                              _placed_first(engine_mod._prefill_step)):
        parents, _, fed_end = _serve(fed)
    assert sorted(parents.values()) == sorted(tokens.values())
    assert fed.stats["decode_traces"] == 0
    assert fed.stats["prefill_traces"] == 0 and fed_end == end2
