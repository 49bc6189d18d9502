"""What the TPU compiler makes of the sampler (ISSUE 37): a row with a
small top-k is filtered from its candidates, so the decode program's
sampler must hold a TopK custom call, and its one sort of the
vocabulary must stand inside a branch of a conditional (the full-sort
class), never where every step runs it.

Compiled ahead of time for a described `v5e:2x2` (no chip attached; the
TPU compiler is installed) at the largest vocabulary a cell serves,
64 rows of 200,192. A compile that passes is not a chip run. libtpu is
touched only inside the `topo` fixture (one process at a time may load
it; a module that touches it while being imported breaks the collection
under several workers), and the tests skip where no topology can be
described.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bigdl_tpu.serving.sampler import MAX_CANDIDATES, sample_logits

SLOTS, VOCAB = 64, 200192


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 - any failure means: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_cache():
    """An AOT compile for an absent chip can be written to the persistent
    cache but not read back; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def computations(topo, no_cache):
    """{name: body} of the optimised module's computations, and the
    entry computation's name."""
    one_chip = SingleDeviceSharding(topo.devices[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = jax.jit(sample_logits).lower(
        spec((SLOTS, VOCAB), jnp.float32), spec((SLOTS, 2), jnp.uint32),
        spec((SLOTS,), jnp.float32), spec((SLOTS,), jnp.int32),
        spec((SLOTS,), jnp.float32)).compile().as_text()
    heads = list(re.finditer(
        r"^(ENTRY )?%?([\w.\-]+) \([^\n]*\{\n", text, re.M))
    comps, entry = {}, None
    for head, nxt in zip(heads, heads[1:] + [None]):
        comps[head.group(2)] = text[head.end():
                                    nxt.start() if nxt else len(text)]
        if head.group(1):
            entry = head.group(2)
    assert entry is not None, "no ENTRY computation in the module"
    return comps, entry


def _called(body: str):
    """Names of the computations an instruction list calls."""
    names = set()
    for m in re.finditer(
            r"(?:calls|to_apply|body|condition|true_computation|"
            r"false_computation)=%?([\w.\-]+)", body):
        names.add(m.group(1))
    for m in re.finditer(r"branch_computations=\{([^}]*)\}", body):
        names.update(n.strip().lstrip("%") for n in m.group(1).split(","))
    return names


def _conditional_depth(comps, entry):
    """{computation: the fewest conditionals whose branches lie between
    the entry computation and it}."""
    depth, todo = {entry: 0}, [entry]
    while todo:
        name = todo.pop()
        for line in comps.get(name, "").splitlines():
            d = depth[name] + (" conditional(" in line)
            for callee in _called(line):
                if callee in comps and d < depth.get(callee, 1 << 30):
                    depth[callee] = d
                    todo.append(callee)
    return depth


def _sorted_widths(body: str):
    """The row width of every sort in an instruction list."""
    return [int(m.group(1)) for m in re.finditer(
        r"= \(?\w+\[\d+,(\d+)\][^\n=]*\bsort\(", body)]


def test_the_candidates_are_one_topk_custom_call(computations):
    """One TopK, and not over the vocabulary: over the 128 pieces of 128
    whose maxima are the row's largest (`sampler._largest`)."""
    comps, _ = computations
    calls = [l for body in comps.values() for l in body.splitlines()
             if 'custom_call_target="TopK"' in l]
    assert len(calls) == 1, calls
    assert f"f32[{SLOTS},{MAX_CANDIDATES}]" in calls[0], calls
    assert f"[{SLOTS},{VOCAB}]" not in calls[0], calls


def test_the_only_sort_of_the_vocabulary_stands_inside_a_branch(
        computations):
    """Never in the entry computation, and not in the branch that every
    sampling step takes either: under the all-greedy conditional AND
    under one of its own, which only a full-sort row turns on. What the
    candidates' branch sorts is the pieces' maxima (the compiler's
    lowering of a `lax.top_k` this narrow), 1/128 of a row."""
    comps, entry = computations
    depth = _conditional_depth(comps, entry)
    widths = {n: _sorted_widths(body) for n, body in comps.items()}
    whole = {n for n, w in widths.items() if VOCAB in w}
    assert whole, "the full-sort class has lost its sort"
    assert not widths[entry]
    assert all(depth[n] >= 2 for n, w in widths.items() if w), {
        n: (depth[n], w) for n, w in widths.items() if w}
    topk = {n for n, body in comps.items()
            if 'custom_call_target="TopK"' in body}
    assert not (topk & whole), "candidates and sort share a branch"
    assert all(w <= -(-VOCAB // 128) for n in topk for w in widths[n]), {
        n: widths[n] for n in topk}
