"""The sampler filters a row from its top-k candidates (ISSUE 37): what
must not move when `lax.top_k` stands where the sort of the vocabulary
stood. Held against a float64 NumPy reference of the same mathematics
(top-k, then top-p over the renormalised survivors, always the top-1)
and against the full-sort branch, which is the code every row took
before."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.serving import sampler
from bigdl_tpu.serving.sampler import (MAX_CANDIDATES, SAMPLER_PATHS,
                                       filter_logits, row_classes,
                                       sample_logits, step_path)

K = MAX_CANDIDATES
# a cumulative mass this close to top_p may fall on either side of it
# when the same float32 terms are added in another order
MARGIN = 1e-5


def _keys(n, seed=0):
    return jax.vmap(jax.random.PRNGKey)(
        jnp.arange(seed, seed + n, dtype=jnp.int32))


def _tempered(logits, temperature):
    """The float32 row the filters see, as the program computes it."""
    return np.asarray(jnp.asarray(logits, jnp.float32) / jnp.maximum(
        jnp.asarray(temperature, jnp.float32), 1e-6)[:, None])


def reference_support(lt_row, top_k, top_p, margin=MARGIN):
    """(support mask (V,), near): one row in float64. `near` says that a
    cumulative mass lies within `margin` of top_p: the cut may then
    rightly fall to either side of that token."""
    lt = lt_row.astype(np.float64)
    desc = np.sort(lt)[::-1]
    keep = np.ones_like(desc, bool)
    if top_k > 0:
        keep = desc >= desc[min(top_k, len(desc)) - 1]   # ties stay
    if top_p >= 1:                                       # nucleus off
        return lt >= desc[keep].min(), False
    p = np.where(keep, np.exp(desc - desc[0]), 0.0)
    p /= p.sum()
    before = np.cumsum(p) - p
    kept = keep & ((before < top_p) | (np.arange(len(desc)) == 0))
    near = bool((np.abs(before[keep][1:] - top_p) < margin).any())
    return lt >= desc[kept].min(), near


def _mixed_knobs(b, rng):
    """Knobs of all four classes in one batch, by row index mod 4:
    greedy, unfiltered, candidates, full sort."""
    temp = np.where(np.arange(b) % 4 == 0, 0.0,
                    rng.choice([0.7, 0.8, 1.0, 1.3], b)).astype(np.float32)
    top_k = np.zeros(b, np.int32)
    top_p = np.ones(b, np.float32)
    cand = np.arange(b) % 4 == 2
    top_k[cand] = rng.choice([1, 2, 5, 40, 64, K - 1, K], cand.sum())
    top_p[cand] = rng.choice([0.3, 0.9, 0.95, 1.0], cand.sum())
    full = np.arange(b) % 4 == 3
    top_k[full] = rng.choice([0, 0, K + 1, 500], full.sum())
    top_p[full] = np.where(top_k[full] > 0,
                           rng.choice([0.9, 1.0], full.sum()),
                           rng.choice([0.05, 0.2], full.sum()))
    return temp, top_k, top_p


@pytest.mark.parametrize("vocab", [50257, 200192])
def test_support_equals_the_float64_reference(vocab):
    b = 64
    rng = np.random.default_rng(vocab)
    logits = (rng.standard_normal((b, vocab)) * 2.0).astype(np.float32)
    temp, top_k, top_p = _mixed_knobs(b, rng)
    classes = [np.asarray(m) for m in
               row_classes(temp, top_k, top_p, vocab)]
    assert all(m.sum() == b // 4 for m in classes)
    filt = np.asarray(jax.jit(filter_logits)(
        logits, temp, top_k, top_p))
    lt = _tempered(logits, temp)
    left_out = 0
    for i in range(b):
        got = filt[i] > -1e29
        if classes[0][i]:
            continue            # greedy: sample_logits takes the argmax
        np.testing.assert_array_equal(filt[i][got], lt[i][got])
        want, near = reference_support(lt[i], top_k[i], top_p[i])
        if near:
            left_out += 1
        else:
            assert (got == want).all(), (
                i, top_k[i], top_p[i], got.sum(), want.sum())
    assert left_out < 0.01 * b + 1
    tokens = np.asarray(jax.jit(sample_logits)(
        logits, _keys(b, 3), temp, top_k, top_p))
    np.testing.assert_array_equal(tokens[classes[0]],
                                  logits.argmax(-1)[classes[0]])
    assert all(filt[i, tokens[i]] > -1e29
               for i in range(b) if not classes[0][i])


def test_tokens_equal_the_full_sort_paths_on_4096_rows():
    """temperature 0.8 / top-k 40 / top-p 0.95, every cell's sampled
    half: the token from the candidates is the token from the sorted
    row, same key, on every row whose cut is not within the margin.

    The sorted row is the full-sort branch's: `_prefix_threshold` over
    all V values in descending order. NumPy sorts them here (XLA's CPU
    sort takes 13 ms a row of 50,257, a minute for this test); the first
    chunk holds that route to `_full_sort_threshold`, bit for bit."""
    vocab, chunk, chunks = 50257, 128, 32

    @jax.jit
    def both(logits, desc, keys, temp, top_k, top_p):
        lt = logits / jnp.maximum(temp, 1e-6)[:, None]
        thr = sampler._prefix_threshold(desc, top_k, top_p)
        parent = sampler._gumbel_argmax(
            jnp.where(lt >= thr[:, None], lt, -1e30), keys)
        mass = jnp.cumsum(jax.nn.softmax(desc[:, :40], axis=-1), axis=-1)
        near = jnp.any(jnp.abs(mass - top_p[:, None]) < MARGIN, axis=-1)
        return (sample_logits(logits, keys, temp, top_k, top_p),
                parent.astype(jnp.int32), near, thr)

    temp = np.full(chunk, 0.8, np.float32)
    top_k = jnp.full((chunk,), 40, jnp.int32)
    top_p = jnp.full((chunk,), 0.95, jnp.float32)
    compared = left_out = 0
    tokens = set()
    logits = np.empty((chunk, vocab), np.float32)
    for c in range(chunks):
        rng = np.random.default_rng(370000 + c)
        rng.standard_normal(out=logits, dtype=np.float32)
        logits *= 2.5
        desc = np.sort(_tempered(logits, temp), axis=-1)[:, ::-1]
        new, parent, near, thr = (np.asarray(a) for a in both(
            logits, desc, _keys(chunk, 1000 * c), temp, top_k, top_p))
        if c == 0:
            np.testing.assert_array_equal(thr, np.asarray(
                jax.jit(sampler._full_sort_threshold)(
                    _tempered(logits, temp), top_k, top_p)))
        np.testing.assert_array_equal(new[~near], parent[~near])
        compared += int((~near).sum())
        left_out += int(near.sum())
        tokens.update(zip(range(chunk), new.tolist()))
    assert compared + left_out == 4096
    assert left_out < 0.01 * 4096
    # the noise had a choice: the rows did not all take their top-1
    assert len(tokens) > 4 * chunk


@pytest.mark.parametrize("vocab", [128 * 128, 128 * 129, 50257, 200192])
def test_the_candidates_of_a_long_row_are_its_largest_values(vocab):
    """`_largest` searches a long row by pieces of 128 (the K pieces
    with the largest maxima hold the K largest values): bit for bit
    NumPy's sorted head, on rows made to break a search by pieces. At
    128 x 128 the pieces are no more than K and `lax.top_k` reads the
    row; one piece more and the search is by pieces; 50,257 is padded."""
    rng = np.random.default_rng(vocab)
    rows = (rng.standard_normal((8, vocab)) * 2.0).astype(np.float32)
    rows[1, 4096:4096 + 200] += 30.0        # the K largest in two pieces
    rows[2] = np.round(rows[2])             # ties across every piece
    rows[3] = 0.5                           # nothing but ties
    rows[4, ::2] = -np.inf                  # half the row masked out
    rows[5, -3:] = 40.0                     # the best in the padded piece
    rows[6, :K] += 30.0                     # the K largest in ONE piece
    want = np.sort(rows, axis=-1)[:, ::-1][:, :K]
    got = np.asarray(jax.jit(sampler._largest, static_argnums=1)(rows, K))
    np.testing.assert_array_equal(got, want)
    few = np.asarray(jax.jit(sampler._largest, static_argnums=1)(rows, 7))
    np.testing.assert_array_equal(few, want[:, :7])


@pytest.mark.parametrize("beside", ["greedy", "full_sort", "alone"])
def test_a_candidates_rows_token_is_its_own(beside):
    """Bitwise the same beside 63 greedy rows, beside a row that turns
    the full-sort branch on, and in a batch of one."""
    vocab, b = 50257, 64
    rng = np.random.default_rng(11)
    logits = (rng.standard_normal((b, vocab)) * 2.0).astype(np.float32)
    keys = _keys(b, 500)
    temp = np.full(b, 0.8, np.float32)
    top_k = np.full(b, 40, np.int32)
    top_p = np.full(b, 0.95, np.float32)
    sample = jax.jit(sample_logits)
    want = np.asarray(sample(logits, keys, temp, top_k, top_p))
    assert len(set(want.tolist())) > 8      # the rows do sample
    if beside == "alone":
        for i in (0, 17, 63):
            got = np.asarray(sample(
                logits[i:i + 1], keys[i:i + 1], temp[i:i + 1],
                top_k[i:i + 1], top_p[i:i + 1]))
            assert got[0] == want[i]
        return
    for i in (0, 17, 63):
        t, k, p = np.zeros(b, np.float32), top_k.copy(), top_p.copy()
        t[i] = temp[i]
        if beside == "full_sort":
            j = (i + 1) % b
            t[j], k[j], p[j] = 1.0, 0, 0.9
            assert step_path(t, k, p, vocab) == "full_sort"
        else:
            assert step_path(t, k, p, vocab) == "candidates"
        got = np.asarray(sample(logits, keys, t, k, p))
        assert got[i] == want[i]


@pytest.mark.parametrize("vocab", [4, 20, 100])
@pytest.mark.parametrize("which_k", ["one", "K", "K_plus_1"])
def test_small_vocabularies_and_the_edges_of_k(vocab, which_k):
    """V < 128: K is the vocabulary; top_k = K + 1 takes the full sort
    and agrees with the reference all the same."""
    k_here = min(K, vocab)
    k = {"one": 1, "K": k_here, "K_plus_1": k_here + 1}[which_k]
    b = 32
    rng = np.random.default_rng(vocab * 7 + k)
    logits = (rng.standard_normal((b, vocab)) * 1.5).astype(np.float32)
    temp = np.full(b, 0.9, np.float32)
    top_k = np.full(b, k, np.int32)
    top_p = rng.choice([0.6, 0.9, 1.0], b).astype(np.float32)
    assert step_path(temp, top_k, top_p, vocab) == (
        "full_sort" if which_k == "K_plus_1" else "candidates")
    filt = np.asarray(filter_logits(logits, temp, top_k, top_p))
    lt = _tempered(logits, temp)
    for i in range(b):
        want, near = reference_support(lt[i], k, top_p[i])
        if not near:
            assert ((filt[i] > -1e29) == want).all(), (i, top_p[i])
    tokens = np.asarray(sample_logits(logits, _keys(b, 9), temp,
                                      top_k, top_p))
    assert all(filt[i, tokens[i]] > -1e29 for i in range(b))
    if which_k == "one":
        np.testing.assert_array_equal(tokens, logits.argmax(-1))


def test_the_edges_of_k_at_a_served_vocabulary():
    """top_k = 1, K and K + 1 in one batch at V = 50,257: the last row
    is of the full-sort class, and all three agree with the reference."""
    vocab = 50257
    rng = np.random.default_rng(5)
    logits = (rng.standard_normal((3, vocab)) * 2.0).astype(np.float32)
    temp = np.full(3, 1.0, np.float32)
    top_k = np.asarray([1, K, K + 1], np.int32)
    top_p = np.asarray([0.9, 0.99, 0.99], np.float32)
    _, _, cand, full = row_classes(temp, top_k, top_p, vocab)
    assert cand.tolist() == [True, True, False]
    assert full.tolist() == [False, False, True]
    filt = np.asarray(filter_logits(logits, temp, top_k, top_p))
    lt = _tempered(logits, temp)
    for i in range(3):
        want, near = reference_support(lt[i], top_k[i], top_p[i])
        assert not near
        assert ((filt[i] > -1e29) == want).all()


@pytest.mark.parametrize("bad", ["nan", "all_equal"])
def test_an_unsound_row_harms_no_neighbour(bad):
    vocab, b = 1000, 8
    rng = np.random.default_rng(3)
    logits = (rng.standard_normal((b, vocab)) * 2.0).astype(np.float32)
    keys = _keys(b, 70)
    temp = np.full(b, 0.8, np.float32)
    top_k = np.asarray([40, 40, 0, 40, 0, K + 1, 40, 40], np.int32)
    top_p = np.asarray([.95, .95, 1., .95, .9, .95, .95, .95], np.float32)
    want = np.asarray(sample_logits(logits, keys, temp, top_k, top_p))
    for row in (1, 2, 4):       # a candidates, an unfiltered, a sorted row
        hurt = logits.copy()
        hurt[row] = np.nan if bad == "nan" else 0.25
        got = np.asarray(sample_logits(hurt, keys, temp, top_k, top_p))
        assert 0 <= got[row] < vocab
        others = np.arange(b) != row
        np.testing.assert_array_equal(got[others], want[others])
    if bad == "all_equal":
        # every token ties at the k-th value: all stay, as a sort's
        # `>= kth` kept them
        hurt = logits.copy()
        hurt[1] = 0.25
        filt = np.asarray(filter_logits(hurt, temp, top_k, top_p))
        assert (filt[1] > -1e29).all()


def test_host_and_program_class_a_row_alike():
    """The ONE predicate over NumPy arrays (the engine's count) and
    over traced operands (the program's branches), on a grid."""
    temps = [-1.0, 0.0, 1e-3, 0.8, 2.0]
    ks = [-1, 0, 1, 40, 99, 100, 101, K - 1, K, K + 1, 50257, 10 ** 6]
    ps = [0.0, 0.5, 0.95, 1.0, 1.5]
    grid = np.asarray([(t, k, p) for t in temps for k in ks for p in ps])
    temp = grid[:, 0].astype(np.float32)
    top_k = grid[:, 1].astype(np.int32)
    top_p = grid[:, 2].astype(np.float32)
    for vocab in (4, 100, 50257):
        host = row_classes(temp, top_k, top_p, vocab)
        prog = jax.jit(row_classes, static_argnums=3)(
            jnp.asarray(temp), jnp.asarray(top_k), jnp.asarray(top_p),
            vocab)
        for h, d in zip(host, prog):
            assert isinstance(h, np.ndarray)
            np.testing.assert_array_equal(h, np.asarray(d))
        assert (np.sum(host, axis=0) == 1).all()    # one class a row
        greedy, unfiltered, candidates, full_sort = host
        np.testing.assert_array_equal(greedy, temp <= 0)
        k_here = min(K, vocab)
        np.testing.assert_array_equal(
            candidates, (temp > 0) & (top_k >= 1) & (top_k <= k_here))
        np.testing.assert_array_equal(
            full_sort, (temp > 0) & ((top_k > k_here)
                                     | ((top_k <= 0) & (top_p < 1))))
        # a step's word is its costliest row's; none seated is greedy
        for n in range(len(temp)):
            word = step_path(temp[:n + 1], top_k[:n + 1], top_p[:n + 1],
                             vocab)
            seen = [m[:n + 1].any() for m in host]
            assert word == [w for w, s in zip(SAMPLER_PATHS, seen)
                            if s][-1]
    assert step_path(np.zeros(4, np.float32), np.full(4, 40, np.int32),
                     np.full(4, 0.9, np.float32), 50257) == "greedy"
