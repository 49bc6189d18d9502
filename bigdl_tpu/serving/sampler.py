"""Token sampling — greedy / temperature / top-k / top-p (nucleus).

Everything is per-ROW arrays, not Python scalars: a continuous-batching
step serves requests with different sampling configs in one launch, so
temperature/top_k/top_p ride inside the jitted decode step as (B,)
operands — changing a request's knobs never retraces
(bigdl_tpu/serving/engine.py's zero-mid-stream-recompile contract).

Conventions: temperature <= 0 → greedy (argmax); top_k <= 0 → top-k off;
top_p >= 1 → nucleus off. Filters compose the standard way: top-k first,
then top-p over the renormalized survivors, then categorical sampling
via per-row Gumbel-max.

What a row costs is decided by its own three numbers (`row_classes`,
the ONE predicate: the program runs it on the operands, the engine on
its host copies of them to count the step's path), never by an option
or by the model:

  greedy      temperature <= 0 (a released slot is one): the argmax.
  unfiltered  top_k <= 0 and top_p >= 1: temperature and Gumbel only.
  candidates  1 <= top_k <= K, K = min(128, V), any top_p: the row is
              filtered from its K largest logits (`_largest`: exactly
              `lax.top_k`'s, found in the K pieces of 128 whose maxima
              are largest), never from a sort of the vocabulary: the
              k-th of them is the top-k threshold, and the top-p prefix
              is a softmax and a cumulative sum over those of the K
              that pass it.
  full sort   the rest (top-p without top-k, or top_k > K): one sort of
              the row, inside a `lax.cond` that runs only when some
              sampling row is of this class.

A candidates row is ALWAYS computed from its candidates, whether or not
a co-batched row turns the sort on, so its token depends on that row
alone. Either way the filter ends in one LOGIT threshold a row and the
Gumbel noise is drawn over the whole vocabulary by token id: for a
given key the noise at every id is what it was when every row was
sorted, and so is the support wherever no cumulative mass lies within
float rounding of top_p (the K-wide and the V-wide sums add the same
non-zero terms in another order). One limit: ties AT the k-th value
that reach past the K candidates stay in the support (`lt >= kth`
keeps them, as the sort did) but not in the top-p mass.

Poison plumb-through (the serving reliability contract,
serving/engine.py): every op here is strictly per-ROW, so a NaN/inf
logits row — real or injected via the engine's (B,) poison operand —
yields a garbage-but-defined token for THAT row only (argmax over
all-NaN is index 0; NaN comparisons are False throughout the filter)
and cannot perturb any co-batched row. The engine discards the token:
the per-row finite flag (utils/anomaly.rows_finite) is computed on the
logits BEFORE sampling and rides back beside the tokens, turning the
row into a 'poisoned' eviction with no extra host sync.

Coupling property (ISSUE 15, the speculative-decoding operand):
`sample_logits` is a PURE FUNCTION of (logits, key) — no carried
sampler state, no global RNG — and the engine derives each key as
fold_in(PRNGKey(request.seed), output_index). So the token the target
emits at output index n is fully determined by (target logits at n,
key_n), whoever computes it: a speculative verify row that holds the
target's logits for position n and the same fold_in key reproduces
the target-only token BITWISE, greedy and sampled alike. The draft
proposes with the SAME keys over its own logits (common random
numbers — a well-matched draft's sample agrees often), acceptance is
proposal == target-sample equality, and the emitted stream is the
target sampler's verbatim — exactness by construction rather than by
the classic rejection-sampling argument (which is exact only in
distribution and would break the repo's bitwise discipline).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

_NEG_INF = -1e30
# the candidates a row's filter is computed from: top_k up to this many
# never sorts the vocabulary (K = min(MAX_CANDIDATES, V) in a program)
MAX_CANDIDATES = 128
SAMPLER_PATHS = ("greedy", "unfiltered", "candidates", "full_sort")
# the width of the pieces a long row's candidates are searched by: the
# lanes of a TPU tile, which divide 200,192 and 129,280
_PIECE = 128


def row_classes(temperature, top_k, top_p, vocab: int):
    """(greedy, unfiltered, candidates, full_sort): four bool masks,
    one True a row. Plain comparisons, so NumPy arrays (the engine's
    host copies) and traced ones (the program's operands) class a row
    the same way."""
    greedy = temperature <= 0
    candidates = ~greedy & (top_k >= 1) & (
        top_k <= min(MAX_CANDIDATES, vocab))
    unfiltered = ~greedy & (top_k <= 0) & (top_p >= 1)
    return greedy, unfiltered, candidates, \
        ~(greedy | unfiltered | candidates)


def step_path(temperature, top_k, top_p, vocab: int) -> str:
    """The costliest class among a step's rows, as a word of
    SAMPLER_PATHS: what the decode program's sampler runs for them
    (host arrays; a released slot's temperature is 0, so it is
    greedy)."""
    masks = row_classes(temperature, top_k, top_p, vocab)
    for path, mask in zip(SAMPLER_PATHS[::-1], masks[::-1]):
        if mask.any():
            return path
    return SAMPLER_PATHS[0]


def _prefix_threshold(desc: jax.Array, top_k: jax.Array,
                      top_p: jax.Array) -> jax.Array:
    """The logit (B,) at which a row's filter cuts, from `desc` (B, N):
    the row's N largest tempered logits in descending order (N = V: the
    sorted row). top-k: threshold at the k-th largest value per row.
    top-p over the top-k survivors: keep the smallest prefix of the
    descending-prob order whose mass reaches top_p — and ALWAYS the
    top-1, so a degenerate top_p <= 0 means "maximally greedy", not
    "all masked → uniform noise". Softmax is order-preserving, so the
    descending probabilities come from softmax of `desc`; masked-by-k
    entries lie at its tail, so zero their probs before the cumsum
    instead of re-softmaxing. The cutoff is carried back to the
    unsorted row as a LOGIT threshold — desc holds exact copies of
    lt's values, so `lt >= threshold` is an exact comparison; a
    probability threshold would compare two independently computed
    softmaxes, whose ~1-ULP disagreement can empty the support. The
    kept prefix lies inside the top-k survivors, so the one threshold
    stands for both filters."""
    n = desc.shape[-1]
    kth = jnp.take_along_axis(
        desc, jnp.clip(top_k - 1, 0, n - 1)[:, None], axis=-1)  # (B, 1)
    desc_keep = (top_k[:, None] <= 0) | (desc >= kth)
    sp = jnp.where(desc_keep, jax.nn.softmax(
        jnp.where(desc_keep, desc, _NEG_INF), axis=-1), 0.0)
    csum = jnp.cumsum(sp, axis=-1)
    keep_sorted = ((csum - sp) < top_p[:, None]) \
        | (jnp.arange(n)[None, :] == 0)
    return jnp.min(
        jnp.where(keep_sorted & desc_keep, desc, jnp.inf), axis=-1)


def _largest(lt: jax.Array, k: int) -> jax.Array:
    """Each row's k largest values (B, k) in descending order: what
    `lax.top_k(lt, k)` returns, taken in two steps where the row is
    long, because the TPU's TopK reads a row at a few values a
    nanosecond (5.2 ms for 64 x 200,192; PERF.md §6, PR 37). The row is
    cut into pieces of _PIECE; any of its k largest values lies in one
    of the k pieces whose maxima are largest (a value in another piece
    has k values no smaller before it, those maxima), so the k largest
    of those k pieces are the row's. Exact, ties included: equal values
    are interchangeable here, only values are returned."""
    b, v = lt.shape
    pieces = -(-v // _PIECE)
    if pieces <= k:
        return lax.top_k(lt, k)[0]
    rows = jnp.pad(lt, ((0, 0), (0, pieces * _PIECE - v)),
                   constant_values=-jnp.inf).reshape(b, pieces, _PIECE)
    _, best = lax.top_k(rows.max(axis=-1), k)                   # (B, k)
    held = jax.vmap(lambda row, ids: row[ids])(rows, best)  # (B, k, 128)
    return lax.top_k(held.reshape(b, k * _PIECE), k)[0]


def _candidates_threshold(lt: jax.Array, top_k: jax.Array,
                          top_p: jax.Array) -> jax.Array:
    """A candidates row's threshold: its K largest values are the head
    of its sorted row, and with 1 <= top_k <= K every survivor is
    among them."""
    return _prefix_threshold(
        _largest(lt, min(MAX_CANDIDATES, lt.shape[-1])), top_k, top_p)


def _full_sort_threshold(lt: jax.Array, top_k: jax.Array,
                         top_p: jax.Array) -> jax.Array:
    """A full-sort row's threshold: ONE sort of the vocabulary."""
    return _prefix_threshold(jnp.sort(lt, axis=-1)[:, ::-1], top_k, top_p)


def filter_logits(logits: jax.Array, temperature: jax.Array,
                  top_k: jax.Array, top_p: jax.Array) -> jax.Array:
    """Temperature-scale then mask logits (B, V) to the top-k / top-p
    support per row; masked entries at -1e30. Exposed separately so
    tests can assert the support set without sampling. A row pays for
    its class (the module docstring's table); a greedy row comes back
    unmasked, `sample_logits` takes its argmax instead."""
    lt = logits.astype(jnp.float32) / jnp.maximum(
        temperature, 1e-6)[:, None]
    _, _, candidates, full_sort = row_classes(
        temperature, top_k, top_p, logits.shape[-1])
    off = jnp.full(lt.shape[:1], -jnp.inf, jnp.float32)
    thr_c = lax.cond(jnp.any(candidates), _candidates_threshold,
                     lambda *_: off, lt, top_k, top_p)
    thr_s = lax.cond(jnp.any(full_sort), _full_sort_threshold,
                     lambda *_: off, lt, top_k, top_p)
    thr = jnp.where(candidates, thr_c, jnp.where(full_sort, thr_s, off))
    return jnp.where(lt >= thr[:, None], lt, _NEG_INF)


def _gumbel_argmax(filt: jax.Array, keys: jax.Array) -> jax.Array:
    """Categorical sample (B,) of masked logits by per-row Gumbel-max;
    the noise is drawn over the whole vocabulary, by token id."""
    gumbel = jax.vmap(
        lambda k, row: -jnp.log(-jnp.log(
            jax.random.uniform(k, row.shape, jnp.float32,
                               minval=1e-20, maxval=1.0))))(keys, filt)
    return jnp.argmax(filt + gumbel, axis=-1)


def sample_logits(logits: jax.Array, keys: jax.Array,
                  temperature: jax.Array, top_k: jax.Array,
                  top_p: jax.Array) -> jax.Array:
    """Next-token ids (B,) int32. `keys`: per-row PRNG keys (B, 2) —
    per-request streams, so a request samples identically whichever
    slot or co-batch it lands in (the batcher-equivalence property).
    Rows with temperature <= 0 take the plain argmax (untempered,
    unfiltered — greedy ignores the knobs). When EVERY row is greedy,
    a lax.cond skips the filter+Gumbel work entirely — greedy-only
    decode steps pay only the argmax."""
    greedy = jnp.argmax(logits, axis=-1)

    def sample_branch(_):
        sampled = _gumbel_argmax(
            filter_logits(logits, temperature, top_k, top_p), keys)
        return jnp.where(temperature <= 0, greedy, sampled)

    out = lax.cond(jnp.all(temperature <= 0),
                   lambda _: greedy, sample_branch, operand=None)
    return out.astype(jnp.int32)
