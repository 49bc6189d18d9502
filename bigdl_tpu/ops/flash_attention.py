"""Flash attention — Pallas (Mosaic) TPU kernel with online softmax.

The reference has no attention at all (SURVEY.md §5.7: sequence handling
is unrolled-BPTT `nn/Recurrent.scala` only, bounded by one node's memory).
Long-context attention is this framework's TPU-first extension of that
subsystem, and the hot op is a real Pallas kernel — the TPU-native
counterpart of the reference's hand-tuned native MKL-DNN primitives
(SURVEY.md §2.1 native checklist).

Design
------
* Forward (`flash_fwd`): a grid cell holds the query and key rows of
  one batch-head in VMEM (the whole sequence up to `_MAX_CELL_ROWS`)
  and walks their (block_q, block_k) tiles ITSELF: for each q tile the
  segments of kv tiles it sees, each one update of an f32 accumulator
  and running max / running sum (the online softmax, in base 2), so HBM
  traffic is O(S·D) and nothing of size S×S ever materializes. Under a
  causal mask the walk visits only the tiles at or under the diagonal
  and masks only those that straddle it; the grid itself (bh, q cells,
  kv cells) is for longer sequences, and its index maps repeat the last
  block a q cell needs, so a skipped cell fetches nothing. QK^T and P·V
  run on the MXU via `dot_general` on native-dtype operands with f32
  accumulation.
* Backward (`flash_bwd_fused`): ONE kernel of the same shape, kv tiles
  outermost, recomputing probabilities from the saved log-sum-exp;
  dk/dv per kv tile, dq into a full-sequence f32 plane that stays in
  VMEM. Past the cap on that plane: two kernels (`flash_bwd_dq`,
  `flash_bwd_dkv`) that walk the grid one tile a cell. (A blockwise
  `lax.scan` XLA backward remains for impl="xla".)
* Operands cross HBM at the head's own width where that is 64 or a
  multiple of 128 (any other is padded to the lanes), and lse / delta
  as compact (bh, 1, seq) planes.
* `flash_attention_plan` is the ONE place the tiles, the cells and the
  backward's form are chosen, from the call's static shapes; it also
  counts how far the causal skip engages (`kv_tiles_visited` of
  `kv_tiles_total`). The chip table behind its constants:
  scripts/flash_attention_table.py (PERF.md §6, PR 50).
* The same math is exposed as `attention_reference` (jnp oracle for
  tests, CPU fallback), and `flash_attention_with_lse` returns the
  (out, lse) pair that the ring-attention combine consumes
  (bigdl_tpu/parallel/ring_attention.py).

Numerics: masked logits use a large finite negative (-1e30), not -inf,
so fully-masked rows produce zeros (not NaN) after normalization — the
convention the ring combine relies on.

Env tile overrides (`BIGDL_FLASH_FWD_TILES` / `BIGDL_FLASH_BWD_TILES`:
the size of a TILE) are snapshotted at IMPORT via utils/envknobs —
never read at trace time, so the value in the environment when
`bigdl_tpu` is imported wins and later env mutations are visibly inert
(graftlint `trace-env-read` guards the class). A sweep passes explicit
`block_q` / `block_k` / `bwd_tiles` (scripts/flash_attention_table.py
calls the kernels' wrappers with its tiles and cells), or sets the env
before the process starts.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from bigdl_tpu.ops.pallas_names import named_pallas_call
from bigdl_tpu.utils import envknobs

_NEG_INF = -1e30
_LOG2E = 1.4426950408889634  # MUST match between _bwd_recompute (s2) and _bwd_prep (lse2)
_LN2 = 0.6931471805599453

# The chip table's findings (scripts/flash_attention_table.py on a v5e, 128
# x 1,024 x 64 bfloat16 causal; PERF.md §6, PR 50). A sequence of up to
# _MAX_CELL_ROWS rows is ONE grid cell a batch-head, whose walk is static:
# at 1,024 / 2,048 / 4,096 rows that beat cells of 1,024 walked under
# program_id's predicates by 2.0 / 1.7 / 1.6x forward and 1.2 / 1.3 /
# 1.1x backward. A longer one takes cells of _LONG_CELL_ROWS, ONE tile a
# cell: at 16 x 8,192 x 64 the walk inside such a cell lost to the
# parent's grid of whole tiles (forward 512-row tiles 2.78 ms, 1,024 2.58,
# the parent 2.79; backward 256 6.91, 512 4.84, 1,024 4.74, the parent
# 5.03). One cell of all 8,192 rows read 2.04 / 4.23 there, for a minute
# of compile a kernel (the walk is unrolled): not taken.
_MAX_CELL_ROWS = 4096
_LONG_CELL_ROWS = 1024
# The forward pays some 0.03 ms a SEGMENT at that shape (its matmuls and
# its softmax take turns), so few wide ones win though they visit more:
# 512-row q tiles 0.34 ms (12 of 16 tiles), 256 0.48 (10 of 16), 1,024
# 0.39 (the parent's whole-sequence tile, padded: 0.58); the kv width of
# a tile moves nothing (a run without a mask is one update however long).
# The backward goes by the area it visits: 256 x 256 0.83 ms, 128 x 128
# 0.84, 512 x 512 0.93 (the parent's 512 x 1,024: 1.15).
_FWD_BLOCK = 512
_BWD_BLOCK = 256
# The split backward walks the grid itself, one tile a cell: few, large
_SPLIT_BWD_BLOCK = 1024


# --------------------------------------------------------------------------
# jnp oracle / CPU fallback
# --------------------------------------------------------------------------

def attention_reference(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    return_lse: bool = False,
    dropout: float = 0.0,
    dropout_rng: Optional[jax.Array] = None,
):
    """Plain softmax attention. q,k,v: (..., S, D); returns (..., S, D).

    Numeric oracle for the Pallas kernel and the non-TPU fallback.
    Materializes S×S — fine for tests and short sequences. `dropout`
    applies inverted dropout to the attention probabilities (the one
    path that needs them materialized; flash never does).
    """
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("...qd,...kd->...qk", q, k).astype(jnp.float32) * sm_scale
    if causal:
        q_len, k_len = s.shape[-2], s.shape[-1]
        row = lax.broadcasted_iota(jnp.int32, (q_len, k_len), 0)
        col = lax.broadcasted_iota(jnp.int32, (q_len, k_len), 1)
        s = jnp.where(col <= row + (k_len - q_len), s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    probs = p / l
    # fully-masked rows (possible when causal and seq_q > seq_k) emit
    # zeros, matching the kernel's convention
    probs = jnp.where(m > _NEG_INF / 2, probs, 0.0)
    if dropout > 0.0:
        if dropout_rng is None:
            raise ValueError("attention dropout needs dropout_rng")
        keep = 1.0 - dropout
        mask = jax.random.bernoulli(dropout_rng, keep, probs.shape)
        probs = jnp.where(mask, probs, 0.0) / keep
    out = jnp.einsum("...qk,...kd->...qd", probs.astype(v.dtype), v)
    if return_lse:
        lse = (m + jnp.log(l))[..., 0]
        return out, lse
    return out


# --------------------------------------------------------------------------
# The walk over the score square — ONE arithmetic for the kernels (traced
# program_id scalars, or Python ints where the grid has one cell on an
# axis) and for flash_attention_plan (Python ints)
# --------------------------------------------------------------------------

def _tiles_upto(x, size, hi):
    """min(hi, max(x, 0) // size): the whole `size`-row tiles at or
    before x."""
    if isinstance(x, int):
        return min(hi, max(x, 0) // size)
    return jnp.minimum(hi, lax.div(jnp.maximum(x, 0), jnp.int32(size)))


def _clip(x, lo, hi):
    if all(isinstance(a, int) for a in (x, lo, hi)):
        return min(max(x, lo), hi)
    return jnp.clip(x, lo, hi)


def _kv_tile_range(q_start, block_q, block_k, seq_q, seq_k, causal):
    """(full, reach) for the q tile whose first row is `q_start`: kv
    tiles [0, full) lie wholly inside what every row of it sees and take
    no mask; [full, reach) straddle the diagonal or the end of seq_k and
    take one; from `reach` on every score is masked: never visited."""
    n_k = -(-seq_k // block_k)
    whole = seq_k // block_k
    if not causal:
        return whole, n_k
    # bottom-right alignment: query i sees keys <= i + seq_k - seq_q
    last = q_start + (seq_k - seq_q)       # the tile's first row's last key
    return (_tiles_upto(last + 1, block_k, whole),
            _tiles_upto(last + block_q - 1 + block_k, block_k, n_k))


def _q_tile_range(k_start, block_q, block_k, seq_q, seq_k, causal):
    """(reach, full_lo, full_hi) for the kv tile whose first column is
    `k_start`, the backward's view of the same walk: q tiles before
    `reach` lie wholly above the diagonal and are never visited;
    [full_lo, full_hi) take no mask; the rest of [reach, n_q) does."""
    n_q = -(-seq_q // block_q)
    if causal:
        first = k_start - (seq_k - seq_q)  # the first row that sees k_start
        reach = _tiles_upto(first, block_q, n_q)
        full_lo = _tiles_upto(first + block_k - 1 + block_q - 1, block_q,
                              n_q)
    else:
        reach = full_lo = 0
    full_hi = seq_q // block_q
    if seq_k % block_k:
        # the kv tile that holds the padded columns is masked throughout
        inside = k_start + block_k <= seq_k
        full_hi = (full_hi if inside else 0) if isinstance(inside, bool) \
            else jnp.where(inside, full_hi, 0)
    return reach, full_lo, full_hi


def _segments(visit, plain, n, maskable):
    """How a cell walks `n` tiles of which [visit[0], visit[1]) are
    visible at all and [plain[0], plain[1]) of those need no mask:
    (first tile, tiles, masked, when) segments, each ONE update of the
    accumulators over all its tiles at once (an update has a price of
    its own, whatever its width).

    Static bounds (a grid of one cell): the exact three runs, the plain
    one merged however long. Traced bounds (program_id): tile by tile,
    each emitted plain and masked under the predicates that pick one of
    them or neither. `maskable` False (no diagonal, no padding): nothing
    can need a mask, and no masked form is emitted."""
    (v_lo, v_hi), (p_lo, p_hi) = visit, plain
    if all(isinstance(x, int) for x in (v_lo, v_hi, p_lo, p_hi)):
        out = [(lo, hi - lo, masked, True) for lo, hi, masked in
               ((v_lo, p_lo, True), (p_lo, p_hi, False), (p_hi, v_hi, True))
               if hi > lo]
    else:
        out = []
        for t in range(n):
            whole = (p_lo <= t) & (p_hi > t)
            some = (v_lo <= t) & (v_hi > t)
            out += [(t, 1, False, whole),
                    (t, 1, True, some & jnp.logical_not(whole))]
    return [s for s in out if maskable or not s[2]]


def _when(cond, fn):
    if isinstance(cond, bool):
        if cond:
            fn()
    else:
        pl.when(cond)(fn)


def _rows(i, size, tiles=1):
    """The rows of `tiles` tiles of `size` from tile `i` on, as a ref's
    slice."""
    if isinstance(i, int):
        return pl.ds(i * size, tiles * size)
    return pl.ds(pl.multiple_of(i * size, size), tiles * size)


def _visible(shape, q_start, k_start, seq_q, seq_k, causal, kv_padded,
             q_padded):
    """Mask of a TRANSPOSED tile of scores (kv positions from `k_start`
    down the rows, queries from `q_start` along the columns): only the
    terms the static facts ask for (`q_padded`: the backward sums over
    queries, so the padded ones must contribute nothing)."""
    k_idx = lax.broadcasted_iota(jnp.int32, shape, 0)
    q_idx = lax.broadcasted_iota(jnp.int32, shape, 1)
    terms = []
    if causal:
        # bottom-right alignment: query i sees keys <= i + seq_k - seq_q
        terms.append(k_idx - q_idx <= q_start + (seq_k - seq_q) - k_start)
    if kv_padded:
        terms.append(k_idx < seq_k - k_start)
    if q_padded:
        terms.append(q_idx < seq_q - q_start)
    return functools.reduce(jnp.logical_and, terms)


# --------------------------------------------------------------------------
# Pallas forward kernel
# --------------------------------------------------------------------------

def _fa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
               *, sm_scale, causal, block_q, block_k, seq_q, seq_k,
               grid_q, grid_k):
    """One grid cell holds `cell_q` query rows and `cell_k` key rows of a
    batch-head and walks them itself: a static loop over its q tiles,
    and for each the segments of kv tiles it sees (_segments) — the run
    that needs no mask in one update however wide, the tiles on the
    diagonal in another.

    Scores are computed TRANSPOSED (kv rows x q columns), as the
    backward's: the softmax reduces over sublanes, its statistics are
    lane-dense ROWS (block_q / 128 registers an update where a column of
    them is block_q / 8, which was most of the kernel's time), lse
    leaves as the row it is, and the accumulator is out^T = v^T·p^T,
    turned once a q tile at the end."""
    cell_q, cell_k = q_ref.shape[1], k_ref.shape[1]
    qi = pl.program_id(1) if grid_q > 1 else 0
    ki = pl.program_id(2) if grid_k > 1 else 0
    n_sub = cell_k // block_k
    t0 = ki * n_sub                      # the cell's first kv tile
    scale2 = sm_scale * _LOG2E           # softmax in base 2, as the backward
    kv_padded = seq_k % block_k != 0

    def _init(at=slice(None)):
        m_scr[:, at] = jnp.full_like(m_scr[:, at], _NEG_INF)
        l_scr[:, at] = jnp.zeros_like(l_scr[:, at])
        acc_scr[:, at] = jnp.zeros_like(acc_scr[:, at])

    # where the walk is static (one kv cell, bounds free of program_id)
    # a row's first update overwrites its statistics: nothing to clear
    static = grid_k == 1 and (grid_q == 1 or not causal)
    if not static:
        _when(ki == 0, _init)

    for u in range(cell_q // block_q):
        at = pl.ds(u * block_q, block_q)
        q_start = qi * cell_q + u * block_q
        # NATIVE-dtype operands (bf16 on the training path) with f32 MXU
        # accumulation; the scale applies to the f32 scores
        q = q_ref[0, at, :]                                  # (bq, D)

        def _update(first, width, masked, fresh, q=q, at=at,
                    q_start=q_start):
            cols = _rows(first, block_k, width)
            k = k_ref[0, cols, :]                            # (w, D)
            v = v_ref[0, cols, :]
            st = lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
            st = st * scale2                                 # (w, bq)
            if masked:
                mask = _visible(st.shape, q_start, (t0 + first) * block_k,
                                seq_q, seq_k, causal, kv_padded, False)
                st = jnp.where(mask, st, _NEG_INF)
            m_new = jnp.max(st, axis=0, keepdims=True)       # (1, bq)
            if not fresh:
                m_prev = m_scr[:, at]
                m_new = jnp.maximum(m_prev, m_new)
            pt = jnp.exp2(st - m_new)
            if masked:
                # _NEG_INF is finite: in a row with nothing visible yet
                # exp2(s - m_new) == 1, and the row would emit mean(V)
                # instead of the zeros the ring combine relies on
                pt = jnp.where(mask, pt, 0.0)
            l_new = jnp.sum(pt, axis=0, keepdims=True)
            acc = lax.dot_general(
                v, pt.astype(v.dtype), (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)          # (D, bq)
            if not fresh:
                alpha = jnp.exp2(m_prev - m_new)
                l_new = alpha * l_scr[:, at] + l_new
                acc = alpha * acc_scr[:, at] + acc
            m_scr[:, at], l_scr[:, at], acc_scr[:, at] = m_new, l_new, acc

        full, reach = _kv_tile_range(q_start, block_q, block_k, seq_q,
                                     seq_k, causal)
        full = _clip(full - t0, 0, n_sub)
        reach = _clip(reach - t0, full, n_sub)
        segments = _segments((0, reach), (0, full), n_sub,
                             causal or kv_padded)
        if static and not segments:
            _init(at)                    # rows that see no key at all
        for n, (first, width, masked, when) in enumerate(segments):
            _when(when, functools.partial(_update, first, width, masked,
                                          static and n == 0))

    def _finalize():
        for u in range(cell_q // block_q):
            at = pl.ds(u * block_q, block_q)
            l = l_scr[:, at]                                 # (1, bq)
            safe_l = jnp.where(l == 0.0, 1.0, l)
            o_ref[0, at, :] = (acc_scr[:, at] / safe_l).T.astype(o_ref.dtype)
            lse = m_scr[:, at] * _LN2 + jnp.log(safe_l)
            lse_ref[0, :, at] = jnp.where(l == 0.0, _NEG_INF, lse)

    _when(ki == grid_k - 1, _finalize)


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _head_pad(dim: int) -> int:
    """Lanes of zero padding the head dimension takes in HBM: none where
    it is 64 (a block whose last dimension is the array's is a legal
    Mosaic block, and the MXU pass is half-filled either way) or a
    multiple of 128; any other width is padded up to one."""
    return 0 if dim == 64 else (-dim) % 128


def _cell(block: int, seq: int) -> int:
    """Rows a grid cell holds: the whole sequence (padded to its tiles)
    up to _MAX_CELL_ROWS; of a longer one, `block` times the largest
    divisor of its tile count within _LONG_CELL_ROWS, so it is padded to
    its tiles and no further."""
    tiles = -(-seq // block)
    if tiles * block <= _MAX_CELL_ROWS:
        return tiles * block
    return block * max(d for d in range(1, tiles + 1) if tiles % d == 0
                       and (d == 1 or d * block <= _LONG_CELL_ROWS))


def _last_kv_cell(i, cell_q, cell_k, seq_q, seq_k, grid_k, causal):
    """The last kv cell that q cell `i` visits: index maps repeat it for
    the cells past it, so a skipped cell fetches nothing."""
    if not causal:
        return grid_k - 1
    reach = (i + 1) * cell_q - 1 + (seq_k - seq_q)
    return jnp.clip(lax.div(jnp.maximum(reach, 0), jnp.int32(cell_k)), 0,
                    grid_k - 1)


def _flash_fwd_pallas(q, k, v, causal, sm_scale, block_q, block_k,
                      cell_q, cell_k, interpret):
    """q,k,v: (BH, S, D) → (out (BH, S, D), lse (BH, S)). `block_*` are
    the tiles of the walk, `cell_*` the rows a grid cell holds (the
    plan's, from _cell)."""
    from jax.experimental.pallas import tpu as pltpu

    bh, seq_q, dim = q.shape
    seq_k = k.shape[1]

    pad = _head_pad(dim)
    qp = _pad_to(_pad_to(q, 1, block_q), 2, dim + pad)
    kp = _pad_to(_pad_to(k, 1, block_k), 2, dim + pad)
    vp = _pad_to(_pad_to(v, 1, block_k), 2, dim + pad)
    sq, dp = qp.shape[1], qp.shape[2]
    sk = kp.shape[1]
    grid_q, grid_k = sq // cell_q, sk // cell_k

    kernel = functools.partial(
        _fa_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_k=block_k, seq_q=seq_q, seq_k=seq_k, grid_q=grid_q,
        grid_k=grid_k)

    def kv_map(b, i, j):
        return (b, jnp.minimum(j, _last_kv_cell(
            i, cell_q, cell_k, seq_q, seq_k, grid_k, causal)), 0)

    out_p, lse_p = named_pallas_call(
        "flash_fwd",
        kernel,
        grid=(bh, grid_q, grid_k),
        # bh and q cells are independent; only the kv sweep carries the
        # online-softmax scratch
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        in_specs=[
            pl.BlockSpec((1, cell_q, dp), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, cell_k, dp), kv_map),
            pl.BlockSpec((1, cell_k, dp), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, cell_q, dp), lambda b, i, j: (b, i, 0)),
            # compact: (1, cell_q) matches or 128-tiles the (1, sq) plane
            pl.BlockSpec((1, 1, cell_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, dp), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, cell_q), jnp.float32),
            pltpu.VMEM((1, cell_q), jnp.float32),
            pltpu.VMEM((dp, cell_q), jnp.float32),
        ],
        interpret=interpret,
    )(qp, kp, vp)
    return out_p[:, :seq_q, :dim], lse_p[:, 0, :seq_q]


# --------------------------------------------------------------------------
# Pallas backward kernels (dq; dk/dv) — recompute-from-lse flash backward
# --------------------------------------------------------------------------

def _bwd_recompute(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   q_start, k_start, sm_scale, causal, block_q, block_k,
                   seq_q, seq_k):
    """The split kernels' shared recompute chain: (q, k, do, p, ds) for
    one (q_block, kv_block) tile — p from the saved lse, ds from delta.
    `q` comes back UNSCALED (dk needs it that way).

    All dots take NATIVE-dtype operands with f32 MXU accumulation (the
    library-kernel convention); q/k/do come back in native dtype and
    p/ds in f32 — callers cast p/ds to the operand dtype at their dots.
    A pre-dot f32 cast would force multi-pass f32 MXU mode (~3-6x
    slower on v5e).

    VPU-chain economies of the SPLIT kernels, which still walk the grid
    one masked tile a cell: (1) p is computed in base 2 — _bwd_prep pre-multiplies
    lse by log2(e) and the s tile is scaled once by sm_scale·log2(e),
    so `exp2` needs no hidden ×log2(e) tile op; (2) `do` is pre-scaled
    by sm_scale at tile load (a (bq,D) op) and delta arrives pre-scaled
    from _bwd_prep, so ds = p·(dp′−delta′) drops its ×sm_scale tile op.
    Consequence for callers: the returned `do` is SCALED — dv
    accumulators must be divided by sm_scale once at finalize."""
    q = q_ref[0]                                             # (bq, D)
    k = k_ref[0]                                             # (bk, D)
    s2 = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32) \
        * (sm_scale * _LOG2E)
    lse2 = lse_ref[0, 0, pl.dslice(q_start, block_q)][:, None]
    delta = delta_ref[0, 0, pl.dslice(q_start, block_q)][:, None]
    row = q_start + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    col = k_start + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    # padded q rows must contribute nothing (dk/dv accumulate over rows)
    mask = (col < seq_k) & (row < seq_q)
    if causal:
        mask = mask & (col <= row + (seq_k - seq_q))
    p = jnp.where(mask, jnp.exp2(s2 - lse2), 0.0)            # (bq, bk)
    if sm_scale == 0.0:  # degenerate static case: ds is exactly zero
        do = do_ref[0]
        ds = jnp.zeros_like(p)
        return q, k, do, p, ds
    do = (do_ref[0].astype(jnp.float32)
          * sm_scale).astype(do_ref.dtype)                   # (bq, D)
    dp = lax.dot_general(do, v_ref[0],
                         (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32)
    ds = p * (dp - delta)
    return q, k, do, p, ds


def _fa_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dq_scr, *, sm_scale, causal, block_q,
                      block_k, seq_q, seq_k, num_kv):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    q_start = qi * block_q
    k_start = ki * block_k

    def _compute():
        _, k, _, _, ds = _bwd_recompute(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, q_start,
            k_start, sm_scale, causal, block_q, block_k, seq_q, seq_k)
        dq_scr[:] = dq_scr[:] + lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        @pl.when(k_start <= q_start + block_q - 1 + (seq_k - seq_q))
        def _():
            _compute()
    else:
        _compute()

    @pl.when(ki == num_kv - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _fa_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       dk_ref, dv_ref, dk_scr, dv_scr, *, sm_scale,
                       causal, block_q, block_k, seq_q, seq_k, num_q):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    q_start = qi * block_q
    k_start = ki * block_k

    def _compute():
        q, _, do, p, ds = _bwd_recompute(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, q_start,
            k_start, sm_scale, causal, block_q, block_k, seq_q, seq_k)
        dv_scr[:] = dv_scr[:] + lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # (bk, D)
        # dk = ds^T @ q_unscaled
        dk_scr[:] = dk_scr[:] + lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        # q blocks entirely above the diagonal contribute nothing
        @pl.when(q_start + block_q - 1 + (seq_k - seq_q) >= k_start)
        def _():
            _compute()
    else:
        _compute()

    @pl.when(qi == num_q - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        # do arrived pre-scaled by sm_scale (see _bwd_recompute)
        inv = 1.0 / sm_scale if sm_scale != 0.0 else 1.0
        dv_ref[0] = (dv_scr[:] * inv).astype(dv_ref.dtype)


def _bwd_prep(q, k, v, o, lse, do, block_q, block_k, sm_scale):
    """Shared backward setup (fused AND split wrappers): pad the
    sequences to their tiles (and a head width that needs it to the
    lanes), precompute delta = sum(do*o), and lay lse and delta out as
    the compact (BH, 1, sq) planes the kernels read.

    lse ships PRE-MULTIPLIED by log2(e) and delta PRE-MULTIPLIED by
    sm_scale — the per-tile VPU economies _bwd_recompute documents."""
    width = q.shape[2] + _head_pad(q.shape[2])
    qp = _pad_to(_pad_to(q, 1, block_q), 2, width)
    dop = _pad_to(_pad_to(do, 1, block_q), 2, width)
    kp = _pad_to(_pad_to(k, 1, block_k), 2, width)
    vp = _pad_to(_pad_to(v, 1, block_k), 2, width)

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1) * sm_scale                      # (BH, Sq)
    # (BH, 1, sq): Mosaic wants the last two block dims (8,128)-tileable
    # OR equal to the array dims — (1, sq) matches exactly
    lse_p = _pad_to(lse.astype(jnp.float32) * _LOG2E,
                    1, block_q)[:, None, :]
    delta_p = _pad_to(delta, 1, block_q)[:, None, :]
    return qp, kp, vp, dop, lse_p, delta_p


def _fa_bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr,
                         *, sm_scale, causal, block_q, block_k, seq_q,
                         seq_k, grid_q, grid_k):
    """Single-pass backward over a (bh, kv cells, q cells) grid: dk/dv
    per kv tile, with dq accumulated IN the same pass.

    What makes one pass legal under Mosaic's output-revisit semantics:
    dq's output block is the WHOLE (seq, D) row plane with index map
    (b, 0, 0) — it never changes within a batch-head, so it stays
    resident in VMEM and is flushed once per bh; every tile adds its
    ds·k to the rows of a full-sequence f32 scratch.

    A cell holds `cell_q` query rows and `cell_k` key rows and walks
    them itself: a static loop over its kv tiles and, for each, the
    segments of q tiles at or under the diagonal (_segments) — the ones
    that straddle it masked, the run past them in one update however
    long. Scores are computed TRANSPOSED
    (kv rows x q columns), so lse and delta broadcast as the rows they
    arrive as and dv = p^T·do, dk = ds^T·q are plain matmuls; only dq
    contracts a transposed operand.

    The scale rides on v (once a kv tile): dp' = do·(sm_scale·v)^T and
    the pre-scaled delta give ds' = sm_scale·ds directly, so dq and dk
    need no further factor and dv = p^T·do none at all."""
    cell_q, cell_k = q_ref.shape[1], k_ref.shape[1]
    ki = pl.program_id(1) if grid_k > 1 else 0
    qi = pl.program_id(2) if grid_q > 1 else 0
    n_sub = cell_q // block_q
    u0 = qi * n_sub                      # the cell's first q tile
    scale2 = sm_scale * _LOG2E

    def _init_dq():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    first_q = (qi == 0)
    _when(first_q if grid_k == 1 else first_q & (ki == 0), _init_dq)

    def _init_dkv():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    _when(first_q, _init_dkv)

    kv_padded, q_padded = seq_k % block_k != 0, seq_q % block_q != 0

    for j in range(cell_k // block_k):
        cols = pl.ds(j * block_k, block_k)
        k_start = ki * cell_k + j * block_k
        k = k_ref[0, cols, :]                                # (bk, D)
        v = v_ref[0, cols, :]
        vs = (v.astype(jnp.float32) * sm_scale).astype(v.dtype)

        def _update(first, width, masked, k=k, vs=vs, cols=cols,
                    k_start=k_start):
            here = _rows(first, block_q, width)              # in the cell
            q = q_ref[0, here, :]                            # (w, D)
            do = do_ref[0, here, :]
            q_start = (u0 + first) * block_q
            at = _rows(u0 + first, block_q, width)           # in the sequence
            lse2 = lse_ref[0, :, at]                         # (1, w)
            delta = delta_ref[0, :, at]
            st = lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
            pt = jnp.exp2(st * scale2 - lse2)                # (bk, w)
            if masked:
                pt = jnp.where(_visible(
                    pt.shape, q_start, k_start, seq_q, seq_k, causal,
                    kv_padded, q_padded), pt, 0.0)
            dpt = lax.dot_general(vs, do, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
            dst = (pt * (dpt - delta)).astype(q.dtype)       # (bk, w)
            dv_scr[cols] = dv_scr[cols] + lax.dot_general(
                pt.astype(do.dtype), do, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)          # (bk, D)
            dk_scr[cols] = dk_scr[cols] + lax.dot_general(
                dst, q, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dq_scr[at, :] = dq_scr[at, :] + lax.dot_general(
                dst, k, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)          # (w, D)

        reach, full_lo, full_hi = _q_tile_range(
            k_start, block_q, block_k, seq_q, seq_k, causal)
        reach = _clip(reach - u0, 0, n_sub)
        full_lo = _clip(full_lo - u0, reach, n_sub)
        full_hi = _clip(full_hi - u0, full_lo, n_sub)
        for first, width, masked, when in _segments(
                (reach, n_sub), (full_lo, full_hi), n_sub,
                causal or kv_padded or q_padded):
            _when(when, functools.partial(_update, first, width, masked))

    def _finalize_dkv():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)

    _when(qi == grid_q - 1, _finalize_dkv)

    # the rows of q cell i have every contribution once the LAST kv
    # sweep has passed them
    def _finalize_dq():
        at = _rows(qi, cell_q)
        dq_ref[0, at, :] = dq_scr[at, :].astype(dq_ref.dtype)

    _when(ki == grid_k - 1, _finalize_dq)


def _flash_bwd_pallas_fused(q, k, v, o, lse, do, causal, sm_scale,
                            block_q, block_k, cell_q, cell_k, interpret):
    """One-kernel Mosaic backward (see _fa_bwd_fused_kernel); the plan
    sends a sequence whose dq plane would crowd VMEM to the two-kernel
    form instead."""
    from jax.experimental.pallas import tpu as pltpu

    bh, seq_q, dim = q.shape
    seq_k = k.shape[1]
    qp, kp, vp, dop, lse_p, delta_p = _bwd_prep(
        q, k, v, o, lse, do, block_q, block_k, sm_scale)
    sq, dp_ = qp.shape[1], qp.shape[2]
    sk = kp.shape[1]
    grid_q, grid_k = sq // cell_q, sk // cell_k

    def first_q_cell(j):
        # the first q cell that kv cell j's walk reaches: index maps
        # repeat it for the cells above the diagonal, which fetch nothing
        if not causal:
            return 0
        first = j * cell_k - (seq_k - seq_q)
        return jnp.clip(lax.div(jnp.maximum(first, 0), jnp.int32(cell_q)),
                        0, grid_q - 1)

    def q_map(b, j, i):
        return (b, jnp.maximum(i, first_q_cell(j)), 0)

    dq_p, dk_p, dv_p = named_pallas_call(
        "flash_bwd_fused",
        functools.partial(
            _fa_bwd_fused_kernel, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, seq_q=seq_q, seq_k=seq_k,
            grid_q=grid_q, grid_k=grid_k),
        grid=(bh, grid_k, grid_q),
        # the full-sequence dq residents exceed Mosaic's default 16 MiB
        # scoped-vmem budget at long context (18.1 MiB at S=16384 with
        # native-dtype dots); v5e has 128 MiB — raise the kernel's cap.
        # Only bh is parallel: the dq plane persists across kv AND q.
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024,
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        in_specs=[
            pl.BlockSpec((1, cell_q, dp_), q_map),                    # q
            pl.BlockSpec((1, cell_k, dp_), lambda b, j, i: (b, j, 0)),  # k
            pl.BlockSpec((1, cell_k, dp_), lambda b, j, i: (b, j, 0)),  # v
            pl.BlockSpec((1, cell_q, dp_), q_map),                    # do
            pl.BlockSpec((1, 1, sq), lambda b, j, i: (b, 0, 0)),      # lse
            pl.BlockSpec((1, 1, sq), lambda b, j, i: (b, 0, 0)),      # delta
        ],
        out_specs=[
            # whole dq row plane per bh: index map constant in (j, i),
            # so the block is flushed once per batch-head
            pl.BlockSpec((1, sq, dp_), lambda b, j, i: (b, 0, 0)),
            pl.BlockSpec((1, cell_k, dp_), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, cell_k, dp_), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, dp_), q.dtype),
            jax.ShapeDtypeStruct((bh, sk, dp_), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, dp_), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((sq, dp_), jnp.float32),
                        pltpu.VMEM((cell_k, dp_), jnp.float32),
                        pltpu.VMEM((cell_k, dp_), jnp.float32)],
        interpret=interpret,
    )(qp, kp, vp, dop, lse_p, delta_p)

    return (dq_p[:, :seq_q, :dim], dk_p[:, :seq_k, :dim],
            dv_p[:, :seq_k, :dim])


# Above this, the fused kernel's full-sequence VMEM residents (f32 dq
# scratch + dq output block in q.dtype, at the 128 lanes a row takes in
# VMEM whatever the head width) would crowd VMEM; use the two-kernel
# backward instead. 13 MiB admits bf16 S=16384 (12.6 MiB resident) and
# sends f32 S=16384 (16.8 MiB) to the split form.
_FUSED_BWD_MAX_RESIDENT_BYTES = 13 * 1024 * 1024


def resolve_bwd_form(seq_q: int, head_dim: int, itemsize: int,
                     block_q: int = _BWD_BLOCK) -> str:
    """'fused' | 'split': which Mosaic backward a shape routes to: the
    resident-bytes gate that flash_attention_plan applies, on the dq
    plane of `seq_q` padded to the fused backward's q tile `block_q`.
    Past the cap `bwd_tiles` and its env snapshot do NOT apply: the
    split backward tiles at the forward's explicit tiles, else at
    _SPLIT_BWD_BLOCK."""
    sq_padded = -(-seq_q // block_q) * block_q
    dp_padded = -(-head_dim // 128) * 128
    resident = sq_padded * dp_padded * (4 + itemsize)
    return "fused" if resident <= _FUSED_BWD_MAX_RESIDENT_BYTES \
        else "split"


def _flash_bwd_pallas(q, k, v, o, lse, do, causal, sm_scale, plan,
                      interpret):
    """The Mosaic backward in the form and at the tiles `plan` names."""
    if plan.bwd_form == "fused":
        return _flash_bwd_pallas_fused(
            q, k, v, o, lse, do, causal, sm_scale, plan.bwd_block_q,
            plan.bwd_block_k, plan.bwd_cell_q, plan.bwd_cell_k, interpret)
    return _flash_bwd_pallas_split(q, k, v, o, lse, do, causal, sm_scale,
                                   plan.bwd_block_q, plan.bwd_block_k,
                                   interpret)


def _flash_bwd_pallas_split(q, k, v, o, lse, do, causal, sm_scale,
                            block_q, block_k, interpret):
    """Flash backward as two Mosaic kernels: dq over a (bh, q, kv) grid,
    dk/dv over a (bh, kv, q) grid, both recomputing probabilities from
    the forward's log-sum-exp (nothing S×S in HBM). The form of the
    sequences whose dq plane would crowd VMEM; its walk is the grid's
    own (one tile a cell, the cells above the diagonal skipped), left
    as it was when the forward and the fused backward took theirs
    inside a cell."""
    from jax.experimental.pallas import tpu as pltpu

    bh, seq_q, dim = q.shape
    seq_k = k.shape[1]
    qp, kp, vp, dop, lse_p, delta_p = _bwd_prep(
        q, k, v, o, lse, do, block_q, block_k, sm_scale)
    sq, dp_ = qp.shape[1], qp.shape[2]
    sk = kp.shape[1]
    num_q, num_kv = sq // block_q, sk // block_k

    # dk/dv kernel iterates (bh, kv, q)
    col_specs = [
        pl.BlockSpec((1, block_q, dp_), lambda b, j, i: (b, i, 0)),   # q
        pl.BlockSpec((1, block_k, dp_), lambda b, j, i: (b, j, 0)),   # k
        pl.BlockSpec((1, block_k, dp_), lambda b, j, i: (b, j, 0)),   # v
        pl.BlockSpec((1, block_q, dp_), lambda b, j, i: (b, i, 0)),   # do
        pl.BlockSpec((1, 1, sq), lambda b, j, i: (b, 0, 0)),          # lse
        pl.BlockSpec((1, 1, sq), lambda b, j, i: (b, 0, 0)),          # delta
    ]
    # dq kernel iterates (bh, q, kv): same specs, swapped grid axes
    row_specs = [
        pl.BlockSpec((1, block_q, dp_), lambda b, i, j: (b, i, 0)),   # q
        pl.BlockSpec((1, block_k, dp_), lambda b, i, j: (b, j, 0)),   # k
        pl.BlockSpec((1, block_k, dp_), lambda b, i, j: (b, j, 0)),   # v
        pl.BlockSpec((1, block_q, dp_), lambda b, i, j: (b, i, 0)),   # do
        pl.BlockSpec((1, 1, sq), lambda b, i, j: (b, 0, 0)),          # lse
        pl.BlockSpec((1, 1, sq), lambda b, i, j: (b, 0, 0)),          # delta
    ]
    dq_p = named_pallas_call(
        "flash_bwd_dq",
        functools.partial(
            _fa_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, seq_q=seq_q, seq_k=seq_k,
            num_kv=num_kv),
        grid=(bh, num_q, num_kv),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        in_specs=row_specs,
        out_specs=pl.BlockSpec((1, block_q, dp_), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, dp_), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, dp_), jnp.float32)],
        interpret=interpret,
    )(qp, kp, vp, dop, lse_p, delta_p)

    dk_p, dv_p = named_pallas_call(
        "flash_bwd_dkv",
        functools.partial(
            _fa_bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, seq_q=seq_q, seq_k=seq_k,
            num_q=num_q),
        grid=(bh, num_kv, num_q),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        in_specs=col_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, dp_), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, dp_), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, dp_), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, dp_), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, dp_), jnp.float32),
                        pltpu.VMEM((block_k, dp_), jnp.float32)],
        interpret=interpret,
    )(qp, kp, vp, dop, lse_p, delta_p)

    return (dq_p[:, :seq_q, :dim], dk_p[:, :seq_k, :dim],
            dv_p[:, :seq_k, :dim])


# --------------------------------------------------------------------------
# Blockwise XLA forward (online softmax, no S×S) — impl="xla"
# --------------------------------------------------------------------------

def _flash_fwd_xla(q, k, v, causal, sm_scale, block_k):
    """Flash forward as a `lax.scan` over KV blocks in plain XLA.

    Same online-softmax recurrence as the Pallas kernel, but expressed
    as jnp ops so XLA fuses the elementwise chain into the two matmuls
    per block. Memory O(S·block_k). The AD-able default of
    `flash_attention_with_lse` on a TPU (the ring combine's building
    block); behind `flash_attention` it is impl='xla', for comparison
    and as a fallback from the Mosaic kernels.
    """
    bh, seq_q, dim = q.shape
    seq_k = k.shape[1]
    kp = _pad_to(k, 1, block_k)
    vp = _pad_to(v, 1, block_k)
    sk = kp.shape[1]
    num_kv = sk // block_k

    q32 = q.astype(jnp.float32) * sm_scale
    k_blocks = kp.reshape(bh, num_kv, block_k, dim).transpose(1, 0, 2, 3)
    v_blocks = vp.reshape(bh, num_kv, block_k, dim).transpose(1, 0, 2, 3)

    def step(carry, blk):
        m, l, acc = carry
        j, kb, vb = blk
        s = jnp.einsum("bqd,bkd->bqk", q32, kb.astype(jnp.float32))
        col = j * block_k + lax.broadcasted_iota(
            jnp.int32, (seq_q, block_k), 1)
        mask = col < seq_k
        if causal:
            row = lax.broadcasted_iota(jnp.int32, (seq_q, block_k), 0)
            mask = mask & (col <= row + (seq_k - seq_q))
        s = jnp.where(mask[None], s, _NEG_INF)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_cur)
        # _NEG_INF is finite: for a fully-masked row s - m_new == 0, so a
        # bare exp would emit 1 per masked column. Zero masked columns
        # explicitly; fully-masked rows then keep l == 0 and hit the
        # zero-output guard below (the reference/ring-combine convention).
        p = jnp.where(mask[None], jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bqk,bkd->bqd", p, vb.astype(jnp.float32))
        return (m_new, l, acc), None

    m0 = jnp.full((bh, seq_q), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((bh, seq_q), jnp.float32)
    acc0 = jnp.zeros((bh, seq_q, dim), jnp.float32)
    (m, l, acc), _ = lax.scan(step, (m0, l0, acc0),
                              (jnp.arange(num_kv), k_blocks, v_blocks))
    safe_l = jnp.where(l == 0.0, 1.0, l)
    out = (acc / safe_l[..., None]).astype(q.dtype)
    lse = jnp.where(l == 0.0, _NEG_INF, m + jnp.log(safe_l))
    return out, lse


# --------------------------------------------------------------------------
# Blockwise XLA backward (recompute from lse)
# --------------------------------------------------------------------------

def _flash_bwd_blockwise(q, k, v, o, lse, do, causal, sm_scale, block_k):
    """Flash backward via lax.scan over KV blocks; memory O(S·block_k)."""
    bh, seq_q, dim = q.shape
    seq_k = k.shape[1]
    pad_k = (-seq_k) % block_k
    kp = _pad_to(k, 1, block_k)
    vp = _pad_to(v, 1, block_k)
    sk = kp.shape[1]
    num_kv = sk // block_k

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)                                  # (BH, Sq)
    k_blocks = kp.reshape(bh, num_kv, block_k, dim).transpose(1, 0, 2, 3)
    v_blocks = vp.reshape(bh, num_kv, block_k, dim).transpose(1, 0, 2, 3)

    q32, do32 = q.astype(jnp.float32), do.astype(jnp.float32)

    def step(dq_acc, blk):
        j, kb, vb = blk                                       # (BH, bk, D)
        s = jnp.einsum("bqd,bkd->bqk", q32,
                       kb.astype(jnp.float32)) * sm_scale
        col = j * block_k + lax.broadcasted_iota(
            jnp.int32, (seq_q, block_k), 1)
        mask = col < seq_k
        if causal:
            row = lax.broadcasted_iota(jnp.int32, (seq_q, block_k), 0)
            mask = mask & (col <= row + (seq_k - seq_q))
        p = jnp.where(mask[None], jnp.exp(s - lse[..., None]), 0.0)
        dv = jnp.einsum("bqk,bqd->bkd", p, do32)
        dp = jnp.einsum("bqd,bkd->bqk", do32, vb.astype(jnp.float32))
        ds = p * (dp - delta[..., None]) * sm_scale
        dq_acc = dq_acc + jnp.einsum("bqk,bkd->bqd", ds,
                                     kb.astype(jnp.float32))
        dk = jnp.einsum("bqk,bqd->bkd", ds, q32)
        return dq_acc, (dk, dv)

    dq, (dk_blocks, dv_blocks) = lax.scan(
        step, jnp.zeros_like(q32),
        (jnp.arange(num_kv), k_blocks, v_blocks))
    dk = dk_blocks.transpose(1, 0, 2, 3).reshape(bh, sk, dim)
    dv = dv_blocks.transpose(1, 0, 2, 3).reshape(bh, sk, dim)
    if pad_k:
        dk, dv = dk[:, :seq_k], dv[:, :seq_k]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# --------------------------------------------------------------------------
# The plan: tiles, cells, form and how far the causal walk engages
# --------------------------------------------------------------------------

class FlashAttentionPlan(NamedTuple):
    """What one `flash_attention` call compiles to; static per program.
    Tiles are counted as (q tile, kv tile) pairs a batch-head."""
    block_q: int            # forward tile rows x columns
    block_k: int
    cell_q: int             # rows of q / of k, v one forward grid cell holds
    cell_k: int
    bwd_form: str           # "fused" | "split"
    bwd_block_q: int
    bwd_block_k: int
    bwd_cell_q: int         # == bwd_block_* in the split form
    bwd_cell_k: int
    kv_tiles_visited: int   # pairs the forward computes,
    kv_tiles_total: int     #   of all there are,
    kv_tiles_masked: int    #   and how many of the visited take a mask
    bwd_tiles_visited: int  # the same three of the backward's walk
    bwd_tiles_total: int
    bwd_tiles_masked: int
    head_pad: int           # lanes of padding the head dimension gets in HBM


def _clamp_block(block: int, seq: int) -> int:
    """Clamp a block size to the (128-rounded-up) sequence length, so
    short sequences run a single Mosaic-tileable block."""
    return min(block, ((max(seq, 1) + 127) // 128) * 128)


def _walk(block_q, block_k, seq_q, seq_k, causal):
    """(visited, total, masked) tile pairs of the forward's walk."""
    n_q, n_k = -(-seq_q // block_q), -(-seq_k // block_k)
    visited = masked = 0
    for i in range(n_q):
        full, reach = _kv_tile_range(i * block_q, block_q, block_k, seq_q,
                                     seq_k, causal)
        visited += reach
        masked += reach - full
    return visited, n_q * n_k, masked


def _bwd_walk(block_q, block_k, seq_q, seq_k, causal, fused):
    """(visited, total, masked) tile pairs of the backward's walk; the
    split kernels skip what lies above the diagonal and mask the rest."""
    n_q, n_k = -(-seq_q // block_q), -(-seq_k // block_k)
    visited = plain = 0
    for j in range(n_k):
        reach, full_lo, full_hi = _q_tile_range(
            j * block_k, block_q, block_k, seq_q, seq_k, causal)
        visited += n_q - reach
        plain += max(full_hi - full_lo, 0) if fused else 0
    return visited, n_q * n_k, visited - plain


def flash_attention_plan(seq_q: int, seq_k: int, head_dim: int,
                         batch_heads: int, itemsize: int, causal: bool,
                         block_q: Optional[int] = None,
                         block_k: Optional[int] = None,
                         bwd_tiles: Optional[Tuple[int, int]] = None
                         ) -> FlashAttentionPlan:
    """The ONE place the Mosaic path and any reader learn a call's tiles.

    The walk adapts to what the call can see, static per compiled
    program: the tiles default to _FWD_BLOCK / _BWD_BLOCK (clamped to
    short sequences), a grid cell holds the whole sequence up to
    _MAX_CELL_ROWS rows and walks its tiles itself (a longer sequence
    takes cells of _LONG_CELL_ROWS, one tile each), and the backward is
    one fused kernel while its dq plane fits VMEM. `kv_tiles_visited /
    kv_tiles_total` (`bwd_tiles_*` for the backward) says how far the
    causal skip engages: 1.0 for a non-causal call, towards 0.5 as a
    causal sequence grows past its tile.

    Explicit `block_q` / `block_k` / `bwd_tiles`, else the import-time
    snapshots of BIGDL_FLASH_FWD_TILES / BIGDL_FLASH_BWD_TILES, give the
    size of a TILE; the split backward takes the forward's explicit
    tile (else _SPLIT_BWD_BLOCK), never `bwd_tiles`. `batch_heads` does not move the choice today (the
    chip table was read at one value); it is part of what a call can
    see."""
    del batch_heads
    if block_q is None and block_k is None \
            and envknobs.FLASH_FWD_TILES is not None:
        block_q, block_k = envknobs.FLASH_FWD_TILES
    split_q, split_k = block_q, block_k     # explicit, or None
    long = max(seq_q, seq_k) > _MAX_CELL_ROWS
    block_q = _clamp_block(
        block_q or (_LONG_CELL_ROWS if long else _FWD_BLOCK), seq_q)
    block_k = _clamp_block(
        block_k or (_LONG_CELL_ROWS if long else _FWD_BLOCK), seq_k)

    if bwd_tiles is None:
        bwd_tiles = envknobs.FLASH_BWD_TILES
    bwd_q, bwd_k = bwd_tiles or (
        (_LONG_CELL_ROWS,) * 2 if long else (_BWD_BLOCK, _BWD_BLOCK))
    bwd_q, bwd_k = _clamp_block(bwd_q, seq_q), _clamp_block(bwd_k, seq_k)
    fused = resolve_bwd_form(seq_q, head_dim, itemsize, bwd_q) == "fused"
    if fused:
        bwd_cells = _cell(bwd_q, seq_q), _cell(bwd_k, seq_k)
    else:
        # one tile a cell, at the forward's explicit tiles as ever
        bwd_q = _clamp_block(split_q or _SPLIT_BWD_BLOCK, seq_q)
        bwd_k = _clamp_block(split_k or _SPLIT_BWD_BLOCK, seq_k)
        bwd_cells = bwd_q, bwd_k
    return FlashAttentionPlan(
        block_q, block_k, _cell(block_q, seq_q), _cell(block_k, seq_k),
        "fused" if fused else "split", bwd_q, bwd_k, *bwd_cells,
        *_walk(block_q, block_k, seq_q, seq_k, causal),
        *_bwd_walk(bwd_q, bwd_k, seq_q, seq_k, causal, fused),
        head_pad=_head_pad(head_dim))


# --------------------------------------------------------------------------
# Public entry with custom VJP
# --------------------------------------------------------------------------

def _forward(q, k, v, causal, sm_scale, tiles, impl):
    """`tiles`: the FlashAttentionPlan of a Mosaic impl, the kv block
    of the XLA scan."""
    if impl == "reference":
        return attention_reference(q, k, v, causal, sm_scale,
                                   return_lse=True)
    if impl == "xla":
        return _flash_fwd_xla(q, k, v, causal, sm_scale, tiles)
    return _flash_fwd_pallas(q, k, v, causal, sm_scale, tiles.block_q,
                             tiles.block_k, tiles.cell_q, tiles.cell_k,
                             impl == "interpret")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_core(q, k, v, causal, sm_scale, tiles, bwd_block_k, impl):
    out, _ = _forward(q, k, v, causal, sm_scale, tiles, impl)
    return out


def _flash_core_fwd(q, k, v, causal, sm_scale, tiles, bwd_block_k, impl):
    out, lse = _forward(q, k, v, causal, sm_scale, tiles, impl)
    return out, (q, k, v, out, lse)


def _flash_core_bwd(causal, sm_scale, tiles, bwd_block_k, impl, res, do):
    q, k, v, out, lse = res
    if impl in ("pallas", "interpret"):
        return _flash_bwd_pallas(q, k, v, out, lse, do, causal, sm_scale,
                                 tiles, interpret=(impl == "interpret"))
    return _flash_bwd_blockwise(q, k, v, out, lse, do, causal, sm_scale,
                                bwd_block_k)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def _default_impl() -> str:
    # a backend that fails to initialise RAISES here: answering "cpu"
    # would send a broken TPU run down the reference path unnoticed
    if jax.devices()[0].platform != "tpu":
        return "reference"
    # the Mosaic forward and backward beat the blockwise-XLA scan at
    # every shape measured (gpt2m-train's included)
    return "pallas"


def _tiles(q, k, causal, block_q, block_k, impl, bwd_tiles=None):
    """What `_forward` takes as `tiles` for the impl: the plan of the
    call's shapes, or the XLA scan's kv block (128: its per-block
    elementwise chain stays cache-resident)."""
    if impl in ("pallas", "interpret"):
        return flash_attention_plan(
            q.shape[-2], k.shape[-2], q.shape[-1],
            math.prod(q.shape[:-2]), q.dtype.itemsize, causal, block_q,
            block_k, bwd_tiles)
    return _clamp_block(block_k or 128, k.shape[-2])


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    bwd_block_k: Optional[int] = None,
    impl: Optional[str] = None,
    bwd_tiles: Optional[Tuple[int, int]] = None,
) -> jax.Array:
    """Memory-efficient attention. q,k,v: (B, H, S, D) or (BH, S, D).

    impl: None → auto ('pallas' on TPU — Mosaic forward AND backward
    kernels; 'reference' off-TPU); explicit choices: 'xla'
    (blockwise-scan fwd + scan bwd) | 'pallas' | 'interpret' (Pallas
    interpreter mode, for CPU tests) | 'reference'.

    The Mosaic kernels take their tiles from `flash_attention_plan` and
    from nowhere else: `block_q` / `block_k` (else
    `BIGDL_FLASH_FWD_TILES`) are the forward's tile, `bwd_tiles=(bq,
    bk)` (else `BIGDL_FLASH_BWD_TILES`) the fused backward's; all are
    clamped to the sequence lengths, so a short sequence runs one
    tile. The XLA scan wants SMALL kv blocks (`block_k`, default 128);
    `bwd_block_k` applies only to its scan backward.
    """
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    impl = impl or _default_impl()
    tiles = _tiles(q, k, causal, block_q, block_k, impl,
                   None if bwd_tiles is None else tuple(bwd_tiles))
    bwd_block_k = _clamp_block(bwd_block_k or 128, k.shape[-2])
    squeeze = q.ndim == 4
    if squeeze:
        b, h, s, d = q.shape
        sk = k.shape[2]
        q = q.reshape(b * h, s, d)
        k = k.reshape(b * h, sk, k.shape[-1])
        v = v.reshape(b * h, sk, v.shape[-1])
    out = _flash_core(q, k, v, causal, float(sm_scale), tiles, bwd_block_k,
                      impl)
    if squeeze:
        out = out.reshape(b, h, s, -1)
    return out


def flash_attention_with_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    impl: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """(out, lse) for one KV chunk — a building block for callers that
    combine partial attention results themselves (online-softmax style).

    Not wrapped in the custom VJP, so the DEFAULT impl here is the
    AD-able 'xla' blockwise scan on TPU (the raw Mosaic kernel has no
    differentiation rule — pass impl='pallas' explicitly for a
    forward-only kernel call).
    """
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    if impl is None:
        impl = "xla" if _default_impl() == "pallas" else _default_impl()
    return _forward(q, k, v, causal, float(sm_scale),
                    _tiles(q, k, causal, block_q, block_k, impl), impl)
