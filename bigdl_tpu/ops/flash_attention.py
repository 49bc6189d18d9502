"""Flash attention — Pallas (Mosaic) TPU kernel with online softmax.

The reference has no attention at all (SURVEY.md §5.7: sequence handling
is unrolled-BPTT `nn/Recurrent.scala` only, bounded by one node's memory).
Long-context attention is this framework's TPU-first extension of that
subsystem, and the hot op is a real Pallas kernel — the TPU-native
counterpart of the reference's hand-tuned native MKL-DNN primitives
(SURVEY.md §2.1 native checklist).

Design
------
* Forward: `pl.pallas_call` over a (batch*heads, q_blocks, kv_blocks)
  grid. kv is the minor grid axis; an f32 VMEM accumulator plus running
  max / running sum scratch implement the online (streaming) softmax, so
  HBM traffic is O(S·D) and nothing of size S×S ever materializes. QK^T
  and P·V both run on the MXU via `dot_general` with f32 accumulation.
* Backward: two more Mosaic kernels — dq over a (bh, q, kv) grid and
  dk/dv over a (bh, kv, q) grid — recomputing probabilities from the
  saved log-sum-exp, VMEM accumulators, nothing S×S in HBM. (A
  blockwise `lax.scan` XLA backward remains for impl="xla".)
* The same math is exposed as `attention_reference` (jnp oracle for
  tests, CPU fallback), and `flash_attention_with_lse` returns the
  (out, lse) pair that the ring-attention combine consumes
  (bigdl_tpu/parallel/ring_attention.py).

Numerics: masked logits use a large finite negative (-1e30), not -inf,
so fully-masked rows produce zeros (not NaN) after normalization — the
convention the ring combine relies on.

Env tile overrides (`BIGDL_FLASH_FWD_TILES` / `BIGDL_FLASH_BWD_TILES`)
are snapshotted at IMPORT via utils/envknobs — never read at trace
time, so the value in the environment when `bigdl_tpu` is imported
wins and later env mutations are visibly inert (graftlint
`trace-env-read` guards the class). Sweeps set the env before the
process starts — or run each config in a fresh process, as the sweep
scripts do (scripts/sweep_attn_blocks.py,
scripts/sweep_attn_bwd_tiles.py); in-process rotation requires an
explicit `envknobs.refresh()` plus a fresh jit root per config.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from bigdl_tpu.ops.pallas_names import named_pallas_call
from bigdl_tpu.utils import envknobs

_NEG_INF = -1e30
_LOG2E = 1.4426950408889634  # MUST match between _bwd_recompute (s2) and _bwd_prep (lse2)


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
# Pallas forward kernel
# --------------------------------------------------------------------------

def _fa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
               *, sm_scale, causal, block_q, block_k, seq_q, seq_k,
               num_kv):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k

    def _compute(masked):
        # dot NATIVE-dtype operands (bf16 on the training path) with f32
        # MXU accumulation; a pre-dot f32 cast would force the MXU into
        # multi-pass f32 mode (~3-6x slower on v5e). Scale applies to the
        # f32 s tile post-matmul (more accurate than pre-scaling bf16 q).
        q = q_ref[0]                                         # (bq, D)
        k = k_ref[0]                                         # (bk, D)
        s = lax.dot_general(q, k,
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)
        s = s * sm_scale
        if masked:
            col = k_start + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            mask = col < seq_k
            if causal:
                # bottom-right alignment (query i sees keys ≤
                # i + seq_k-seq_q), matching attention_reference and
                # the blockwise backward
                row = q_start + lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                mask = mask & (col <= row + (seq_k - seq_q))
            s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_scr[:, :1]                                # (bq, 1)
        l_prev = l_scr[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        # zero masked columns explicitly: _NEG_INF is finite, so for a
        # fully-masked row exp(s - m_new) == 1 and the row would emit
        # mean(V) instead of the zeros the ring combine relies on
        p = jnp.exp(s - m_new)                               # (bq, bk)
        if masked:
            p = jnp.where(mask, p, 0.0)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)

        acc = acc_scr[:] * alpha + lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # (bq, D)

        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)
        acc_scr[:] = acc

    # a tile entirely in-bounds and (for causal) entirely below the
    # diagonal needs NO mask — skip the iota/where chain on the s tile
    # (the VPU elementwise chain is the fwd kernel's residual cost)
    in_bounds = k_start + block_k <= seq_k
    if causal:
        reachable = k_start <= q_start + block_q - 1 + (seq_k - seq_q)
        full = in_bounds & (k_start + block_k - 1
                            <= q_start + (seq_k - seq_q))

        @pl.when(full)
        def _():
            _compute(masked=False)

        @pl.when(reachable & jnp.logical_not(full))
        def _():
            _compute(masked=True)
    else:
        @pl.when(in_bounds)
        def _():
            _compute(masked=False)

        @pl.when(jnp.logical_not(in_bounds))
        def _():
            _compute(masked=True)

    @pl.when(ki == num_kv - 1)
    def _finalize():
        l = l_scr[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)
        lse = m_scr[:, :1] + jnp.log(safe_l)
        lse = jnp.where(l == 0.0, _NEG_INF, lse)             # (bq, 1)
        # lane-broadcast: Mosaic requires the minor-most two block dims be
        # (8k, 128)-tileable, so lse rides a (bq, 128) block; the caller
        # reads lane 0
        lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _flash_fwd_pallas(q, k, v, causal, sm_scale, block_q, block_k,
                      interpret):
    """q,k,v: (BH, S, D) → (out (BH, S, D), lse (BH, S))."""
    from jax.experimental.pallas import tpu as pltpu

    bh, seq_q, dim = q.shape
    seq_k = k.shape[1]

    qp = _pad_to(_pad_to(q, 1, block_q), 2, 128)
    kp = _pad_to(_pad_to(k, 1, block_k), 2, 128)
    vp = _pad_to(_pad_to(v, 1, block_k), 2, 128)
    sq, dp = qp.shape[1], qp.shape[2]
    sk = kp.shape[1]
    num_q, num_kv = sq // block_q, sk // block_k

    kernel = functools.partial(
        _fa_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_k=block_k, seq_q=seq_q, seq_k=seq_k, num_kv=num_kv)

    out_p, lse_p = named_pallas_call(
        "flash_fwd",
        kernel,
        grid=(bh, num_q, num_kv),
        # bh and q rows are independent; only the kv sweep carries the
        # online-softmax scratch. Marking them parallel lets Mosaic
        # overlap/reorder grid cells (the library kernel's convention).
        # vmem cap raised like the fused backward's so 2048-row tiles
        # compile (default 16 MiB rejects them).
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        in_specs=[
            pl.BlockSpec((1, block_q, dp), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, dp), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, dp), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dp), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 128), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, dp), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, dp), jnp.float32),
        ],
        interpret=interpret,
    )(qp, kp, vp)
    return out_p[:, :seq_q, :dim], lse_p[:, :seq_q, 0]


# --------------------------------------------------------------------------
# Pallas backward kernels (dq; dk/dv) — recompute-from-lse flash backward
# --------------------------------------------------------------------------

def _bwd_recompute(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   q_start, k_start, sm_scale, causal, block_q, block_k,
                   seq_q, seq_k, masked=True):
    """The shared dq/dkv recompute chain: (q, k, do, p, ds) for one
    (q_block, kv_block) tile — p from the saved lse, ds from delta.
    `q` comes back UNSCALED (dk needs it that way). `masked=False`
    skips the iota/where chain — only valid for tiles fully in-bounds
    on BOTH axes and (causal) entirely below the diagonal.

    All dots take NATIVE-dtype operands with f32 MXU accumulation (the
    library-kernel convention); q/k/do come back in native dtype and
    p/ds in f32 — callers cast p/ds to the operand dtype at their dots.
    A pre-dot f32 cast would force multi-pass f32 MXU mode (~3-6x
    slower on v5e) — measured as the dominant term of the round-4
    backward (PROFILE_r05).

    VPU-chain economies (the backward's bound is the elementwise chain
    over the s/p/ds tiles, not the MXU — PROFILE_r05 per-cell
    arithmetic): (1) p is computed in base 2 — _bwd_prep pre-multiplies
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
    if masked:
        row = q_start + lax.broadcasted_iota(jnp.int32,
                                             (block_q, block_k), 0)
        col = k_start + lax.broadcasted_iota(jnp.int32,
                                             (block_q, block_k), 1)
        # padded q rows must contribute nothing (dk/dv accumulate over
        # rows)
        mask = (col < seq_k) & (row < seq_q)
        if causal:
            mask = mask & (col <= row + (seq_k - seq_q))
        p = jnp.where(mask, jnp.exp2(s2 - lse2), 0.0)        # (bq, bk)
    else:
        p = jnp.exp2(s2 - lse2)
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
    """Shared backward setup (fused AND split wrappers): pad operands to
    block/lane multiples, precompute delta = sum(do*o), reshape lse and
    delta to the (BH, 1, sq) layout Mosaic accepts, and build the
    (bh, kv, q)-grid input BlockSpecs.

    lse ships PRE-MULTIPLIED by log2(e) and delta PRE-MULTIPLIED by
    sm_scale — the per-tile VPU economies _bwd_recompute documents."""
    qp = _pad_to(_pad_to(q, 1, block_q), 2, 128)
    dop = _pad_to(_pad_to(do, 1, block_q), 2, 128)
    kp = _pad_to(_pad_to(k, 1, block_k), 2, 128)
    vp = _pad_to(_pad_to(v, 1, block_k), 2, 128)
    sq, dp_ = qp.shape[1], qp.shape[2]
    sk = kp.shape[1]

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1) * sm_scale                      # (BH, Sq)
    # (BH, 1, sq): Mosaic wants the last two block dims (8,128)-tileable
    # OR equal to the array dims — (1, sq) matches exactly
    lse_p = _pad_to(lse.astype(jnp.float32) * _LOG2E,
                    1, block_q)[:, None, :]
    delta_p = _pad_to(delta, 1, block_q)[:, None, :]

    col_specs = [
        pl.BlockSpec((1, block_q, dp_), lambda b, j, i: (b, i, 0)),   # q
        pl.BlockSpec((1, block_k, dp_), lambda b, j, i: (b, j, 0)),   # k
        pl.BlockSpec((1, block_k, dp_), lambda b, j, i: (b, j, 0)),   # v
        pl.BlockSpec((1, block_q, dp_), lambda b, j, i: (b, i, 0)),   # do
        pl.BlockSpec((1, 1, sq), lambda b, j, i: (b, 0, 0)),          # lse
        pl.BlockSpec((1, 1, sq), lambda b, j, i: (b, 0, 0)),          # delta
    ]
    return (qp, kp, vp, dop, lse_p, delta_p, sq, sk, dp_, col_specs)


def _fa_bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr,
                         *, sm_scale, causal, block_q, block_k, seq_q,
                         seq_k, num_q, num_kv):
    """Single-pass backward: dk/dv over the (bh, kv, q) grid as before,
    with dq accumulated IN the same pass.

    The trick that makes one pass legal under Mosaic's output-revisit
    semantics: dq's output block is the WHOLE (seq, D) row plane with
    index map (b, 0, 0) — it never changes within a batch-head, so the
    block stays resident in VMEM across every (kv, q) cell and is
    flushed exactly once per bh. Each cell adds its ds·k contribution
    to the dq row-slice in a full-sequence f32 scratch, and the row
    slice is emitted during the final kv sweep. One s/p/ds recompute
    per tile instead of the two the split dq/dkv kernels pay, and half
    the grid cells.
    """
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when((ki == 0) & (qi == 0))
    def _init_dq():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(qi == 0)
    def _init_dkv():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    q_start = qi * block_q
    k_start = ki * block_k

    def _compute(masked):
        q, k, do, p, ds = _bwd_recompute(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, q_start,
            k_start, sm_scale, causal, block_q, block_k, seq_q, seq_k,
            masked=masked)
        ds_n = ds.astype(q.dtype)
        dv_scr[:] = dv_scr[:] + lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # (bk, D)
        dk_scr[:] = dk_scr[:] + lax.dot_general(
            ds_n, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dq_scr[pl.dslice(q_start, block_q)] = \
            dq_scr[pl.dslice(q_start, block_q)] + lax.dot_general(
                ds_n, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    # same unmasked fast path as the forward kernel, with the extra
    # q-rows-in-bounds requirement (padded rows feed dk/dv sums)
    full = (k_start + block_k <= seq_k) & (q_start + block_q <= seq_q)
    if causal:
        reachable = q_start + block_q - 1 + (seq_k - seq_q) >= k_start
        full = full & (k_start + block_k - 1
                       <= q_start + (seq_k - seq_q))

        @pl.when(full)
        def _():
            _compute(masked=False)

        @pl.when(reachable & jnp.logical_not(full))
        def _():
            _compute(masked=True)
    else:
        @pl.when(full)
        def _():
            _compute(masked=False)

        @pl.when(jnp.logical_not(full))
        def _():
            _compute(masked=True)

    @pl.when(qi == num_q - 1)
    def _finalize_dkv():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        # do arrived pre-scaled by sm_scale (see _bwd_recompute)
        inv = 1.0 / sm_scale if sm_scale != 0.0 else 1.0
        dv_ref[0] = (dv_scr[:] * inv).astype(dv_ref.dtype)

    # dq row-block i has received every contribution once the kv sweep
    # is past its diagonal; emitting during the LAST kv sweep is always
    # safe (later sweeps add nothing above the diagonal)
    @pl.when(ki == num_kv - 1)
    def _finalize_dq():
        dq_ref[0, pl.dslice(q_start, block_q)] = \
            dq_scr[pl.dslice(q_start, block_q)].astype(dq_ref.dtype)


def _flash_bwd_pallas_fused(q, k, v, o, lse, do, causal, sm_scale,
                            block_q, block_k, interpret):
    """One-kernel Mosaic backward (see _fa_bwd_fused_kernel). Falls
    back to the two-kernel form for very long sequences where the
    full-sequence dq scratch would crowd VMEM
    (_flash_bwd_pallas caller decides)."""
    from jax.experimental.pallas import tpu as pltpu

    bh, seq_q, dim = q.shape
    seq_k = k.shape[1]
    (qp, kp, vp, dop, lse_p, delta_p, sq, sk, dp_,
     col_specs) = _bwd_prep(q, k, v, o, lse, do, block_q, block_k,
                            sm_scale)
    num_q, num_kv = sq // block_q, sk // block_k

    dq_p, dk_p, dv_p = named_pallas_call(
        "flash_bwd_fused",
        functools.partial(
            _fa_bwd_fused_kernel, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, seq_q=seq_q, seq_k=seq_k,
            num_q=num_q, num_kv=num_kv),
        grid=(bh, num_kv, num_q),
        # the full-sequence dq residents exceed Mosaic's default 16 MiB
        # scoped-vmem budget at long context (18.1 MiB at S=16384 with
        # native-dtype dots); v5e has 128 MiB — raise the kernel's cap.
        # Only bh is parallel: the dq plane persists across kv AND q.
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024,
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        in_specs=col_specs,
        out_specs=[
            # whole dq row plane per bh: index map constant in (j, i),
            # so the block is flushed once per batch-head
            pl.BlockSpec((1, sq, dp_), lambda b, j, i: (b, 0, 0)),
            pl.BlockSpec((1, block_k, dp_), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, dp_), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, dp_), q.dtype),
            jax.ShapeDtypeStruct((bh, sk, dp_), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, dp_), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((sq, dp_), jnp.float32),
                        pltpu.VMEM((block_k, dp_), jnp.float32),
                        pltpu.VMEM((block_k, dp_), jnp.float32)],
        interpret=interpret,
    )(qp, kp, vp, dop, lse_p, delta_p)

    return (dq_p[:, :seq_q, :dim], dk_p[:, :seq_k, :dim],
            dv_p[:, :seq_k, :dim])


# Above this, the fused kernel's full-sequence VMEM residents (f32 dq
# scratch + dq output block in q.dtype) would crowd VMEM; use the
# two-kernel backward instead. 13 MiB admits the largest measured-good
# config (bf16 S=16384, D=64→128: 12.6 MiB resident, 70.9k tok/s —
# PROFILE_r04) while sending f32 S=16384 (16.8 MiB) to the split form.
_FUSED_BWD_MAX_RESIDENT_BYTES = 13 * 1024 * 1024


_FUSED_BWD_MAX_TILE = 1024 * 512  # bq*bk cap for the fused backward's
# DEFAULT tile derivation (512x1024 at the default fwd blocks). Round-5
# re-swept with the 64 MiB kernel-vmem limit: true 1024x1024 and
# kv-wide 1024x2048 tiles now COMPILE but are in-model neutral (186M:
# 259.4 vs 258.7 ms) to slightly worse (43M op-level 9.70/10.67 vs
# 9.43 ms) — PROFILE_r05/bwd_tile_sweep. Explicit bwd_tiles/env
# overrides bypass this cap entirely.


def resolve_bwd_form(seq_q: int, head_dim: int, itemsize: int,
                     block_q: int = 1024) -> str:
    """'fused' | 'split': which Mosaic backward a shape routes to.

    Mirrors the resident-bytes gate in `_flash_bwd_pallas` so SWEEPS
    can record (and refuse to mislabel) the kernel that actually runs:
    past the cap, a `bwd_tiles`/env override does NOT apply — the
    split backward tiles at the forward blocks. Recording this per row
    replaced the old trace-time "override ignored" warning (the
    ADVICE-r05 wrong-kernel-measurement hazard)."""
    sq_padded = ((seq_q + block_q - 1) // block_q) * block_q
    dp_padded = ((head_dim + 127) // 128) * 128
    resident = sq_padded * dp_padded * (4 + itemsize)
    return "fused" if resident <= _FUSED_BWD_MAX_RESIDENT_BYTES \
        else "split"


def _flash_bwd_pallas(q, k, v, o, lse, do, causal, sm_scale, block_q,
                      block_k, interpret, bwd_tiles=None):
    sq_padded = ((q.shape[1] + block_q - 1) // block_q) * block_q
    dp_padded = ((q.shape[2] + 127) // 128) * 128
    # fused-path VMEM residents that scale with the FULL sequence: the
    # f32 dq scratch AND the dq output block (q.dtype) — both stay live
    # per batch-head (keep in sync with resolve_bwd_form above)
    resident = sq_padded * dp_padded * (4 + q.dtype.itemsize)
    if resident <= _FUSED_BWD_MAX_RESIDENT_BYTES:
        # the fused kernel's per-cell tiles cap lower than the split
        # kernels'. Default tie-break shrinks the Q tile first: the
        # round-5 sweep with native-dtype dots re-confirmed 512x1024 as
        # the optimum at the 186M shape (13.39 ms vs 13.58 at 1024x512,
        # 15.94 at 512x512 — July records, another stack); the
        # serial kv loop amortizes better with a WIDE kv tile.
        # `bwd_tiles` overrides for experimentation.
        if bwd_tiles is None:
            bwd_tiles = envknobs.FLASH_BWD_TILES
        if bwd_tiles is not None:
            # explicit/env tiles are trusted as-is (only seq-clamped):
            # the auto-shrink below would silently rewrite a swept
            # override into a different config
            fb_q = _clamp_block(bwd_tiles[0], q.shape[1])
            fb_k = _clamp_block(bwd_tiles[1], k.shape[1])
        else:
            fb_q, fb_k = block_q, block_k
            while fb_q * fb_k > _FUSED_BWD_MAX_TILE:
                if fb_q >= fb_k:
                    fb_q //= 2
                else:
                    fb_k //= 2
        return _flash_bwd_pallas_fused(q, k, v, o, lse, do, causal,
                                       sm_scale, fb_q, fb_k, interpret)
    # NOTE: past the resident cap a bwd_tiles/env override does not
    # apply — the split backward tiles at the forward blocks. The old
    # trace-time "override ignored" warning is gone: env knobs can no
    # longer be resolved mid-trace (import-time snapshots, graftlint
    # trace-env-read), and sweep_attn_bwd_tiles.py records
    # `resolve_bwd_form` per row, skipping combos a split route would
    # mislabel.
    return _flash_bwd_pallas_split(q, k, v, o, lse, do, causal, sm_scale,
                                   block_q, block_k, interpret)


def _flash_bwd_pallas_split(q, k, v, o, lse, do, causal, sm_scale,
                            block_q, block_k, interpret):
    """Flash backward as two Mosaic kernels: dq over a (bh, q, kv) grid,
    dk/dv over a (bh, kv, q) grid, both recomputing probabilities from
    the forward's log-sum-exp (nothing S×S in HBM)."""
    from jax.experimental.pallas import tpu as pltpu

    bh, seq_q, dim = q.shape
    seq_k = k.shape[1]
    (qp, kp, vp, dop, lse_p, delta_p, sq, sk, dp_,
     col_specs) = _bwd_prep(q, k, v, o, lse, do, block_q, block_k,
                            sm_scale)
    num_q, num_kv = sq // block_q, sk // block_k

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
    per block. Memory O(S·block_k). This was the round-2 TPU default;
    since the Mosaic kernels were retuned (512x512 tiles) and gained a
    Mosaic backward it loses at every measured shape (July records,
    another stack) and remains as impl='xla' for comparison
    and as a fallback.
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
# Public entry with custom VJP
# --------------------------------------------------------------------------

def _forward(q, k, v, causal, sm_scale, block_q, block_k, impl):
    if impl == "reference":
        return attention_reference(q, k, v, causal, sm_scale,
                                   return_lse=True)
    if impl == "xla":
        return _flash_fwd_xla(q, k, v, causal, sm_scale, block_k)
    return _flash_fwd_pallas(q, k, v, causal, sm_scale, block_q, block_k,
                             interpret=(impl == "interpret"))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_core(q, k, v, causal, sm_scale, block_q, block_k, bwd_block_k,
                impl, bwd_tiles):
    out, _ = _forward(q, k, v, causal, sm_scale, block_q, block_k, impl)
    return out


def _flash_core_fwd(q, k, v, causal, sm_scale, block_q, block_k,
                    bwd_block_k, impl, bwd_tiles):
    out, lse = _forward(q, k, v, causal, sm_scale, block_q, block_k, impl)
    return out, (q, k, v, out, lse)


def _flash_core_bwd(causal, sm_scale, block_q, block_k, bwd_block_k, impl,
                    bwd_tiles, res, do):
    q, k, v, out, lse = res
    if impl in ("pallas", "interpret"):
        # Mosaic backward; fused-kernel tiles chosen independently
        return _flash_bwd_pallas(q, k, v, out, lse, do, causal, sm_scale,
                                 block_q, block_k,
                                 interpret=(impl == "interpret"),
                                 bwd_tiles=bwd_tiles)
    return _flash_bwd_blockwise(q, k, v, out, lse, do, causal, sm_scale,
                                bwd_block_k)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def _clamp_block(block: int, seq: int) -> int:
    """Clamp a block size to the (128-rounded-up) sequence length, so
    short sequences run a single Mosaic-tileable block."""
    return min(block, ((max(seq, 1) + 127) // 128) * 128)


def _resolve_impl_and_blocks(q, k, block_q, block_k, impl):
    """Shared default resolution for both public entry points: pick the
    impl (Mosaic kernels on TPU, reference elsewhere), then per-impl
    default tiles, clamped to the sequences.

    Mosaic default tiles are 1024x1024 (round-4 sweep: the grid-cell
    count, not the MXU, binds, so fewer/bigger cells win), EXCEPT the
    single-tile-per-bh regime bh<=64 AND S<=2048 where one whole-
    sequence 2048x2048 tile per batch-head wins (+3.6% in-model at the
    43M shape — PROFILE_r05/fwd2048_43m_inmodel_ab.log; at BH>=128
    2048-row tiles regress, r4+r5 sweeps). `BIGDL_FLASH_FWD_TILES=BQxBK`
    overrides when no explicit blocks are passed. The XLA scan keeps
    128."""
    impl = impl or _default_impl()
    big = impl in ("pallas", "interpret")
    env = envknobs.FLASH_FWD_TILES if big else None
    if env is not None and (block_q is None and block_k is None):
        block_q, block_k = env
    default = 1024
    if big and block_q is None and block_k is None:
        # single-tile-per-bh regime: at few batch*heads the grid has too
        # few cells to amortize per-cell overhead — one whole-sequence
        # tile per bh wins (43M in-model: 202.0k -> 209.4k tok/s,
        # +3.6%, PROFILE_r05). At BH>=128 2048-tiles regress (r4+r5
        # sweeps), and at long context the 1024 default stays.
        import math as _math

        bh = int(_math.prod(q.shape[:-2])) if q.ndim >= 3 else 1
        if bh <= 64 and q.shape[-2] <= 2048 and k.shape[-2] <= 2048:
            default = 2048
    block_q = _clamp_block(block_q or (default if big else 128),
                           q.shape[-2])
    block_k = _clamp_block(block_k or (default if big else 128),
                           k.shape[-2])
    return impl, block_q, block_k


def _default_impl() -> str:
    # a backend that fails to initialise RAISES here: answering "cpu"
    # would send a broken TPU run down the reference path unnoticed
    if jax.devices()[0].platform != "tpu":
        return "reference"
    # Round-3 full-step measurements on the real chip (S=2048, D=64,
    # remat, fused loss): with both the forward kernel (512x512 tiles)
    # AND the Mosaic backward (dq + dk/dv kernels), pallas wins at every
    # measured shape — 48.9k vs 27.5k tok/s at 186M (B*H=128) and
    # 150.7k vs 139.1k at 43M (B*H=64) against the round-2
    # blockwise-XLA-scan default. (Fwd-kernel-only, the 43M shape
    # preferred the scan — the Mosaic backward is what tipped it.)
    return "pallas"


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
    kernels, fastest at every measured shape; 'reference' off-TPU);
    explicit choices: 'xla' (blockwise-scan fwd + scan bwd) | 'pallas'
    | 'interpret' (Pallas interpreter mode, for CPU tests) |
    'reference'.

    Block sizes default per impl from measurement: the Mosaic kernels
    want LARGE tiles — 1024x1024, or one whole-sequence 2048x2048 tile
    per batch-head when bh<=64 and S<=2048 (see
    _resolve_impl_and_blocks) — while the XLA scan wants SMALL kv
    blocks (128 — its per-block elementwise chain stays
    cache-resident). `BIGDL_FLASH_FWD_TILES` overrides the fwd default.
    `bwd_block_k` applies only to the impl='xla' scan backward.
    `bwd_tiles=(bq, bk)` overrides the FUSED Mosaic backward's tiles
    (default: the fwd blocks, q-tile halved first until bq·bk fits the
    VMEM cap — 512x1024 at the default fwd blocks, re-confirmed optimal
    by the round-5 sweep). All are clamped to the sequence lengths, so
    short sequences run a single-tile kernel.
    """
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    impl, block_q, block_k = _resolve_impl_and_blocks(
        q, k, block_q, block_k, impl)
    bwd_block_k = _clamp_block(bwd_block_k or 128, k.shape[-2])
    squeeze = q.ndim == 4
    if squeeze:
        b, h, s, d = q.shape
        sk = k.shape[2]
        q = q.reshape(b * h, s, d)
        k = k.reshape(b * h, sk, k.shape[-1])
        v = v.reshape(b * h, sk, v.shape[-1])
    out = _flash_core(q, k, v, causal, float(sm_scale), block_q, block_k,
                      bwd_block_k, impl,
                      None if bwd_tiles is None else tuple(bwd_tiles))
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
    impl, block_q, block_k = _resolve_impl_and_blocks(
        q, k, block_q, block_k, impl)
    return _forward(q, k, v, causal, float(sm_scale), block_q, block_k,
                    impl)
