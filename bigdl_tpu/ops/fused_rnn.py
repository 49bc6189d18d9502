"""Persistent-RNN fused scan kernels — Pallas (Mosaic) TPU.

Reference parity: nn/Recurrent.scala (the reference's unrolled time
loop), nn/LSTM.scala, nn/GRU.scala, nn/BiRecurrent.scala. The math is
EXACTLY the hoisted-input protocol of `nn/recurrent.py`
(`step_precomputed`): the time-independent x·W_x half of every gate
matmul runs once outside as a full-sequence MXU matmul, and these
kernels run only the recurrent half — but with the ENTIRE time loop
inside one kernel launch instead of one XLA dispatch per `lax.scan`
step.

Why: the recurrent path is latency-floor-bound, not compute-bound
(PROFILE_r04 roofline: ~13 µs per sequential scan step at the BiLSTM
shape ⇒ 1.5% MFU; the (N,H)·(H,4H) recurrent matmul itself is ~0.2 µs
of MXU work). A `lax.scan` pays per-step dispatch and an HBM
round-trip of the (h, c) carry every timestep. Here:

* grid = (batch-tiles, T), time the minor sequential axis — ONE launch
  for the whole sequence; Mosaic streams the per-step input-projection
  block through VMEM while the previous step computes;
* the (h, c) carries live in VMEM scratch for the whole sweep — they
  NEVER touch HBM;
* the (N,H)·(H,4H) recurrent matmul is fused with the sigmoid/tanh
  gate elementwise block in the same kernel body (native-dtype MXU
  operands, f32 accumulation — the flash-attention convention);
* the bidirectional variant runs BOTH directions in one launch (the
  reverse direction reads/writes time-mirrored blocks via index maps,
  so no `jnp.flip` HBM passes and per-grid-cell overhead is amortized
  over twice the work);
* the backward is a `custom_vjp` with the same residency scheme: one
  reversed sweep, dh/dc carries in VMEM, gates recomputed from the
  saved activations (i, f, g, o and the cell-state sequence are the
  only residuals), dW_hh accumulated in a VMEM f32 scratch and
  emitted once per batch-tile.

Fallback: `impl="xla"` (auto-selected off-TPU, for hidden sizes that
are not lane-tileable (H % 128 != 0), and for H too large for the
VMEM-resident weight scheme) is the plain `lax.scan` this kernel
replaces — also the numeric oracle for the parity tests.

Env knobs (snapshotted at IMPORT via utils/envknobs — never read at
trace time; in-process sweeps call `envknobs.refresh()` after
mutating the environment): `BIGDL_FUSED_RNN=0` disables the kernels
(auto mode only); `BIGDL_FUSED_RNN_BLOCK_N` overrides the batch-tile
rows.
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

# Above this hidden size the backward's VMEM residents no longer fit
# the kernel budget: at H the resident set is the (H, 4H) weight, the
# f32 dW output block + dW scratch (H·4H·4 B each), the dh/dc carries,
# and ~6 double-buffered (block_n, 4H)/(block_n, H) f32 per-step
# blocks. At H=1024 the dW pair alone is 32 MiB and the total tops
# ~100 MiB at block_n=512 — past _VMEM_LIMIT with no compile-time
# fallback — so eligibility caps at 512 (≈38 MiB at block_n=512,
# ≈25 MiB at the derated default tile below).
_MAX_HIDDEN = 512
_VMEM_LIMIT = 64 * 1024 * 1024


def _default_platform() -> str:
    # a backend that fails to initialise RAISES here: answering "cpu"
    # would send a broken TPU run down the lax.scan path unnoticed
    return jax.devices()[0].platform


def resolve_impl(hidden: int, impl: Optional[str] = None) -> str:
    """'pallas' | 'interpret' | 'xla'. Auto (None/'auto') picks the
    Mosaic kernel on TPU when the shape is kernel-eligible: the gate
    splits slice the lane dimension, so H must be a multiple of 128,
    and the resident weight scheme caps H at `_MAX_HIDDEN`.
    Unknown impl strings RAISE rather than silently degrading to the
    fallback — a typo'd 'palas' measuring the lax.scan path would be
    indistinguishable from real kernel data in a sweep."""
    if impl in ("pallas", "interpret", "xla"):
        return impl
    if impl not in (None, "auto"):
        raise ValueError(
            f"fused_rnn impl {impl!r}: expected None/'auto'/'pallas'/"
            f"'interpret'/'xla'")
    if not envknobs.FUSED_RNN_ENABLED:
        return "xla"
    if _default_platform() != "tpu":
        return "xla"
    if hidden % 128 != 0 or hidden > _MAX_HIDDEN:
        return "xla"
    return "pallas"


def _pad_batch(n: int, block_n: Optional[int],
               hidden: int) -> Tuple[int, int]:
    """(padded_n, block_n): batch rows padded to a sublane-tileable
    block multiple (16 covers bf16's (16, 128) min tile). The default
    tile derates with H so the backward's per-step f32 blocks stay
    within the VMEM budget (see _MAX_HIDDEN note); explicit/env
    overrides are trusted as-is (sweep knobs)."""
    n16 = ((n + 15) // 16) * 16
    bn = block_n or envknobs.FUSED_RNN_BLOCK_N \
        or (512 if hidden <= 256 else 256)
    bn = min(((bn + 15) // 16) * 16, n16)
    return ((n16 + bn - 1) // bn) * bn, bn


# --------------------------------------------------------------------------
# LSTM — shared per-direction step bodies
# --------------------------------------------------------------------------

def _lstm_gate_math(z, c_prev, h):
    """z (bn, 4H) f32 pre-activations, c_prev (bn, H) f32 → (h_new, c,
    gates) with gates the ACTIVATED (i, f, g, o) concat — the backward's
    residual. MUST match nn/recurrent.LSTM._gates bit-for-math."""
    i = jax.nn.sigmoid(z[:, :h])
    f = jax.nn.sigmoid(z[:, h:2 * h])
    g = jnp.tanh(z[:, 2 * h:3 * h])
    o = jax.nn.sigmoid(z[:, 3 * h:])
    c = f * c_prev + i * g
    hy = o * jnp.tanh(c)
    return hy, c, jnp.concatenate([i, f, g, o], axis=-1)


def _lstm_fwd_dir(zx_ref, w_ref, ys_ref, c_ref, g_ref, h_scr, c_scr,
                  hidden):
    """One direction's fused step: recurrent matmul + gate block, carries
    in VMEM scratch, residuals (gates, c) written to this step's block.
    c_ref/g_ref are None on the inference-only (no-residual) variant —
    then gates/c die in registers and HBM sees only ys."""
    z = zx_ref[0].astype(jnp.float32) + lax.dot_general(
        h_scr[:].astype(w_ref.dtype), w_ref[:],
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    hy, c, gates = _lstm_gate_math(z, c_scr[:], hidden)
    h_scr[:] = hy
    c_scr[:] = c
    ys_ref[0] = hy.astype(ys_ref.dtype)
    if c_ref is not None:
        c_ref[0] = c.astype(c_ref.dtype)
        g_ref[0] = gates.astype(g_ref.dtype)


def _lstm_bwd_dir(w_ref, g_ref, c_ref, cp_ref, hp_ref, dy_ref, dzx_ref,
                  dh_scr, dc_scr, dw_scr, live, hidden):
    """One direction's backward step (reversed sweep): recompute the
    cell derivative chain from the saved gate activations, carry dh/dc
    in VMEM, accumulate dW_hh in f32 scratch. `live` is 0.0 at the
    direction's FIRST timestep (h_prev/c_prev are the zero init)."""
    gates = g_ref[0].astype(jnp.float32)
    i = gates[:, :hidden]
    f = gates[:, hidden:2 * hidden]
    g = gates[:, 2 * hidden:3 * hidden]
    o = gates[:, 3 * hidden:]
    c = c_ref[0].astype(jnp.float32)
    c_prev = cp_ref[0].astype(jnp.float32) * live
    h_prev = hp_ref[0].astype(jnp.float32) * live
    dh = dy_ref[0].astype(jnp.float32) + dh_scr[:]
    tc = jnp.tanh(c)
    do_pre = dh * tc * o * (1.0 - o)
    dc = dc_scr[:] + dh * o * (1.0 - tc * tc)
    di_pre = dc * g * i * (1.0 - i)
    df_pre = dc * c_prev * f * (1.0 - f)
    dg_pre = dc * i * (1.0 - g * g)
    dz = jnp.concatenate([di_pre, df_pre, dg_pre, do_pre], axis=-1)
    dzx_ref[0] = dz.astype(dzx_ref.dtype)
    dzn = dz.astype(w_ref.dtype)
    dh_scr[:] = lax.dot_general(
        dzn, w_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    dc_scr[:] = dc * f
    dw_scr[:] = dw_scr[:] + lax.dot_general(
        h_prev.astype(w_ref.dtype), dzn, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


# --------------------------------------------------------------------------
# LSTM — unidirectional kernels
# --------------------------------------------------------------------------

def _lstm_fwd_kernel(zx_ref, w_ref, ys_ref, c_ref, g_ref, h_scr, c_scr,
                     *, hidden):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        h_scr[:] = jnp.zeros_like(h_scr)
        c_scr[:] = jnp.zeros_like(c_scr)

    _lstm_fwd_dir(zx_ref, w_ref, ys_ref, c_ref, g_ref, h_scr, c_scr,
                  hidden)


def _lstm_fwd_infer_kernel(zx_ref, w_ref, ys_ref, h_scr, c_scr, *,
                           hidden):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        h_scr[:] = jnp.zeros_like(h_scr)
        c_scr[:] = jnp.zeros_like(c_scr)

    _lstm_fwd_dir(zx_ref, w_ref, ys_ref, None, None, h_scr, c_scr,
                  hidden)


def _lstm_bwd_kernel(w_ref, g_ref, c_ref, cp_ref, hp_ref, dy_ref,
                     dzx_ref, dw_ref, dh_scr, dc_scr, dw_scr, *, hidden,
                     n_t):
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        dh_scr[:] = jnp.zeros_like(dh_scr)
        dc_scr[:] = jnp.zeros_like(dc_scr)
        dw_scr[:] = jnp.zeros_like(dw_scr)

    live = jnp.where(s == n_t - 1, 0.0, 1.0)  # t == 0 has zero carry-in
    _lstm_bwd_dir(w_ref, g_ref, c_ref, cp_ref, hp_ref, dy_ref, dzx_ref,
                  dh_scr, dc_scr, dw_scr, live, hidden)

    @pl.when(s == n_t - 1)
    def _emit():
        dw_ref[0] = dw_scr[:].astype(dw_ref.dtype)


def _lstm_fwd_pallas(zx, w, block_n, interpret, save_residuals=True):
    """zx (T, N, 4H) scan-major, N a block_n multiple → (ys, c_seq,
    gates), all (T, N, ·). `save_residuals=False` (the inference-only
    primal — no vjp will consume them) emits just ys: pallas outputs
    are opaque to XLA DCE, so unwanted residuals would cost real HBM
    writes."""
    from jax.experimental.pallas import tpu as pltpu

    n_t, n, h4 = zx.shape
    hidden = h4 // 4
    blk = pl.BlockSpec((1, block_n, hidden), lambda b, t: (t, b, 0))
    blk4 = pl.BlockSpec((1, block_n, h4), lambda b, t: (t, b, 0))
    kernel = _lstm_fwd_kernel if save_residuals else _lstm_fwd_infer_kernel
    out = named_pallas_call(
        "fused_lstm_fwd",
        functools.partial(kernel, hidden=hidden),
        grid=(n // block_n, n_t),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        in_specs=[
            blk4,
            pl.BlockSpec((hidden, h4), lambda b, t: (0, 0)),
        ],
        out_specs=[blk, blk, blk4] if save_residuals else [blk],
        out_shape=(
            [jax.ShapeDtypeStruct((n_t, n, hidden), zx.dtype),
             jax.ShapeDtypeStruct((n_t, n, hidden), zx.dtype),
             jax.ShapeDtypeStruct((n_t, n, h4), zx.dtype)]
            if save_residuals
            else [jax.ShapeDtypeStruct((n_t, n, hidden), zx.dtype)]),
        scratch_shapes=[pltpu.VMEM((block_n, hidden), jnp.float32),
                        pltpu.VMEM((block_n, hidden), jnp.float32)],
        interpret=interpret,
    )(zx, w)
    return out if save_residuals else (out[0], None, None)


def _lstm_bwd_pallas(w, ys, c_seq, gates, dy, block_n, interpret):
    """Reversed sweep; prev-step (h, c) come from the saved sequences
    via shifted index maps (clamped at t=0 and zeroed in-kernel)."""
    from jax.experimental.pallas import tpu as pltpu

    n_t, n, h4 = gates.shape
    hidden = h4 // 4
    at_t = lambda b, s: (n_t - 1 - s, b, 0)
    at_prev = lambda b, s: (jnp.maximum(n_t - 2 - s, 0), b, 0)
    dzx, dw = named_pallas_call(
        "fused_lstm_bwd",
        functools.partial(_lstm_bwd_kernel, hidden=hidden, n_t=n_t),
        grid=(n // block_n, n_t),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        in_specs=[
            pl.BlockSpec((hidden, h4), lambda b, s: (0, 0)),       # w
            pl.BlockSpec((1, block_n, h4), at_t),                  # gates
            pl.BlockSpec((1, block_n, hidden), at_t),              # c
            pl.BlockSpec((1, block_n, hidden), at_prev),           # c_prev
            pl.BlockSpec((1, block_n, hidden), at_prev),           # h_prev
            pl.BlockSpec((1, block_n, hidden), at_t),              # dy
        ],
        out_specs=[
            pl.BlockSpec((1, block_n, h4), at_t),                  # dzx
            pl.BlockSpec((1, hidden, h4), lambda b, s: (b, 0, 0)),  # dw
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_t, n, h4), gates.dtype),
            jax.ShapeDtypeStruct((n // block_n, hidden, h4),
                                 jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_n, hidden), jnp.float32),
                        pltpu.VMEM((block_n, hidden), jnp.float32),
                        pltpu.VMEM((hidden, h4), jnp.float32)],
        interpret=interpret,
    )(w, gates, c_seq, c_seq, ys, dy)
    return dzx, dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _lstm_core(zx, w, cfg):
    # primal-only call (inference / no grad requested): skip residuals
    ys, _, _ = _lstm_fwd_pallas(zx, w, *cfg, save_residuals=False)
    return ys


def _lstm_core_fwd(zx, w, cfg):
    ys, c_seq, gates = _lstm_fwd_pallas(zx, w, *cfg)
    return ys, (w, ys, c_seq, gates)


def _lstm_core_bwd(cfg, res, dy):
    w, ys, c_seq, gates = res
    dzx, dw = _lstm_bwd_pallas(w, ys, c_seq, gates, dy, *cfg)
    return dzx, jnp.sum(dw, axis=0).astype(w.dtype)


_lstm_core.defvjp(_lstm_core_fwd, _lstm_core_bwd)


def _lstm_scan_xla(zx, w_hh):
    """`lax.scan` fallback/oracle — byte-for-byte the math of
    nn/recurrent.LSTM.step_precomputed."""
    n, n_t, h4 = zx.shape
    h = h4 // 4

    def body(carry, z_t):
        h_prev, c_prev = carry
        z = z_t + h_prev @ w_hh
        i, f, g, o = jnp.split(z, 4, axis=-1)
        c = jax.nn.sigmoid(f) * c_prev + jax.nn.sigmoid(i) * jnp.tanh(g)
        hy = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (hy, c), hy

    z0 = jnp.zeros((n, h), zx.dtype)
    _, ys = lax.scan(body, (z0, z0), jnp.swapaxes(zx, 0, 1))
    return jnp.swapaxes(ys, 0, 1)


def lstm_scan(zx: jax.Array, w_hh: jax.Array,
              impl: Optional[str] = None,
              block_n: Optional[int] = None) -> jax.Array:
    """Run the whole LSTM time loop in one persistent kernel.

    zx: (N, T, 4H) hoisted input projections INCLUDING bias (the
    `precompute_inputs` output); w_hh: (H, 4H) recurrent weight.
    Returns the hidden-state sequence (N, T, H). Differentiable wrt
    both args (custom_vjp on the kernel path).
    """
    n, n_t, h4 = zx.shape
    hidden = w_hh.shape[0]
    impl = resolve_impl(hidden, impl)
    if impl == "xla":
        return _lstm_scan_xla(zx, w_hh)
    n_pad, bn = _pad_batch(n, block_n, hidden)
    zx_t = jnp.swapaxes(zx, 0, 1)
    if n_pad != n:
        zx_t = jnp.pad(zx_t, ((0, 0), (0, n_pad - n), (0, 0)))
    ys = _lstm_core(zx_t, w_hh, (bn, impl == "interpret"))
    return jnp.swapaxes(ys[:, :n], 0, 1)


# --------------------------------------------------------------------------
# LSTM — fused bidirectional kernels (both directions, one launch)
# --------------------------------------------------------------------------

def _bilstm_fwd_kernel(zxf_ref, zxb_ref, wf_ref, wb_ref,
                       ysf_ref, cf_ref, gf_ref, ysb_ref, cb_ref, gb_ref,
                       hf_scr, cf_scr, hb_scr, cb_scr, *, hidden):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        for scr in (hf_scr, cf_scr, hb_scr, cb_scr):
            scr[:] = jnp.zeros_like(scr)

    # forward direction at time t; reverse direction at time T-1-t —
    # its blocks arrive/depart time-mirrored via the index maps, so
    # both advance one step per grid cell
    _lstm_fwd_dir(zxf_ref, wf_ref, ysf_ref, cf_ref, gf_ref, hf_scr,
                  cf_scr, hidden)
    _lstm_fwd_dir(zxb_ref, wb_ref, ysb_ref, cb_ref, gb_ref, hb_scr,
                  cb_scr, hidden)


def _bilstm_fwd_infer_kernel(zxf_ref, zxb_ref, wf_ref, wb_ref,
                             ysf_ref, ysb_ref,
                             hf_scr, cf_scr, hb_scr, cb_scr, *, hidden):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        for scr in (hf_scr, cf_scr, hb_scr, cb_scr):
            scr[:] = jnp.zeros_like(scr)

    _lstm_fwd_dir(zxf_ref, wf_ref, ysf_ref, None, None, hf_scr, cf_scr,
                  hidden)
    _lstm_fwd_dir(zxb_ref, wb_ref, ysb_ref, None, None, hb_scr, cb_scr,
                  hidden)


def _bilstm_bwd_kernel(wf_ref, wb_ref,
                       gf_ref, cf_ref, cpf_ref, hpf_ref, dyf_ref,
                       gb_ref, cb_ref, cpb_ref, hpb_ref, dyb_ref,
                       dzxf_ref, dzxb_ref, dwf_ref, dwb_ref,
                       dhf_scr, dcf_scr, dwf_scr,
                       dhb_scr, dcb_scr, dwb_scr, *, hidden, n_t):
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        for scr in (dhf_scr, dcf_scr, dwf_scr, dhb_scr, dcb_scr,
                    dwb_scr):
            scr[:] = jnp.zeros_like(scr)

    # fwd direction: backward sweep t = T-1-s; first step (zero
    # carry-in) is t == 0. bwd direction: ITS time runs u = T-1 → 0, so
    # its backward sweep is u = s, and its first step is u == T-1.
    live_f = jnp.where(s == n_t - 1, 0.0, 1.0)
    live_b = jnp.where(s == n_t - 1, 0.0, 1.0)
    _lstm_bwd_dir(wf_ref, gf_ref, cf_ref, cpf_ref, hpf_ref, dyf_ref,
                  dzxf_ref, dhf_scr, dcf_scr, dwf_scr, live_f, hidden)
    _lstm_bwd_dir(wb_ref, gb_ref, cb_ref, cpb_ref, hpb_ref, dyb_ref,
                  dzxb_ref, dhb_scr, dcb_scr, dwb_scr, live_b, hidden)

    @pl.when(s == n_t - 1)
    def _emit():
        dwf_ref[0] = dwf_scr[:].astype(dwf_ref.dtype)
        dwb_ref[0] = dwb_scr[:].astype(dwb_ref.dtype)


def _bilstm_fwd_pallas(zxf, zxb, wf, wb, block_n, interpret,
                       save_residuals=True):
    from jax.experimental.pallas import tpu as pltpu

    n_t, n, h4 = zxf.shape
    hidden = h4 // 4
    at_t = lambda b, t: (t, b, 0)
    at_rev = lambda b, t: (n_t - 1 - t, b, 0)
    w_spec = pl.BlockSpec((hidden, h4), lambda b, t: (0, 0))
    blk = lambda width: (1, block_n, width)
    ys_shape = jax.ShapeDtypeStruct((n_t, n, hidden), zxf.dtype)
    if save_residuals:
        kernel = _bilstm_fwd_kernel
        out_specs = [
            pl.BlockSpec(blk(hidden), at_t),    # ys_f
            pl.BlockSpec(blk(hidden), at_t),    # c_f
            pl.BlockSpec(blk(h4), at_t),        # gates_f
            pl.BlockSpec(blk(hidden), at_rev),  # ys_b (true-time slots)
            pl.BlockSpec(blk(hidden), at_rev),  # c_b
            pl.BlockSpec(blk(h4), at_rev),      # gates_b
        ]
        out_shape = [
            ys_shape, ys_shape,
            jax.ShapeDtypeStruct((n_t, n, h4), zxf.dtype),
            ys_shape, ys_shape,
            jax.ShapeDtypeStruct((n_t, n, h4), zxb.dtype),
        ]
    else:
        kernel = _bilstm_fwd_infer_kernel
        out_specs = [pl.BlockSpec(blk(hidden), at_t),
                     pl.BlockSpec(blk(hidden), at_rev)]
        out_shape = [ys_shape, ys_shape]
    out = named_pallas_call(
        "fused_lstm_bi_fwd",
        functools.partial(kernel, hidden=hidden),
        grid=(n // block_n, n_t),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        in_specs=[
            pl.BlockSpec(blk(h4), at_t),        # zx fwd
            pl.BlockSpec(blk(h4), at_rev),      # zx bwd (time-mirrored)
            w_spec, w_spec,
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((block_n, hidden), jnp.float32)
                        for _ in range(4)],
        interpret=interpret,
    )(zxf, zxb, wf, wb)
    if save_residuals:
        return out
    return out[0], None, None, out[1], None, None


def _bilstm_bwd_pallas(wf, wb, res_f, res_b, dyf, dyb, block_n,
                       interpret):
    from jax.experimental.pallas import tpu as pltpu

    ysf, cf, gf = res_f
    ysb, cb, gb = res_b
    n_t, n, h4 = gf.shape
    hidden = h4 // 4
    # fwd dir processes t = T-1-s (prev block at t-1, clamped); bwd dir
    # processes its sweep at true-time u = s (ITS prev step lives at
    # u+1, clamped)
    f_t = lambda b, s: (n_t - 1 - s, b, 0)
    f_prev = lambda b, s: (jnp.maximum(n_t - 2 - s, 0), b, 0)
    b_t = lambda b, s: (s, b, 0)
    b_prev = lambda b, s: (jnp.minimum(s + 1, n_t - 1), b, 0)
    w_spec = pl.BlockSpec((hidden, h4), lambda b, s: (0, 0))
    blk = lambda width: (1, block_n, width)
    dw_spec = pl.BlockSpec((1, hidden, h4), lambda b, s: (b, 0, 0))
    dw_shape = jax.ShapeDtypeStruct((n // block_n, hidden, h4),
                                    jnp.float32)
    dzxf, dzxb, dwf, dwb = named_pallas_call(
        "fused_lstm_bi_bwd",
        functools.partial(_bilstm_bwd_kernel, hidden=hidden, n_t=n_t),
        grid=(n // block_n, n_t),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        in_specs=[
            w_spec, w_spec,
            pl.BlockSpec(blk(h4), f_t),          # gates_f
            pl.BlockSpec(blk(hidden), f_t),      # c_f
            pl.BlockSpec(blk(hidden), f_prev),   # c_f prev
            pl.BlockSpec(blk(hidden), f_prev),   # h_f prev
            pl.BlockSpec(blk(hidden), f_t),      # dy_f
            pl.BlockSpec(blk(h4), b_t),          # gates_b
            pl.BlockSpec(blk(hidden), b_t),      # c_b
            pl.BlockSpec(blk(hidden), b_prev),   # c_b prev
            pl.BlockSpec(blk(hidden), b_prev),   # h_b prev
            pl.BlockSpec(blk(hidden), b_t),      # dy_b
        ],
        out_specs=[
            pl.BlockSpec(blk(h4), f_t),          # dzx_f
            pl.BlockSpec(blk(h4), b_t),          # dzx_b
            dw_spec, dw_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_t, n, h4), gf.dtype),
            jax.ShapeDtypeStruct((n_t, n, h4), gb.dtype),
            dw_shape, dw_shape,
        ],
        scratch_shapes=(
            [pltpu.VMEM((block_n, hidden), jnp.float32)] * 2
            + [pltpu.VMEM((hidden, h4), jnp.float32)]
            + [pltpu.VMEM((block_n, hidden), jnp.float32)] * 2
            + [pltpu.VMEM((hidden, h4), jnp.float32)]),
        interpret=interpret,
    )(wf, wb, gf, cf, cf, ysf, dyf, gb, cb, cb, ysb, dyb)
    return dzxf, dzxb, dwf, dwb


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _bilstm_core(zxf, zxb, wf, wb, cfg):
    # primal-only call (inference / no grad requested): skip residuals
    ysf, _, _, ysb, _, _ = _bilstm_fwd_pallas(zxf, zxb, wf, wb, *cfg,
                                              save_residuals=False)
    return ysf, ysb


def _bilstm_core_fwd(zxf, zxb, wf, wb, cfg):
    ysf, cf, gf, ysb, cb, gb = _bilstm_fwd_pallas(zxf, zxb, wf, wb,
                                                  *cfg)
    return (ysf, ysb), (wf, wb, (ysf, cf, gf), (ysb, cb, gb))


def _bilstm_core_bwd(cfg, res, dys):
    wf, wb, res_f, res_b = res
    dzxf, dzxb, dwf, dwb = _bilstm_bwd_pallas(wf, wb, res_f, res_b,
                                              dys[0], dys[1], *cfg)
    return (dzxf, dzxb, jnp.sum(dwf, axis=0).astype(wf.dtype),
            jnp.sum(dwb, axis=0).astype(wb.dtype))


_bilstm_core.defvjp(_bilstm_core_fwd, _bilstm_core_bwd)


def bilstm_scan(zx_f: jax.Array, zx_b: jax.Array, w_f: jax.Array,
                w_b: jax.Array, impl: Optional[str] = None,
                block_n: Optional[int] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """Both LSTM directions in ONE persistent launch.

    zx_f/zx_b: (N, T, 4H) hoisted projections of the SAME (unflipped)
    input through each direction's weights — the reverse direction's
    time mirroring happens inside via index maps, so the caller never
    pays a `jnp.flip`. Returns (ys_fwd, ys_bwd), BOTH in true time
    order (ys_bwd[t] is the reverse pass's state after consuming
    x[T-1..t]) — concatenate/add directly.
    """
    n, n_t, h4 = zx_f.shape
    hidden = w_f.shape[0]
    impl = resolve_impl(hidden, impl)
    if impl == "xla":
        ys_f = _lstm_scan_xla(zx_f, w_f)
        ys_b = jnp.flip(_lstm_scan_xla(jnp.flip(zx_b, axis=1), w_b),
                        axis=1)
        return ys_f, ys_b
    n_pad, bn = _pad_batch(n, block_n, hidden)
    zxf_t = jnp.swapaxes(zx_f, 0, 1)
    zxb_t = jnp.swapaxes(zx_b, 0, 1)
    if n_pad != n:
        pad = ((0, 0), (0, n_pad - n), (0, 0))
        zxf_t, zxb_t = jnp.pad(zxf_t, pad), jnp.pad(zxb_t, pad)
    ysf, ysb = _bilstm_core(zxf_t, zxb_t, w_f, w_b,
                            (bn, impl == "interpret"))
    return (jnp.swapaxes(ysf[:, :n], 0, 1),
            jnp.swapaxes(ysb[:, :n], 0, 1))


# --------------------------------------------------------------------------
# GRU — persistent kernel (uni-directional)
# --------------------------------------------------------------------------

def _gru_fwd_kernel(zg_ref, zc_ref, wg_ref, wc_ref, ys_ref, zr_ref,
                    cand_ref, h_scr, *, hidden):
    """zr_ref/cand_ref are None on the inference-only variant (see
    _lstm_fwd_dir)."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        h_scr[:] = jnp.zeros_like(h_scr)

    h_prev = h_scr[:]
    zr = jax.nn.sigmoid(zg_ref[0].astype(jnp.float32) + lax.dot_general(
        h_prev.astype(wg_ref.dtype), wg_ref[:],
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32))
    z = zr[:, :hidden]
    r = zr[:, hidden:]
    rh = r * h_prev
    cand = jnp.tanh(zc_ref[0].astype(jnp.float32) + lax.dot_general(
        rh.astype(wc_ref.dtype), wc_ref[:],
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32))
    h = (1.0 - z) * h_prev + z * cand
    h_scr[:] = h
    ys_ref[0] = h.astype(ys_ref.dtype)
    if zr_ref is not None:
        zr_ref[0] = zr.astype(zr_ref.dtype)
        cand_ref[0] = cand.astype(cand_ref.dtype)


def _gru_fwd_infer_kernel(zg_ref, zc_ref, wg_ref, wc_ref, ys_ref,
                          h_scr, *, hidden):
    _gru_fwd_kernel(zg_ref, zc_ref, wg_ref, wc_ref, ys_ref, None, None,
                    h_scr, hidden=hidden)


def _gru_bwd_kernel(wg_ref, wc_ref, zr_ref, cand_ref, hp_ref, dy_ref,
                    dzg_ref, dzc_ref, dwg_ref, dwc_ref,
                    dh_scr, dwg_scr, dwc_scr, *, hidden, n_t):
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        dh_scr[:] = jnp.zeros_like(dh_scr)
        dwg_scr[:] = jnp.zeros_like(dwg_scr)
        dwc_scr[:] = jnp.zeros_like(dwc_scr)

    live = jnp.where(s == n_t - 1, 0.0, 1.0)
    zr = zr_ref[0].astype(jnp.float32)
    z = zr[:, :hidden]
    r = zr[:, hidden:]
    cand = cand_ref[0].astype(jnp.float32)
    h_prev = hp_ref[0].astype(jnp.float32) * live
    dh = dy_ref[0].astype(jnp.float32) + dh_scr[:]
    dz = dh * (cand - h_prev)
    dcand_pre = dh * z * (1.0 - cand * cand)
    dh_prev = dh * (1.0 - z)
    dzc_ref[0] = dcand_pre.astype(dzc_ref.dtype)
    dcn = dcand_pre.astype(wc_ref.dtype)
    drh = lax.dot_general(dcn, wc_ref[:], (((1,), (1,)), ((), ())),
                          preferred_element_type=jnp.float32)
    dr = drh * h_prev
    dh_prev = dh_prev + drh * r
    dz_pre = dz * z * (1.0 - z)
    dr_pre = dr * r * (1.0 - r)
    dzr = jnp.concatenate([dz_pre, dr_pre], axis=-1)
    dzg_ref[0] = dzr.astype(dzg_ref.dtype)
    dzrn = dzr.astype(wg_ref.dtype)
    dh_scr[:] = dh_prev + lax.dot_general(
        dzrn, wg_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    hpn = h_prev.astype(wg_ref.dtype)
    dwg_scr[:] = dwg_scr[:] + lax.dot_general(
        hpn, dzrn, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dwc_scr[:] = dwc_scr[:] + lax.dot_general(
        (r * h_prev).astype(wc_ref.dtype), dcn,
        (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(s == n_t - 1)
    def _emit():
        dwg_ref[0] = dwg_scr[:].astype(dwg_ref.dtype)
        dwc_ref[0] = dwc_scr[:].astype(dwc_ref.dtype)


def _gru_fwd_pallas(zg, zc, wg, wc, block_n, interpret,
                    save_residuals=True):
    from jax.experimental.pallas import tpu as pltpu

    n_t, n, h2 = zg.shape
    hidden = h2 // 2
    at_t = lambda b, t: (t, b, 0)
    blk = pl.BlockSpec((1, block_n, hidden), at_t)
    blk2 = pl.BlockSpec((1, block_n, h2), at_t)
    ys_shape = jax.ShapeDtypeStruct((n_t, n, hidden), zg.dtype)
    kernel = _gru_fwd_kernel if save_residuals else _gru_fwd_infer_kernel
    out = named_pallas_call(
        "fused_gru_fwd",
        functools.partial(kernel, hidden=hidden),
        grid=(n // block_n, n_t),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        in_specs=[
            blk2,
            blk,
            pl.BlockSpec((hidden, h2), lambda b, t: (0, 0)),
            pl.BlockSpec((hidden, hidden), lambda b, t: (0, 0)),
        ],
        out_specs=[blk, blk2, blk] if save_residuals else [blk],
        out_shape=(
            [ys_shape, jax.ShapeDtypeStruct((n_t, n, h2), zg.dtype),
             ys_shape] if save_residuals else [ys_shape]),
        scratch_shapes=[pltpu.VMEM((block_n, hidden), jnp.float32)],
        interpret=interpret,
    )(zg, zc, wg, wc)
    return out if save_residuals else (out[0], None, None)


def _gru_bwd_pallas(wg, wc, ys, zr_seq, cand_seq, dy, block_n,
                    interpret):
    from jax.experimental.pallas import tpu as pltpu

    n_t, n, h2 = zr_seq.shape
    hidden = h2 // 2
    at_t = lambda b, s: (n_t - 1 - s, b, 0)
    at_prev = lambda b, s: (jnp.maximum(n_t - 2 - s, 0), b, 0)
    return named_pallas_call(
        "fused_gru_bwd",
        functools.partial(_gru_bwd_kernel, hidden=hidden, n_t=n_t),
        grid=(n // block_n, n_t),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        in_specs=[
            pl.BlockSpec((hidden, h2), lambda b, s: (0, 0)),
            pl.BlockSpec((hidden, hidden), lambda b, s: (0, 0)),
            pl.BlockSpec((1, block_n, h2), at_t),                # zr
            pl.BlockSpec((1, block_n, hidden), at_t),            # cand
            pl.BlockSpec((1, block_n, hidden), at_prev),         # h_prev
            pl.BlockSpec((1, block_n, hidden), at_t),            # dy
        ],
        out_specs=[
            pl.BlockSpec((1, block_n, h2), at_t),                # dzg
            pl.BlockSpec((1, block_n, hidden), at_t),            # dzc
            pl.BlockSpec((1, hidden, h2), lambda b, s: (b, 0, 0)),
            pl.BlockSpec((1, hidden, hidden), lambda b, s: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_t, n, h2), zr_seq.dtype),
            jax.ShapeDtypeStruct((n_t, n, hidden), zr_seq.dtype),
            jax.ShapeDtypeStruct((n // block_n, hidden, h2),
                                 jnp.float32),
            jax.ShapeDtypeStruct((n // block_n, hidden, hidden),
                                 jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_n, hidden), jnp.float32),
                        pltpu.VMEM((hidden, h2), jnp.float32),
                        pltpu.VMEM((hidden, hidden), jnp.float32)],
        interpret=interpret,
    )(wg, wc, zr_seq, cand_seq, ys, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _gru_core(zg, zc, wg, wc, cfg):
    # primal-only call (inference / no grad requested): skip residuals
    ys, _, _ = _gru_fwd_pallas(zg, zc, wg, wc, *cfg,
                               save_residuals=False)
    return ys


def _gru_core_fwd(zg, zc, wg, wc, cfg):
    ys, zr_seq, cand_seq = _gru_fwd_pallas(zg, zc, wg, wc, *cfg)
    return ys, (wg, wc, ys, zr_seq, cand_seq)


def _gru_core_bwd(cfg, res, dy):
    wg, wc, ys, zr_seq, cand_seq = res
    dzg, dzc, dwg, dwc = _gru_bwd_pallas(wg, wc, ys, zr_seq, cand_seq,
                                         dy, *cfg)
    return (dzg, dzc, jnp.sum(dwg, axis=0).astype(wg.dtype),
            jnp.sum(dwc, axis=0).astype(wc.dtype))


_gru_core.defvjp(_gru_core_fwd, _gru_core_bwd)


def _gru_scan_xla(zg, zc, wg, wc):
    """`lax.scan` fallback/oracle — the math of
    nn/recurrent.GRU.step_precomputed."""
    n, n_t, h2 = zg.shape
    h = h2 // 2

    def body(carry, z_t):
        zg_t, zc_t = z_t
        zr = jax.nn.sigmoid(zg_t + carry @ wg)
        z, r = zr[:, :h], zr[:, h:]
        cand = jnp.tanh(zc_t + (r * carry) @ wc)
        h_new = (1.0 - z) * carry + z * cand
        return h_new, h_new

    h0 = jnp.zeros((n, h), zg.dtype)
    _, ys = lax.scan(body, h0, (jnp.swapaxes(zg, 0, 1),
                                jnp.swapaxes(zc, 0, 1)))
    return jnp.swapaxes(ys, 0, 1)


def gru_scan(zx_gates: jax.Array, zx_cand: jax.Array, w_g: jax.Array,
             w_c: jax.Array, impl: Optional[str] = None,
             block_n: Optional[int] = None) -> jax.Array:
    """Persistent GRU scan. zx_gates: (N, T, 2H) hoisted (z, r) gate
    projections (+bias); zx_cand: (N, T, H) hoisted candidate
    projection (+bias); w_g: (H, 2H); w_c: (H, H). Returns (N, T, H)."""
    n, n_t, h2 = zx_gates.shape
    hidden = w_g.shape[0]
    impl = resolve_impl(hidden, impl)
    if impl == "xla":
        return _gru_scan_xla(zx_gates, zx_cand, w_g, w_c)
    n_pad, bn = _pad_batch(n, block_n, hidden)
    zg_t = jnp.swapaxes(zx_gates, 0, 1)
    zc_t = jnp.swapaxes(zx_cand, 0, 1)
    if n_pad != n:
        pad = ((0, 0), (0, n_pad - n), (0, 0))
        zg_t, zc_t = jnp.pad(zg_t, pad), jnp.pad(zc_t, pad)
    ys = _gru_core(zg_t, zc_t, w_g, w_c, (bn, impl == "interpret"))
    return jnp.swapaxes(ys[:, :n], 0, 1)
