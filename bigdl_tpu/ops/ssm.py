"""The Mamba-2 state-space recurrence (Dao & Gu, arXiv:2405.21060), as a
serving model needs it: the whole of a padded prompt at once, and one
token for every slot.

No reference counterpart (the reference has no recurrent state space
layer). Plain `jax.numpy`; `models/hybrid_ssm.py` is the caller.

With H heads of P numbers, a state of N numbers a head number (ONE group:
B and C are shared by the heads), t the position:

    h_t = exp(Delta_t A) h_{t-1} + Delta_t * x_t (x) B_t     h: (H, P, N)
    y_t = h_t C_t + D * x_t

`ssd_chunked` computes every y_t of a sequence whose length is a multiple
of the chunk, and the state after a GIVEN position `keep`: inside a chunk
the quadratic form (C B^T masked by the decay between two positions, times
x), between chunks the state handed on. Every decay is the exponential of
a DIFFERENCE of the running sum of Delta A inside the chunk, in float32:
never a product of many factors, and never a positive exponent (A < 0,
Delta >= 0, and the difference is taken later minus earlier). Positions
past `keep` get Delta = 0: their decay is 1 and their input nothing, so
the state that comes out is the one after position `keep` whatever the
padding behind it (`keep` = -1: zeros), and the outputs past `keep` are
finite numbers nobody reads.

`conv_taps` cuts out what the depthwise convolution before the recurrence
has to remember of a prompt, `causal_conv` is that convolution over a
sequence, and `ssm_step` is one token of both for every slot.

Precision: `dtype` is the matmul operands' (the weights' dtype in the
caller); accumulation, the running sums, the decays and the state are
float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["causal_conv", "conv_taps", "ssd_chunked", "ssm_step"]


def causal_conv(xbc, w, bias):
    """The depthwise causal convolution and its silu: xbc (T, C) float32
    from position 0 (rows before it are zero), w (K, C) with tap j on the
    row K - 1 - j back, bias (C,) → (T, C) float32. K shifted adds."""
    t, k = xbc.shape[0], w.shape[0]
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    out = bias + sum(w[j] * padded[j:j + t] for j in range(k))
    return jax.nn.silu(out)


def conv_taps(xbc, keep, taps: int):
    """What a slot remembers of a prompt for the convolution: the rows
    (before the convolution) at positions keep - taps + 1 .. keep of xbc
    (T, C), zeros before position 0 → (taps, C). `keep` may be traced;
    -1 gives zeros."""
    padded = jnp.pad(xbc, ((taps, 0), (0, 0)))
    return jax.lax.dynamic_slice_in_dim(padded, keep + 1, taps)


def ssd_chunked(x, dt, a, b, c, d, keep, chunk: int, dtype=jnp.float32):
    """x (T, H, P), dt (T, H) (Delta, after its softplus), a (H,) negative,
    b and c (T, N), d (H,), all float32, T a multiple of `chunk`; `keep`
    an int32 scalar (may be traced) → (y (T, H, P) float32, the state
    (H, P, N) float32 after position `keep`). See the module docstring."""
    t, h, p = x.shape
    n = b.shape[-1]
    if t % chunk:
        raise ValueError(f"{t} positions are no whole chunks of {chunk}")
    nc, f32 = t // chunk, jnp.float32
    dt = jnp.where(jnp.arange(t)[:, None] <= keep, dt, 0.0)
    # chunk-major, heads before positions: (nc, H, Q)
    dtc = dt.reshape(nc, chunk, h).transpose(0, 2, 1)
    run = jnp.cumsum(dtc * a[None, :, None], axis=-1)   # sum of Delta A, <= 0
    xc = x.reshape(nc, chunk, h, p).transpose(0, 2, 1, 3)   # (nc, H, Q, P)
    bc = b.reshape(nc, chunk, n).astype(dtype)
    cc = c.reshape(nc, chunk, n).astype(dtype)

    # inside a chunk: y_t += sum_{s <= t} exp(run_t - run_s) Delta_s
    # (C_t . B_s) x_s
    cb = jnp.einsum("ctn,csn->cts", cc, bc, preferred_element_type=f32)
    later = jnp.tril(jnp.ones((chunk, chunk), bool))
    between = jnp.where(later, run[..., :, None] - run[..., None, :],
                        -jnp.inf)                           # (nc, H, Q, Q)
    weigh = jnp.exp(between) * cb[:, None] * dtc[:, :, None, :]
    y = jnp.einsum("chts,chsp->chtp", weigh.astype(dtype), xc.astype(dtype),
                   preferred_element_type=f32)

    # what a chunk adds to the state by its end, and how much of the state
    # before it is left by then
    to_end = jnp.exp(run[..., -1:] - run) * dtc             # (nc, H, Q)
    adds = jnp.einsum("chsp,csn->chpn",
                      (to_end[..., None] * xc).astype(dtype), bc,
                      preferred_element_type=f32)           # (nc, H, P, N)
    left = jnp.exp(run[..., -1])                            # (nc, H)

    def hand_on(state, chunk_):
        left_, adds_ = chunk_
        return left_[:, None, None] * state + adds_, state

    state, entering = jax.lax.scan(
        hand_on, jnp.zeros((h, p, n), f32), (left, adds))
    # the state a chunk entered with, decayed to each of its positions
    y = y + jnp.exp(run)[..., None] * jnp.einsum(
        "chpn,ctn->chtp", entering.astype(dtype), cc,
        preferred_element_type=f32)
    y = y.transpose(0, 2, 1, 3).reshape(t, h, p)
    return y + d[:, None] * x, state


def ssm_step(h, taps, xbc, dt, w, bias, a, d, seated):
    """One token for every slot. h (B, H, P, N) float32 and taps
    (B, K - 1, C) the slots' state (the last K - 1 rows before the
    convolution, oldest first), xbc (B, C) float32 this token's row
    before the convolution, dt (B, H) its Delta, w (K, C) and bias (C,)
    the convolution's, a and d (H,), seated (B,) bool → (y (B, H, P)
    float32, the new h, the new taps). A slot that is not seated keeps
    the bits of both leaves."""
    b_, heads, p, n = h.shape
    inner = heads * p
    window = jnp.concatenate(
        [taps.astype(jnp.float32), xbc[:, None]], 1)        # (B, K, C)
    conv = jax.nn.silu(bias + jnp.sum(w[None] * window, 1))
    x = conv[:, :inner].reshape(b_, heads, p)
    bt, ct = conv[:, inner:inner + n], conv[:, inner + n:]
    new = jnp.exp(dt * a)[:, :, None, None] * h \
        + (dt[:, :, None] * x)[..., None] * bt[:, None, None, :]
    y = jnp.sum(new * ct[:, None, None, :], -1) + d[:, None] * x
    return (y, jnp.where(seated[:, None, None, None], new, h),
            jnp.where(seated[:, None, None], window[:, 1:].astype(taps.dtype),
                      taps))
