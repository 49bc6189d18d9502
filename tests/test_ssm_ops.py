"""`ops/ssm.py` alone: the chunked Mamba-2 scan against the recurrence run
a position at a time (a plain `lax.scan`, written here, independent of the
module), the state after a GIVEN position of a padded bucket, the taps the
convolution remembers, and the one-token step against the same recurrence.

float32 on the CPU; the two forms differ by the order of summation only:

  TIGHT = 2e-5 RELATIVE to the largest number compared (measured under
  2e-6 over the cases below, decays down to exp(-40) among them): ten
  times the rounding seen, and a hundredth of what one dropped term (a
  chunk's hand-over, the D skip, a mask off by one position) would move.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.ops.ssm import causal_conv, conv_taps, ssd_chunked, ssm_step

TIGHT = 2e-5
H, P, N = 4, 8, 16


def _inputs(t, seed=0, dt_scale=1.0, a_scale=1.0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(k[0], (t, H, P))
    # Delta from 1e-3 to 1 and beyond: the bias's range and far past it
    dt = jnp.exp(jax.random.uniform(k[1], (t, H), minval=np.log(1e-3),
                                    maxval=0.0)) * dt_scale
    a = -jax.random.uniform(k[2], (H,), minval=1.0, maxval=16.0) * a_scale
    b = jax.random.normal(k[3], (t, N))
    c = jax.random.normal(k[4], (t, N))
    d = jax.random.normal(k[5], (H,))
    return x, dt, a, b, c, d


def _plain(x, dt, a, b, c, d, keep):
    """Every y_t and the state after position `keep`, a position at a
    time."""
    def step(carry, row):
        h, kept = carry
        t, x_t, dt_t, b_t, c_t = row
        h = jnp.exp(dt_t * a)[:, None, None] * h \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t
        kept = jnp.where(t == keep, h, kept)
        return (h, kept), jnp.sum(h * c_t, -1) + d[:, None] * x_t

    zero = jnp.zeros((H, P, N))
    (_, kept), y = jax.lax.scan(
        step, (zero, zero), (jnp.arange(x.shape[0]), x, dt, b, c))
    return y, kept


def _gap(got, want):
    return float(jnp.max(jnp.abs(got - want))
                 / jnp.maximum(jnp.max(jnp.abs(want)), 1e-30))


@pytest.mark.parametrize("keep", [-1, 0, 6, 7, 8, 22, 23, 30, 31])
def test_chunked_scan_equals_the_plain_recurrence_up_to_keep(keep):
    """The outputs up to `keep` and the state after it, for `keep` before
    the sequence (-1: zeros), at its first position, at a chunk's last and
    the next chunk's first position, and at the bucket's end."""
    args = _inputs(32)
    y, h = ssd_chunked(*args, jnp.int32(keep), 8)
    want_y, want_h = _plain(*args, keep)
    if keep >= 0:
        assert _gap(y[:keep + 1], want_y[:keep + 1]) < TIGHT
        assert _gap(h, want_h) < TIGHT
        assert float(jnp.abs(h).max()) > 0.1
    else:
        assert not np.asarray(h).any()
    assert np.isfinite(np.asarray(y)).all()     # past `keep`: nobody reads


def test_positions_past_keep_do_not_move_the_state():
    """The same prompt in two buckets, and with other tokens behind it:
    the state is the one after `keep`, bit for bit in the same bucket."""
    x, dt, a, b, c, d = _inputs(32)
    _, h = ssd_chunked(x, dt, a, b, c, d, jnp.int32(10), 8)
    other = _inputs(32, seed=9)
    mixed = [jnp.concatenate([u[:11], v[11:]]) for u, v in
             zip((x, dt, b, c), (other[0], other[1], other[3], other[4]))]
    _, h2 = ssd_chunked(mixed[0], mixed[1], a, mixed[2], mixed[3], d,
                        jnp.int32(10), 8)
    np.testing.assert_array_equal(np.asarray(h), np.asarray(h2))
    _, h3 = ssd_chunked(x[:16], dt[:16], a, b[:16], c[:16], d,
                        jnp.int32(10), 8)
    assert _gap(h3, h) < TIGHT
    # with the mask taken out (the bucket's end instead of `keep`) the
    # state is another: what the bucket-end control of PERF.md reads
    _, end = ssd_chunked(x, dt, a, b, c, d, jnp.int32(31), 8)
    assert _gap(end, h) > 0.1


@pytest.mark.parametrize("dt_scale,a_scale", [(1.0, 1.0), (2.5, 1.0),
                                              (10.0, 4.0), (0.0, 1.0)])
def test_decays_down_to_exp_minus_forty_and_a_delta_of_zero(dt_scale,
                                                            a_scale):
    """Delta A down to -40 a position and -600 or so over a chunk: every
    decay is the exponential of a difference taken later minus earlier, so
    nothing overflows and nothing is NaN; Delta = 0 everywhere leaves the
    state zero and y = D x."""
    args = _inputs(32, seed=3, dt_scale=dt_scale, a_scale=a_scale)
    x, dt, a = args[0], args[1], args[2]
    if dt_scale:
        least = float(jnp.min(dt * a))
        assert least < {1.0: -4, 2.5: -10, 10.0: -40}[dt_scale]
    y, h = ssd_chunked(*args, jnp.int32(31), 8)
    want_y, want_h = _plain(*args, 31)
    assert np.isfinite(np.asarray(y)).all() and np.isfinite(
        np.asarray(h)).all()
    assert _gap(y, want_y) < TIGHT
    if dt_scale:
        assert _gap(h, want_h) < TIGHT
    else:
        assert not np.asarray(h).any()
        np.testing.assert_allclose(y, args[5][:, None] * x, rtol=1e-6)


def test_a_length_that_is_no_whole_chunks_is_refused():
    with pytest.raises(ValueError, match="whole chunks"):
        ssd_chunked(*_inputs(12), jnp.int32(3), 8)


@pytest.mark.parametrize("keep", [-1, 0, 1, 2, 9])
def test_the_taps_are_the_rows_that_end_at_keep(keep):
    xbc = jnp.arange(1.0, 41.0).reshape(10, 4)
    taps = np.asarray(conv_taps(xbc, jnp.int32(keep), 3))
    want = np.zeros((3, 4), np.float32)
    for j in range(3):
        t = keep - 2 + j
        if t >= 0:
            want[j] = np.asarray(xbc[t])
    np.testing.assert_array_equal(taps, want)


def test_the_step_continues_the_sequence():
    """A prompt through `causal_conv` + `ssd_chunked` up to `keep`, then
    `ssm_step` a token at a time from the state and taps it returned, is
    the sequence run whole; a slot that is not seated keeps its bits."""
    t, k, keep = 16, 4, 9
    inner, c_w = H * P, H * P + 2 * N
    keys = jax.random.split(jax.random.PRNGKey(1), 5)
    xbc = jax.random.normal(keys[0], (t, c_w))
    w = jax.random.uniform(keys[1], (k, c_w), minval=-0.5, maxval=0.5)
    bias = jax.random.uniform(keys[2], (c_w,), minval=-0.5, maxval=0.5)
    dt = jnp.exp(jax.random.uniform(keys[3], (t, H), minval=-6., maxval=0.))
    a = -jax.random.uniform(keys[4], (H,), minval=1.0, maxval=16.0)
    d = jnp.ones((H,))

    def split(conv):
        return (conv[:, :inner].reshape(-1, H, P), conv[:, inner:inner + N],
                conv[:, inner + N:])

    conv = causal_conv(xbc, w, bias)
    # the convolution is its definition: tap j on the row K - 1 - j back
    padded = np.concatenate([np.zeros((k - 1, c_w), np.float32),
                             np.asarray(xbc)])
    want_conv = jax.nn.silu(bias + sum(
        np.asarray(w)[j] * padded[j:j + t] for j in range(k)))
    np.testing.assert_allclose(conv, want_conv, atol=1e-6)
    x, b, c = split(conv)
    want_y, _ = _plain(x, dt, a, b, c, d, t - 1)
    _, h = ssd_chunked(x, dt, a, b, c, d, jnp.int32(keep), 8)
    taps = conv_taps(xbc, jnp.int32(keep), k - 1)
    # slot 1 is the sequence's; slot 0 is not seated and holds a marker
    hs = jnp.stack([jnp.full_like(h, 7.0), h])
    tapss = jnp.stack([jnp.full_like(taps, 3.0), taps])
    seated = jnp.asarray([False, True])
    for pos in range(keep + 1, t):
        y, hs, tapss = ssm_step(hs, tapss, jnp.stack([xbc[pos]] * 2),
                                jnp.stack([dt[pos]] * 2), w, bias, a, d,
                                seated)
        assert _gap(y[1], want_y[pos]) < TIGHT
    assert (np.asarray(hs[0]) == 7.0).all()
    assert (np.asarray(tapss[0]) == 3.0).all()
    np.testing.assert_array_equal(np.asarray(tapss[1]),
                                  np.asarray(xbc[t - 3:]))
