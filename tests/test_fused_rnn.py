"""Persistent-RNN fused scan kernel parity (ops/fused_rnn.py).

CPU tier-1 coverage for the Mosaic kernels via Pallas interpret mode
(the flash-attention testing convention): forward AND gradients against
the `lax.scan` fallback (the exact math the kernel replaces) and the
torch oracle, in fp32 and bf16. The kernels' grid/index-map machinery
runs unchanged under interpret — only the Mosaic lowering itself needs
the real chip (chip_smoke.py, kernel leg)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.ops import fused_rnn

KEY = jax.random.PRNGKey(0)


def _rand(rng, *shape, dtype=np.float32, scale=1.0):
    return jnp.asarray((scale * rng.randn(*shape)).astype(dtype))


class TestLSTMScan:
    @pytest.mark.parametrize("n,t,h,block_n", [
        (4, 6, 8, None),      # single tile
        (5, 7, 8, 4),         # odd batch → sublane padding
        (32, 5, 8, 16),       # genuine multi-tile grid (n//block_n = 2)
        (3, 1, 8, None),      # T == 1 edge (init and emit same step)
    ])
    def test_fwd_matches_xla(self, n, t, h, block_n):
        rng = np.random.RandomState(0)
        zx = _rand(rng, n, t, 4 * h)
        w = _rand(rng, h, 4 * h, scale=0.3)
        out = fused_rnn.lstm_scan(zx, w, impl="interpret",
                                  block_n=block_n)
        ref = fused_rnn._lstm_scan_xla(zx, w)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("n,block_n", [
        (5, 4),    # padding
        (32, 16),  # multi-tile grid: per-tile dW emission + sum
    ])
    def test_grads_match_xla(self, n, block_n):
        rng = np.random.RandomState(1)
        zx = _rand(rng, n, 6, 32)
        w = _rand(rng, 8, 32, scale=0.3)

        def loss(fn):
            return lambda zx, w: jnp.sum(jnp.sin(fn(zx, w)))

        gk = jax.grad(loss(lambda zx, w: fused_rnn.lstm_scan(
            zx, w, impl="interpret", block_n=block_n)),
            argnums=(0, 1))(zx, w)
        gr = jax.grad(loss(fused_rnn._lstm_scan_xla),
                      argnums=(0, 1))(zx, w)
        for a, b in zip(gk, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)

    def test_bf16_close_to_fp32_oracle(self):
        """bf16 kernel vs the fp32 scan: agreement within bf16
        resolution (the training path's dtype)."""
        rng = np.random.RandomState(2)
        zx = _rand(rng, 4, 5, 32)
        w = _rand(rng, 8, 32, scale=0.3)
        out = fused_rnn.lstm_scan(zx.astype(jnp.bfloat16),
                                  w.astype(jnp.bfloat16),
                                  impl="interpret")
        ref = fused_rnn._lstm_scan_xla(zx, w)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref), rtol=0.05, atol=0.05)
        g = jax.grad(lambda z: jnp.sum(fused_rnn.lstm_scan(
            z, w.astype(jnp.bfloat16), impl="interpret")))(
                zx.astype(jnp.bfloat16))
        gr = jax.grad(lambda z: jnp.sum(
            fused_rnn._lstm_scan_xla(z, w)))(zx)
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(gr), rtol=0.1, atol=0.1)

    def test_wired_recurrent_matches_torch(self):
        """The full hoisted LSTM path through Recurrent with the fused
        kernel forced (interpret) against torch.nn.LSTM — the same
        oracle as test_recurrent.test_lstm_matches_torch."""
        torch = pytest.importorskip("torch")
        m = nn.Recurrent(nn.LSTM(3, 4), fused="interpret").build(KEY)
        m = m.evaluate()
        p = m.variables["params"]["cell"]
        w = np.asarray(p["weight"])  # (3+4, 4*4) order i,f,g,o
        b = np.asarray(p["bias"])
        x = np.random.RandomState(0).randn(2, 6, 3).astype(np.float32)
        ours = np.asarray(m.forward(jnp.asarray(x)))

        ref = torch.nn.LSTM(3, 4, batch_first=True)
        with torch.no_grad():
            ref.weight_ih_l0.copy_(torch.tensor(w[:3].T))
            ref.weight_hh_l0.copy_(torch.tensor(w[3:].T))
            ref.bias_ih_l0.copy_(torch.tensor(b))
            ref.bias_hh_l0.zero_()
        out, _ = ref(torch.tensor(x))
        np.testing.assert_allclose(ours, out.detach().numpy(),
                                   rtol=1e-4, atol=1e-5)


class TestBiLSTMScan:
    @staticmethod
    def _ref(zxf, zxb, wf, wb):
        ys_f = fused_rnn._lstm_scan_xla(zxf, wf)
        ys_b = jnp.flip(fused_rnn._lstm_scan_xla(
            jnp.flip(zxb, axis=1), wb), axis=1)
        return ys_f, ys_b

    def test_fwd_matches_flip_scan(self):
        rng = np.random.RandomState(3)
        zxf, zxb = (_rand(rng, 4, 6, 32) for _ in range(2))
        wf, wb = (_rand(rng, 8, 32, scale=0.3) for _ in range(2))
        yf, yb = fused_rnn.bilstm_scan(zxf, zxb, wf, wb,
                                       impl="interpret")
        rf, rb = self._ref(zxf, zxb, wf, wb)
        np.testing.assert_allclose(np.asarray(yf), np.asarray(rf),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(yb), np.asarray(rb),
                                   rtol=1e-5, atol=1e-6)
        # the xla fallback branch (what chip_smoke.py oracles the chip
        # against) must itself match this independent flip-scan oracle
        ff, fb = fused_rnn.bilstm_scan(zxf, zxb, wf, wb, impl="xla")
        np.testing.assert_allclose(np.asarray(ff), np.asarray(rf),
                                   rtol=1e-6)
        np.testing.assert_allclose(np.asarray(fb), np.asarray(rb),
                                   rtol=1e-6)

    def test_grads_match_flip_scan(self):
        rng = np.random.RandomState(4)
        args = (_rand(rng, 3, 5, 32), _rand(rng, 3, 5, 32),
                _rand(rng, 8, 32, scale=0.3),
                _rand(rng, 8, 32, scale=0.3))

        def loss(fn):
            def f(*a):
                yf, yb = fn(*a)
                return jnp.sum(jnp.sin(yf)) + jnp.sum(jnp.cos(yb))
            return f

        gk = jax.grad(loss(lambda *a: fused_rnn.bilstm_scan(
            *a, impl="interpret")), argnums=(0, 1, 2, 3))(*args)
        gr = jax.grad(loss(self._ref), argnums=(0, 1, 2, 3))(*args)
        for a, b in zip(gk, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)

    def test_wired_birecurrent_one_launch(self):
        """BiRecurrent with fused='interpret' takes the one-launch path
        and matches the lax.scan BiRecurrent exactly."""
        rng = np.random.RandomState(5)
        x = jnp.asarray(rng.randn(2, 7, 5).astype(np.float32))
        base = nn.BiRecurrent(nn.LSTM(5, 6), fused=False)
        v = base.init(jax.random.PRNGKey(7))
        ref, _ = base.apply(v, x)
        m = nn.BiRecurrent(nn.LSTM(5, 6), fused="interpret")
        got = m._fused_bidir(v, x)
        assert got is not None, "fused bidirectional path not taken"
        out, _ = m.apply(v, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)


class TestGRUScan:
    @staticmethod
    def _args(rng, n=4, t=6, h=8):
        return (_rand(rng, n, t, 2 * h), _rand(rng, n, t, h),
                _rand(rng, h, 2 * h, scale=0.3),
                _rand(rng, h, h, scale=0.3))

    def test_fwd_matches_xla(self):
        args = self._args(np.random.RandomState(6))
        out = fused_rnn.gru_scan(*args, impl="interpret")
        ref = fused_rnn._gru_scan_xla(*args)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)

    def test_grads_match_xla(self):
        args = self._args(np.random.RandomState(7))

        def loss(fn):
            return lambda *a: jnp.sum(jnp.sin(fn(*a)))

        gk = jax.grad(loss(lambda *a: fused_rnn.gru_scan(
            *a, impl="interpret")), argnums=(0, 1, 2, 3))(*args)
        gr = jax.grad(loss(fused_rnn._gru_scan_xla),
                      argnums=(0, 1, 2, 3))(*args)
        for a, b in zip(gk, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)

    def test_wired_recurrent_matches_scan(self):
        """Recurrent(GRU, fused='interpret') == the lax.scan GRU path
        (which test_recurrent oracles against numpy)."""
        rng = np.random.RandomState(8)
        x = jnp.asarray(rng.randn(2, 5, 3).astype(np.float32))
        base = nn.Recurrent(nn.GRU(3, 4), fused=False)
        v = base.init(jax.random.PRNGKey(9))
        ref, _ = base.apply(v, x)
        m = nn.Recurrent(nn.GRU(3, 4), fused="interpret")
        out, _ = m.apply(v, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)


def test_bench_shape_sweep_interpret():
    """The bench.py BiLSTM hidden size (H=128) through the kernel at
    several batch tiles (~2 s interpreted — cheap enough for tier-1);
    the on-chip counterpart is chip_smoke.py's kernel leg."""
    rng = np.random.RandomState(0)
    h = 128
    zxf, zxb = (_rand(rng, 8, 16, 4 * h, scale=0.1) for _ in range(2))
    wf, wb = (_rand(rng, h, 4 * h, scale=0.05) for _ in range(2))
    rf = fused_rnn._lstm_scan_xla(zxf, wf)
    rb = jnp.flip(fused_rnn._lstm_scan_xla(jnp.flip(zxb, axis=1), wb),
                  axis=1)
    for bn in (8, 16):
        yf, yb = fused_rnn.bilstm_scan(zxf, zxb, wf, wb,
                                       impl="interpret", block_n=bn)
        np.testing.assert_allclose(np.asarray(yf), np.asarray(rf),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(yb), np.asarray(rb),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.slow
def test_full_bench_shape_interpret():
    """The FULL bench.py BiLSTM shape (B=128, T=128, H=128) through the
    fused bidirectional kernel + backward in interpret mode — genuinely
    long on one CPU core, so tier-2 (`-m slow`): run before trusting a
    kernel change enough to burn a TPU measurement session on it."""
    rng = np.random.RandomState(0)
    h = 128
    zxf, zxb = (_rand(rng, 128, 128, 4 * h, scale=0.05)
                for _ in range(2))
    wf, wb = (_rand(rng, h, 4 * h, scale=0.02) for _ in range(2))

    def loss(fn):
        def f(*a):
            yf, yb = fn(*a)
            return jnp.sum(jnp.sin(yf)) + jnp.sum(jnp.cos(yb))
        return f

    def ref(zxf, zxb, wf, wb):
        return (fused_rnn._lstm_scan_xla(zxf, wf),
                jnp.flip(fused_rnn._lstm_scan_xla(
                    jnp.flip(zxb, axis=1), wb), axis=1))

    gk = jax.grad(loss(lambda *a: fused_rnn.bilstm_scan(
        *a, impl="interpret")), argnums=(0, 2))(zxf, zxb, wf, wb)
    gr = jax.grad(loss(ref), argnums=(0, 2))(zxf, zxb, wf, wb)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


class TestDispatch:
    def test_auto_resolves_to_xla_off_tpu(self):
        # CPU test env: auto must pick the scan fallback, kernels only
        # by explicit request — the default model path is unchanged
        assert fused_rnn.resolve_impl(128) == "xla"

    def test_ineligible_hidden_sizes(self):
        for h in (96, 2048):  # not lane-tileable / over VMEM budget
            assert fused_rnn.resolve_impl(h, None) == "xla"
        # explicit impl is honored as-is
        assert fused_rnn.resolve_impl(96, "interpret") == "interpret"

    def test_env_kill_switch(self, monkeypatch):
        # the knob is snapshotted at import (utils/envknobs, graftlint
        # trace-env-read) — mutating the env requires an explicit
        # refresh, and the snapshot must be restored afterwards
        from bigdl_tpu.utils import envknobs

        ambient = envknobs.FUSED_RNN_ENABLED  # may be off in the shell
        monkeypatch.setenv("BIGDL_FUSED_RNN", "0")
        envknobs.refresh()
        try:
            assert not envknobs.FUSED_RNN_ENABLED
            assert fused_rnn.resolve_impl(128, None) == "xla"
        finally:
            monkeypatch.undo()
            envknobs.refresh()
        assert envknobs.FUSED_RNN_ENABLED == ambient

    def test_unknown_impl_raises(self):
        # a typo must not silently measure the fallback path
        with pytest.raises(ValueError, match="expected"):
            fused_rnn.resolve_impl(128, "palas")

    def test_fused_scan_protocol_returns_none_on_fallback(self):
        cell = nn.LSTM(3, 4)
        p = cell.init_params(KEY)
        zx = jnp.zeros((2, 3, 16))
        assert cell.fused_scan(p, zx) is None  # CPU → scan path
