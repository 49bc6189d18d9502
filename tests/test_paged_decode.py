"""One-launch Pallas paged-decode kernel (ISSUE 17): interpret-mode
parity against the `ops/kv_cache.paged_attention` oracle — fp32
BITWISE (the load-bearing contract: the kernel must be a drop-in under
every bitwise pin built on the full-extent reduction discipline), bf16
to tolerance — across block-table shapes (ragged last blocks, shuffled
chains, reserved scratch block 0, single-cell and engine-like
launches), the tile-divisibility fail-fast, the env-knob snapshot
round-trip, and the engine-level wiring (attn_impl="interpret" engine
bitwise == the xla engine, sharing its prefill executable)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.ops.kv_cache import (init_block_pool, paged_attention,
                                    paged_attention_form,
                                    paged_attention_heads)
from bigdl_tpu.ops.paged_decode import paged_decode_attention, resolve_tiles
from bigdl_tpu.utils import envknobs


def _case(b, h, nb, bs, d, dtype=jnp.float32, seed=0, pos=None,
          poison=False):
    """A pool + shuffled disjoint block chains + ragged row clocks.
    Block 0 is reserved scratch and never appears in the table (the
    engine contract); poison=True fills it with NaN to prove the
    kernel never reads it and masked keys launder correctly."""
    rng = np.random.RandomState(seed)
    pool_n = b * nb + 1
    shape = jax.eval_shape(
        lambda: init_block_pool(pool_n, h, bs, d))[0].shape
    k_pool = rng.randn(*shape).astype(np.float32)
    v_pool = rng.randn(*shape).astype(np.float32)
    if poison:
        k_pool[0] = np.nan
        v_pool[0] = np.nan
    ids = rng.permutation(np.arange(1, pool_n))[:b * nb]
    table = jnp.asarray(ids.reshape(b, nb), jnp.int32)
    if pos is None:
        pos = rng.randint(0, nb * bs, size=b)
    pos = jnp.asarray(pos, jnp.int32)
    q = jnp.asarray(rng.randn(b, h, 1, d), dtype)
    return (q, jnp.asarray(k_pool, dtype), jnp.asarray(v_pool, dtype),
            table, pos)


CONFIGS = [
    # (b, h, nb, bs, d, block_tile, head_tile)
    (1, 1, 1, 4, 8, 1, 1),       # single cell
    (2, 2, 4, 4, 8, 1, 1),
    (3, 4, 4, 4, 16, 1, 1),      # odd batch
    (1, 4, 4, 4, 8, 1, 2),
    (2, 2, 4, 4, 8, 2, 1),       # multi-block tiles
    (2, 2, 4, 4, 8, 4, 2),       # full-table tile
    (4, 8, 8, 16, 64, 8, 4),     # engine-like 43M shape
    (2, 1, 4, 4, 8, 1, 1),       # H=1, B>1 (dup-batch edge)
]


class TestInterpretParity:
    @pytest.mark.parametrize("b,h,nb,bs,d,bt,ht", CONFIGS)
    def test_fp32_bitwise(self, b, h, nb, bs, d, bt, ht):
        # form against form (ops/kv_cache.py, "Two operand layouts"):
        # the kernel splits heads in VMEM, so its bits are the
        # head-split form's; where the XLA arm attends the rows as
        # stored (the 43M shape: 8 x 64 lanes) they agree to rounding
        args = _case(b, h, nb, bs, d)
        ref = paged_attention_heads(*args)
        out = paged_decode_attention(*args, impl="interpret",
                                     block_tile=bt, head_tile=ht)
        assert out.dtype == ref.dtype
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
        xla = np.asarray(paged_attention(*args))
        if paged_attention_form(h, d) == "heads":
            np.testing.assert_array_equal(np.asarray(out), xla)
        else:
            np.testing.assert_allclose(np.asarray(out), xla,
                                       rtol=1e-5, atol=1e-5)

    def test_fp32_bitwise_ragged_clocks(self):
        # clocks mid-block, at a block boundary, and at 0: the
        # valid-extent masking must agree with the oracle exactly
        args = _case(4, 2, 4, 4, 8, pos=[0, 3, 4, 15])
        ref = paged_attention(*args)
        out = paged_decode_attention(*args, impl="interpret")
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_poisoned_scratch_block_never_read(self):
        # block 0 (reserved scratch) and every masked key row are NaN
        # in spirit: output must stay finite and bitwise the oracle's
        args = _case(2, 2, 4, 4, 8, poison=True, pos=[5, 9])
        ref = paged_attention(*args)
        out = paged_decode_attention(*args, impl="interpret")
        assert np.isfinite(np.asarray(out)).all()
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_bf16_tolerance(self):
        # bf16 pools: both paths cast to fp32 at the same point (VMEM
        # load here, post-gather there), so values match — pinned to
        # tolerance, not bits (module docstring)
        args = _case(2, 4, 4, 4, 16, dtype=jnp.bfloat16)
        ref = paged_attention(*args)
        out = paged_decode_attention(*args, impl="interpret")
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=2e-2, rtol=2e-2)

    def test_custom_sm_scale(self):
        args = _case(2, 2, 4, 4, 8)
        ref = paged_attention(*args, 0.25)
        out = paged_decode_attention(*args, 0.25, impl="interpret")
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_under_jit(self):
        args = _case(2, 2, 4, 4, 8)
        ref = paged_attention(*args)
        out = jax.jit(lambda *a: paged_decode_attention(
            *a, impl="interpret"))(*args)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


class TestDispatchAndTiles:
    def test_xla_impl_is_the_oracle(self):
        args = _case(2, 2, 4, 4, 8)
        np.testing.assert_array_equal(
            np.asarray(paged_decode_attention(*args, impl="xla")),
            np.asarray(paged_attention(*args)))

    def test_rejects_multi_row_q(self):
        q, kp, vp, tbl, pos = _case(2, 2, 4, 4, 8)
        q2 = jnp.concatenate([q, q], axis=2)
        with pytest.raises(ValueError, match="one row"):
            paged_decode_attention(q2, kp, vp, tbl, pos,
                                   impl="interpret")

    def test_rejects_unknown_impl(self):
        args = _case(1, 1, 1, 4, 8)
        with pytest.raises(ValueError, match="impl"):
            paged_decode_attention(*args, impl="mosaic")

    def test_tile_divisibility_fail_fast(self):
        with pytest.raises(ValueError, match="block_tile"):
            resolve_tiles(4, 2, block_tile=3)
        with pytest.raises(ValueError, match="head_tile"):
            resolve_tiles(4, 2, head_tile=4)
        with pytest.raises(ValueError, match="block_tile"):
            resolve_tiles(4, 2, block_tile=0)
        assert resolve_tiles(4, 2) == (1, 1)
        assert resolve_tiles(8, 4, block_tile=2, head_tile=4) == (2, 4)

    def test_env_knob_snapshot(self):
        # BIGDL_PAGED_DECODE_TILES is an import snapshot: mutate env +
        # refresh() (the sweep discipline), explicit args still win
        old = os.environ.get("BIGDL_PAGED_DECODE_TILES")
        os.environ["BIGDL_PAGED_DECODE_TILES"] = "2x2"
        try:
            envknobs.refresh()
            assert envknobs.PAGED_DECODE_TILES == (2, 2)
            assert resolve_tiles(4, 2) == (2, 2)
            assert resolve_tiles(4, 2, block_tile=4, head_tile=1) \
                == (4, 1)
            args = _case(2, 2, 4, 4, 8)
            np.testing.assert_array_equal(
                np.asarray(paged_decode_attention(*args,
                                                  impl="interpret")),
                np.asarray(paged_attention(*args)))
        finally:
            if old is None:
                os.environ.pop("BIGDL_PAGED_DECODE_TILES", None)
            else:
                os.environ["BIGDL_PAGED_DECODE_TILES"] = old
            envknobs.refresh()
        assert envknobs.PAGED_DECODE_TILES is None


class TestEngineWiring:
    def test_interpret_engine_bitwise_and_shares_prefill(self):
        from bigdl_tpu.models.transformer import build_lm
        from bigdl_tpu.serving import InferenceEngine, Request
        from bigdl_tpu.serving.engine import _TRACES

        model = build_lm(vocab_size=61, dim=32, num_heads=2,
                         num_layers=2, max_len=32)
        variables = model.init(jax.random.PRNGKey(0))

        def run(attn_impl):
            eng = InferenceEngine(model, variables, slots=2, max_len=32,
                                  prefill_buckets=(8,), block_size=4,
                                  attn_impl=attn_impl)
            res = eng.run([Request(id=i, prompt=[3 + i, 7, 11 + i],
                                   max_new_tokens=5) for i in range(3)])
            return eng, {r.id: r.tokens for r in res}

        _, toks_xla = run("xla")
        before = dict(_TRACES)
        eng, toks_int = run("interpret")
        # the kernel path is decode-only: one NEW decode executable
        # for the new static attn_impl, ZERO new prefill compiles
        assert _TRACES["prefill"] == before["prefill"]
        assert _TRACES["decode"] == before["decode"] + 1
        assert toks_int == toks_xla  # fp32 kernel == oracle, bitwise
        assert eng.health()["attn_impl"] == "interpret"
        # second interpret engine over the same model: zero new traces
        before2 = dict(_TRACES)
        _, toks_int2 = run("interpret")
        assert dict(_TRACES) == before2
        assert toks_int2 == toks_xla
