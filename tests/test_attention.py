"""Flash attention kernel + MultiHeadAttention tests.

The Pallas kernel runs in interpreter mode on CPU (interpret=True) and is
checked against the jnp oracle `attention_reference` — the same
oracle-based strategy the reference uses with Torch7 (SURVEY.md §4).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.ops.flash_attention import (
    attention_reference,
    flash_attention,
    flash_attention_with_lse,
)


def _rand_qkv(rng, bh=2, sq=64, sk=64, d=16, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(rng, 3)
    q = jax.random.normal(kq, (bh, sq, d), dtype)
    k = jax.random.normal(kk, (bh, sk, d), dtype)
    v = jax.random.normal(kv, (bh, sk, d), dtype)
    return q, k, v


class TestFlashKernel:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_oracle(self, causal):
        q, k, v = _rand_qkv(jax.random.PRNGKey(0))
        ref = attention_reference(q, k, v, causal=causal)
        out = flash_attention(q, k, v, causal=causal, block_q=32,
                              block_k=32, impl="interpret")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_unaligned_seq_and_dim(self):
        # S and D not multiples of the block/lane sizes → padding path
        q, k, v = _rand_qkv(jax.random.PRNGKey(1), sq=50, sk=70, d=24)
        ref = attention_reference(q, k, v)
        out = flash_attention(q, k, v, block_q=32, block_k=32,
                              impl="interpret")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_lse_matches_oracle(self):
        q, k, v = _rand_qkv(jax.random.PRNGKey(2), sq=48, sk=48)
        _, lse_ref = attention_reference(q, k, v, return_lse=True)
        _, lse = flash_attention_with_lse(q, k, v, block_q=16, block_k=16,
                                          impl="interpret")
        np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_oracle(self, causal):
        q, k, v = _rand_qkv(jax.random.PRNGKey(3), sq=32, sk=32, d=8)

        def loss_flash(q, k, v):
            out = flash_attention(q, k, v, causal=causal, block_q=16,
                                  block_k=16, impl="reference")
            return jnp.sum(out * jnp.cos(out))

        def loss_ref(q, k, v):
            out = attention_reference(q, k, v, causal=causal)
            return jnp.sum(out * jnp.cos(out))

        g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)

    def test_grads_through_interpret_kernel(self):
        # custom VJP over the Pallas forward (interpret) — the full path
        q, k, v = _rand_qkv(jax.random.PRNGKey(4), sq=32, sk=32, d=8)

        def loss(q, k, v):
            out = flash_attention(q, k, v, causal=True, block_q=16,
                                  block_k=16, impl="interpret")
            return jnp.sum(out ** 2)

        def loss_ref(q, k, v):
            out = attention_reference(q, k, v, causal=True)
            return jnp.sum(out ** 2)

        g1 = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)

    def test_cross_attention_lengths(self):
        q, k, v = _rand_qkv(jax.random.PRNGKey(5), sq=16, sk=80)
        ref = attention_reference(q, k, v)
        out = flash_attention(q, k, v, block_q=16, block_k=32,
                              impl="interpret")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("sq,sk", [(8, 16), (16, 8), (24, 40)])
    def test_causal_cross_attention_bottom_right_aligned(self, sq, sk):
        # causal with seq_q != seq_k: query i sees keys ≤ i + (sk - sq),
        # the KV-cache decode convention; kernel must match the oracle
        q, k, v = _rand_qkv(jax.random.PRNGKey(6), sq=sq, sk=sk)
        ref = attention_reference(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True, block_q=8, block_k=8,
                              impl="interpret")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


def _walk_qkv(sq, sk, dim, seed=7):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(2, n, dim), jnp.float32)
                 for n in (sq, sk, sk))


class TestCausalWalk:
    """The kernels walk the tiles of the score square inside a grid
    cell: only those at or under the diagonal, only the straddling ones
    masked, at the head's own width where that is 64. Several tiles a
    row at 128-row tiles, in one cell a batch-head (static trip counts)
    and in 2 x 2 cells (trip counts from program_id, clamped index
    maps)."""

    @pytest.mark.parametrize("cell_rows", [1024, 256],
                             ids=["one_cell", "four_cells"])
    @pytest.mark.parametrize("dim", [64, 128, 80])
    @pytest.mark.parametrize("sq,sk", [(512, 512), (256, 512)])
    def test_forward_lse_and_grads(self, monkeypatch, sq, sk, dim,
                                   cell_rows):
        import importlib
        fa = importlib.import_module("bigdl_tpu.ops.flash_attention")
        monkeypatch.setattr(fa, "_MAX_CELL_ROWS", cell_rows)
        monkeypatch.setattr(fa, "_LONG_CELL_ROWS", cell_rows)
        q, k, v = _walk_qkv(sq, sk, dim)
        tiles = dict(block_q=128, block_k=128)
        ref, lse_ref = attention_reference(q, k, v, causal=True,
                                           return_lse=True)
        out, lse = flash_attention_with_lse(q, k, v, causal=True,
                                            impl="interpret", **tiles)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_ref),
                                   atol=2e-5, rtol=2e-5)

        def loss(attention):
            return lambda q, k, v: jnp.sum(jnp.cos(attention(q, k, v)))

        g1 = jax.grad(loss(lambda q, k, v: flash_attention(
            q, k, v, causal=True, impl="interpret", bwd_tiles=(128, 128),
            **tiles)), argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss(lambda q, k, v: attention_reference(
            q, k, v, causal=True)), argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(g1, g2, ("dq", "dk", "dv")):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4, err_msg=name)

    @pytest.mark.parametrize("sq,sk", [(384, 384), (256, 384)])
    def test_a_long_sequence_takes_one_tile_a_cell(self, monkeypatch, sq,
                                                   sk):
        # past _MAX_CELL_ROWS the plan's own tiles are the cell's rows
        # (the chip table at 16 x 8,192: a walk inside cells of 1,024
        # lost to the parent's grid of whole tiles); here 3 x 3 cells
        import importlib
        fa = importlib.import_module("bigdl_tpu.ops.flash_attention")
        monkeypatch.setattr(fa, "_MAX_CELL_ROWS", 128)
        monkeypatch.setattr(fa, "_LONG_CELL_ROWS", 128)
        plan = fa.flash_attention_plan(sq, sk, 64, 2, 4, True)
        assert (plan.block_q, plan.block_k, plan.cell_q, plan.cell_k,
                plan.bwd_block_q, plan.bwd_block_k, plan.bwd_cell_q,
                plan.bwd_cell_k) == (128,) * 8
        q, k, v = _walk_qkv(sq, sk, 64)

        def loss(attention):
            return lambda q, k, v: jnp.sum(jnp.cos(attention(q, k, v)))

        g1 = jax.grad(loss(lambda q, k, v: flash_attention(
            q, k, v, causal=True, impl="interpret")),
            argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss(lambda q, k, v: attention_reference(
            q, k, v, causal=True)), argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(g1, g2, ("dq", "dk", "dv")):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4, err_msg=name)

    @pytest.mark.parametrize("sq,sk,block", [(384, 384, 128),
                                             (200, 330, 128),
                                             (256, 256, 256)])
    def test_non_causal_visits_every_tile(self, sq, sk, block):
        from bigdl_tpu.ops.flash_attention import flash_attention_plan

        plan = flash_attention_plan(sq, sk, 64, 2, 4, False, block, block)
        assert plan.kv_tiles_visited == plan.kv_tiles_total
        # only the tiles that hold the padded columns take a mask
        assert plan.kv_tiles_masked == (
            -(-sq // block) if sk % block else 0)
        q, k, v = _walk_qkv(sq, sk, 64)
        out = flash_attention(q, k, v, block_q=block, block_k=block,
                              impl="interpret")
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(attention_reference(q, k, v)),
            atol=2e-5, rtol=2e-5)


class TestPlan:
    """`flash_attention_plan`: the one place a call's tiles come from,
    and the counter of how far the causal skip engages."""

    def test_the_train_cell(self):
        from bigdl_tpu.ops.flash_attention import flash_attention_plan

        plan = flash_attention_plan(1024, 1024, 64, 128, 2, True)
        assert plan.head_pad == 0
        # the backward goes by the area it visits: 10 of 16 tiles. The
        # forward pays a segment more than the tiles it skips: the chip
        # table chose 512-row q tiles, 3 of 4 (PERF.md, PR 50)
        assert plan.bwd_tiles_visited / plan.bwd_tiles_total <= 0.65
        assert plan.kv_tiles_visited / plan.kv_tiles_total <= 0.75
        assert 0 < plan.kv_tiles_masked < plan.kv_tiles_visited
        assert 0 < plan.bwd_tiles_masked < plan.bwd_tiles_visited
        assert plan.bwd_form == "fused"
        # one cell a batch-head: a static walk
        assert (plan.cell_q, plan.cell_k) == (1024, 1024)
        assert (plan.bwd_cell_q, plan.bwd_cell_k) == (1024, 1024)
        flat = flash_attention_plan(1024, 1024, 64, 128, 2, False)
        assert flat.kv_tiles_visited == flat.kv_tiles_total
        assert flat.bwd_tiles_visited == flat.bwd_tiles_total
        assert flat.kv_tiles_masked == flat.bwd_tiles_masked == 0
        # past 4,096 rows: cells of 1,024, one tile each by default,
        # and an explicit tile is still the size of a tile
        far = flash_attention_plan(8192, 8192, 64, 16, 2, True)
        assert (far.block_q, far.cell_q, far.bwd_block_k,
                far.bwd_cell_k) == (1024,) * 4
        assert far.kv_tiles_visited / far.kv_tiles_total <= 0.6
        fine = flash_attention_plan(8192, 8192, 64, 16, 2, True, 256, 256,
                                    (256, 256))
        assert (fine.block_q, fine.cell_q, fine.bwd_block_k,
                fine.bwd_cell_k) == (256, 1024, 256, 1024)
        # at 256-row forward tiles the walk is the issue's 10 of 16
        fine = flash_attention_plan(1024, 1024, 64, 128, 2, True, 256, 256)
        assert (fine.kv_tiles_visited, fine.kv_tiles_total,
                fine.kv_tiles_masked) == (10, 16, 4)

    @pytest.mark.parametrize("given,env,want", [
        (dict(), None, (1024, 1024)),
        (dict(block_q=512, block_k=256), None, (512, 256)),
        (dict(), (256, 512), (256, 512)),
        (dict(bwd_tiles=(128, 128)), None, (1024, 1024)),
    ], ids=["default", "explicit", "env", "bwd_tiles_do_not_apply"])
    def test_split_form_keeps_the_forward_s_explicit_tiles(
            self, monkeypatch, given, env, want):
        # past the resident cap the two-kernel backward tiles at the
        # forward's explicit tiles (argument, else env snapshot), as it
        # did before the plan; `bwd_tiles` are the FUSED form's
        from bigdl_tpu.ops.flash_attention import flash_attention_plan
        from bigdl_tpu.utils import envknobs

        monkeypatch.setattr(envknobs, "FLASH_FWD_TILES", env)
        plan = flash_attention_plan(32768, 32768, 64, 1, 4, True, **given)
        assert plan.bwd_form == "split"
        assert (plan.bwd_block_q, plan.bwd_block_k) == want
        assert (plan.bwd_cell_q, plan.bwd_cell_k) == want

    @pytest.mark.parametrize("dim,pad", [(64, 0), (128, 0), (256, 0),
                                         (80, 48), (96, 32), (16, 112)])
    def test_head_pad(self, dim, pad):
        from bigdl_tpu.ops.flash_attention import flash_attention_plan

        assert flash_attention_plan(512, 512, dim, 8, 2,
                                    True).head_pad == pad

    @pytest.mark.parametrize("sq,sk,visited,masked", [
        (512, 512, 10, 4),      # the triangle of a 4 x 4 square
        (256, 512, 7, 2),       # bottom-right alignment: rows see 256 more
        (512, 256, 3, 2),       # the first 256 rows see nothing at all
    ])
    def test_counts_the_triangle(self, sq, sk, visited, masked):
        from bigdl_tpu.ops.flash_attention import flash_attention_plan

        plan = flash_attention_plan(sq, sk, 64, 2, 4, True, 128, 128)
        assert (plan.kv_tiles_visited, plan.kv_tiles_masked) == (visited,
                                                                 masked)

    def test_explicit_tiles_and_the_split_form(self):
        from bigdl_tpu.ops.flash_attention import flash_attention_plan

        plan = flash_attention_plan(2048, 2048, 64, 8, 2, True, 512, 256,
                                    (256, 512))
        assert (plan.block_q, plan.block_k) == (512, 256)
        assert (plan.bwd_block_q, plan.bwd_block_k) == (256, 512)
        # a short sequence runs one tile, whatever was asked
        short = flash_attention_plan(100, 100, 64, 8, 2, True, 512, 512)
        assert (short.block_q, short.cell_q) == (128, 128)
        # past the resident cap the backward is two kernels at their
        # own tiles, and `bwd_tiles` does not apply
        long = flash_attention_plan(32768, 32768, 64, 1, 4, True,
                                    bwd_tiles=(256, 256))
        assert long.bwd_form == "split"
        assert long.bwd_block_q == long.bwd_cell_q == 1024
        # the split kernels skip what lies above the diagonal and mask
        # every tile they visit
        assert long.bwd_tiles_masked == long.bwd_tiles_visited == 528
        # a sequence past one cell: cells of 1,024 rows
        assert (long.cell_q, long.cell_k) == (1024, 1024)


class TestMultiHeadAttention:
    def test_forward_shape_and_oracle(self):
        m = nn.MultiHeadAttention(32, 4, name="mha")
        variables = m.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 10, 32))
        y, _ = m.apply(variables, x)
        assert y.shape == (2, 10, 32)

    def test_causal_is_autoregressive(self):
        m = nn.MultiHeadAttention(16, 2, causal=True)
        variables = m.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 16))
        y1, _ = m.apply(variables, x)
        # perturbing future positions must not change earlier outputs
        x2 = x.at[:, 5:].set(jax.random.normal(jax.random.PRNGKey(2),
                                               (1, 3, 16)))
        y2, _ = m.apply(variables, x2)
        np.testing.assert_allclose(np.asarray(y1[:, :5]),
                                   np.asarray(y2[:, :5]), atol=1e-5)

    def test_cross_attention(self):
        m = nn.MultiHeadAttention(16, 2)
        variables = m.init(jax.random.PRNGKey(0))
        xq = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 16))
        xkv = jax.random.normal(jax.random.PRNGKey(2), (2, 9, 16))
        y, _ = m.apply(variables, [xq, xkv])
        assert y.shape == (2, 5, 16)

    def test_grad_flows(self):
        m = nn.MultiHeadAttention(16, 2, causal=True)
        variables = m.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 6, 16))

        def loss(p):
            y, _ = m.apply({"params": p, "state": {}}, x)
            return jnp.mean(y ** 2)

        g = jax.grad(loss)(variables["params"])
        norms = [float(jnp.linalg.norm(v)) for v in
                 jax.tree_util.tree_leaves(g)]
        assert all(np.isfinite(n) for n in norms)
        assert any(n > 0 for n in norms)

    def test_dropout_paths(self):
        m = nn.MultiHeadAttention(16, 2, attn_dropout=0.5, out_dropout=0.5)
        variables = m.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 6, 16))
        y1, _ = m.apply(variables, x, training=True,
                        rng=jax.random.PRNGKey(2))
        y2, _ = m.apply(variables, x, training=True,
                        rng=jax.random.PRNGKey(3))
        assert not np.allclose(np.asarray(y1), np.asarray(y2))
        ye, _ = m.apply(variables, x, training=False)
        ye2, _ = m.apply(variables, x, training=False)
        np.testing.assert_allclose(np.asarray(ye), np.asarray(ye2))


class TestXlaBlockwiseForward:
    """impl='xla' — the blockwise lax.scan flash forward (default on
    TPU since round 2; see _flash_fwd_xla)."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_oracle_with_lse(self, causal):
        rng = np.random.RandomState(3)
        q = jnp.asarray(rng.randn(3, 100, 16), jnp.float32)
        k = jnp.asarray(rng.randn(3, 100, 16), jnp.float32)
        v = jnp.asarray(rng.randn(3, 100, 16), jnp.float32)
        ref, ref_lse = attention_reference(q, k, v, causal=causal,
                                           return_lse=True)
        out, lse = flash_attention_with_lse(q, k, v, causal=causal,
                                            impl="xla", block_k=32)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                                   rtol=1e-5, atol=1e-5)

    def test_grads_match_oracle(self):
        rng = np.random.RandomState(4)
        q = jnp.asarray(rng.randn(2, 64, 8), jnp.float32)
        k = jnp.asarray(rng.randn(2, 96, 8), jnp.float32)
        v = jnp.asarray(rng.randn(2, 96, 8), jnp.float32)

        def loss(fn):
            return lambda q, k, v: jnp.sum(
                fn(q, k, v) * jnp.arange(8, dtype=jnp.float32))

        g_x = jax.grad(loss(lambda q, k, v: flash_attention(
            q, k, v, causal=True, impl="xla", block_k=32)),
            argnums=(0, 1, 2))(q, k, v)
        g_r = jax.grad(loss(lambda q, k, v: attention_reference(
            q, k, v, causal=True)), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_x, g_r):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)

    def test_uneven_kv_padding(self):
        rng = np.random.RandomState(5)
        q = jnp.asarray(rng.randn(2, 33, 8), jnp.float32)
        k = jnp.asarray(rng.randn(2, 77, 8), jnp.float32)
        v = jnp.asarray(rng.randn(2, 77, 8), jnp.float32)
        ref = attention_reference(q, k, v, causal=False)
        out = flash_attention(q, k, v, causal=False, impl="xla",
                              block_k=32)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


class TestFullyMaskedRows:
    """Causal with seq_q > seq_k leaves leading query rows with NO
    visible keys (bottom-right alignment). _NEG_INF is finite, so a bare
    exp(s - m) would emit 1 per masked column and the row would output
    mean(V); all impls must emit zeros (the ring-combine convention)."""

    @pytest.mark.parametrize("impl", ["xla", "interpret", "reference"])
    def test_fully_masked_rows_are_zero(self, impl):
        rng = np.random.RandomState(6)
        q = jnp.asarray(rng.randn(2, 8, 8), jnp.float32)
        k = jnp.asarray(rng.randn(2, 4, 8), jnp.float32)
        v = jnp.asarray(rng.randn(2, 4, 8), jnp.float32)
        out, lse = flash_attention_with_lse(q, k, v, causal=True,
                                            impl=impl, block_q=8,
                                            block_k=4)
        # rows 0..3 see no keys (row i sees keys <= i + 4 - 8)
        np.testing.assert_allclose(np.asarray(out[:, :4]), 0.0, atol=1e-6)
        assert bool(jnp.all(lse[:, :4] < -1e29))
        # visible rows must still match the oracle
        ref = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out[:, 4:]),
                                   np.asarray(ref[:, 4:]),
                                   rtol=1e-5, atol=1e-5)


class TestMosaicBackwardEdgeShapes:
    """Gradient checks through the Mosaic backward kernels (interpret
    mode) on the shapes that can silently break them: cross q/kv
    lengths (bottom-right-aligned causal), block-non-divisible
    sequences (padded-row masking in the dkv kernel), and an explicit
    sm_scale."""

    @pytest.mark.parametrize("sq,sk,causal", [
        (20, 36, True),    # sq < sk, padded rows + cross-length causal
        (40, 24, True),    # sq > sk: fully-masked leading rows
        (33, 33, False),   # non-divisible, non-causal
        (64, 64, True),    # block-divisible control
    ])
    def test_grads_match_oracle(self, sq, sk, causal):
        rng = np.random.RandomState(5)
        q = jnp.asarray(rng.randn(3, sq, 8), jnp.float32)
        k = jnp.asarray(rng.randn(3, sk, 8), jnp.float32)
        v = jnp.asarray(rng.randn(3, sk, 8), jnp.float32)

        def loss_flash(q, k, v):
            return flash_attention(q, k, v, causal=causal, block_q=16,
                                   block_k=16, impl="interpret").sum()

        def loss_ref(q, k, v):
            return attention_reference(q, k, v, causal=causal).sum()

        g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)

    def test_grads_with_explicit_scale(self):
        rng = np.random.RandomState(6)
        q = jnp.asarray(rng.randn(2, 24, 8), jnp.float32)
        k = jnp.asarray(rng.randn(2, 24, 8), jnp.float32)
        v = jnp.asarray(rng.randn(2, 24, 8), jnp.float32)
        for scale in (0.5, 0.0):   # 0.0: uniform attention, dk must be 0
            g1 = jax.grad(lambda q: flash_attention(
                q, k, v, causal=True, sm_scale=scale, block_q=16,
                block_k=16, impl="interpret").sum())(q)
            g2 = jax.grad(lambda q: attention_reference(
                q, k, v, causal=True, sm_scale=scale).sum())(q)
            np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                       rtol=2e-4, atol=2e-5)


class TestFusedBackward:
    """The one-pass backward (persistent dq accumulator) must equal the
    two-kernel form bit-for-bit-ish at any shape both can run."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_fused_equals_split(self, causal):
        import importlib
        fa = importlib.import_module("bigdl_tpu.ops.flash_attention")

        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(2, 64, 16).astype(np.float32))
        k = jnp.asarray(rng.randn(2, 64, 16).astype(np.float32))
        v = jnp.asarray(rng.randn(2, 64, 16).astype(np.float32))
        o, lse = fa._flash_fwd_pallas(q, k, v, causal, 0.25, 32, 32, 64,
                                      64, interpret=True)
        do = jnp.asarray(rng.randn(2, 64, 16).astype(np.float32))
        fused = fa._flash_bwd_pallas_fused(q, k, v, o, lse, do, causal,
                                           0.25, 32, 32, 64, 64,
                                           interpret=True)
        split = fa._flash_bwd_pallas_split(q, k, v, o, lse, do, causal,
                                           0.25, 32, 32, interpret=True)
        for a, b, name in zip(fused, split, ("dq", "dk", "dv")):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5, rtol=1e-5,
                                       err_msg=name)

    def test_long_sequence_falls_back_to_split(self, monkeypatch):
        import importlib
        fa = importlib.import_module("bigdl_tpu.ops.flash_attention")

        calls = []
        monkeypatch.setattr(
            fa, "_flash_bwd_pallas_split",
            lambda *a, **k: calls.append("split") or
            (a[0], a[1], a[2]))
        monkeypatch.setattr(
            fa, "_flash_bwd_pallas_fused",
            lambda *a, **k: calls.append("fused") or
            (a[0], a[1], a[2]))
        small = jnp.zeros((1, 128, 64))
        fa._flash_bwd_pallas(
            small, small, small, small, jnp.zeros((1, 128)), small, True,
            1.0, fa.flash_attention_plan(128, 128, 64, 1, 4, True), True)
        # 13 MiB / (128 lanes * 8 B a float32 row) = 13312 rows: S
        # beyond that splits
        big = jnp.zeros((1, 32768, 64))
        fa._flash_bwd_pallas(
            big, big, big, big, jnp.zeros((1, 32768)), big, True, 1.0,
            fa.flash_attention_plan(32768, 32768, 64, 1, 4, True), True)
        assert calls == ["fused", "split"]
