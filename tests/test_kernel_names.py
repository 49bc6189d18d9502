"""Every Pallas kernel reaches the TPU under its family's name.

A Mosaic kernel is one `tpu_custom_call` in the lowered program; a device
trace labels it by its HLO instruction, which used to be named after
whatever transform enclosed the call (`closed_call.32` backward,
`closed_call.15` forward in PERF.md's gpt2m-train breakdown).
`ops/pallas_names.named_pallas_call` gives each call a `name=` (the
StableHLO `kernel_name` attribute) and a `jax.named_scope` of the same
name. Checked here WITHOUT the TPU's compiler: each family's entry is
lowered for the TPU platform at a tiny shape (lowering only runs Pallas's
Mosaic lowering, which is Python) and every `tpu_custom_call` of the text
must carry a name of its family. Metadata only — the numerics tests of
each family (test_attention, test_fused_rnn) pin that
nothing else moved."""

import importlib
import re

import jax
import jax.numpy as jnp
import pytest

from bigdl_tpu.ops import fused_rnn as fr

# `bigdl_tpu.ops.flash_attention` the attribute is the function
fa = importlib.import_module("bigdl_tpu.ops.flash_attention")
_NAME = re.compile(r'kernel_name = "([^"]+)"')


def _kernel_names(fn, *args):
    """kernel_name of every tpu_custom_call in fn's TPU lowering."""
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    calls = [l for l in text.splitlines() if "@tpu_custom_call" in l]
    assert calls, "the lowering holds no Mosaic kernel"
    names = []
    for line in calls:
        m = _NAME.search(line)
        assert m, f"a tpu_custom_call without a kernel_name: {line[:200]}"
        names.append(m.group(1))
    return names


def _qkv(seq=256, dim=64, dtype=jnp.bfloat16):
    spec = jax.ShapeDtypeStruct((1, 2, seq, dim), dtype)
    return spec, spec, spec


def _attn_loss(q, k, v):
    return fa.flash_attention(q, k, v, causal=True,
                              impl="pallas").astype(jnp.float32).sum()


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def test_flash_forward():
    assert _kernel_names(
        lambda q, k, v: fa.flash_attention(q, k, v, causal=True,
                                           impl="pallas"),
        *_qkv()) == ["flash_fwd"]


def test_flash_backward_fused():
    names = _kernel_names(jax.grad(_attn_loss, argnums=(0, 1, 2)), *_qkv())
    assert sorted(names) == ["flash_bwd_fused", "flash_fwd"]


def test_flash_backward_split(monkeypatch):
    # past the fused form's resident cap the backward is two kernels
    monkeypatch.setattr(fa, "_FUSED_BWD_MAX_RESIDENT_BYTES", 0)
    names = _kernel_names(jax.grad(_attn_loss, argnums=(0, 1, 2)), *_qkv())
    assert sorted(names) == ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]


_TENSOR = re.compile(r"tensor<((?:\d+x)+)(?:bf16|f32)>")


def test_flash_kernels_cross_hbm_at_the_heads_own_width():
    """gpt2m-train's attention (D = 64, bfloat16, causal): one forward
    and one fused backward kernel, and no operand or result of either is
    128 lanes wide — neither a padded head nor a lane-broadcast lse can
    come back unseen."""
    text = jax.jit(jax.grad(_attn_loss, argnums=(0, 1, 2))).trace(
        *_qkv(seq=1024)).lower(lowering_platforms=("tpu",)).as_text()
    calls = [l for l in text.splitlines() if "@tpu_custom_call" in l]
    assert sorted(_NAME.search(l).group(1) for l in calls) == [
        "flash_bwd_fused", "flash_fwd"]
    for line in calls:
        shapes = [tuple(int(n) for n in m.group(1).split("x") if n)
                  for m in _TENSOR.finditer(line)]
        assert len(shapes) >= 5, line[:200]
        assert all(shape[-1] in (64, 1024) for shape in shapes), shapes


@pytest.mark.parametrize("scan,args,family", [
    (fr.lstm_scan, (_f32(16, 4, 512), _f32(128, 512)), "fused_lstm"),
    (fr.bilstm_scan, (_f32(16, 4, 512), _f32(16, 4, 512), _f32(128, 512),
                      _f32(128, 512)), "fused_lstm_bi"),
    (fr.gru_scan, (_f32(16, 4, 256), _f32(16, 4, 128), _f32(128, 256),
                   _f32(128, 128)), "fused_gru"),
], ids=["lstm", "bilstm", "gru"])
class TestFusedRnn:
    def test_inference(self, scan, args, family):
        assert _kernel_names(
            lambda *a: scan(*a, impl="pallas"), *args) == [f"{family}_fwd"]

    def test_training(self, scan, args, family):
        loss = (lambda *a: sum(x.astype(jnp.float32).sum() for x in
                               jax.tree_util.tree_leaves(
                                   scan(*a, impl="pallas"))))
        names = _kernel_names(
            jax.grad(loss, argnums=tuple(range(len(args)))), *args)
        assert sorted(names) == [f"{family}_bwd", f"{family}_fwd"]


def test_grouped_matmul():
    from bigdl_tpu.ops.grouped_matmul import grouped_matmul

    bf16 = jnp.bfloat16
    assert _kernel_names(
        lambda a, w, sizes: grouped_matmul(a, w, sizes, impl="stream"),
        jax.ShapeDtypeStruct((256, 128), bf16),
        jax.ShapeDtypeStruct((8, 128, 256), bf16),
        jax.ShapeDtypeStruct((8,), jnp.int32)) == ["moe_grouped_matmul"]
