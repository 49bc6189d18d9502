"""The streaming grouped matmul (`ops/grouped_matmul.py`) compiled ahead
of time for a described `v5e:2x2` at the shapes it serves: a decode step's
512 assignments over JoyAI's 256 experts and Trinity's 128, the gate / up
matrices and the down matrix, and both families' largest prefill bucket
(no chip attached; the TPU compiler is installed). Each compiles to ONE Mosaic call named `moe_grouped_matmul`
whose temporaries in HBM stay under its two weight buffers plus its
outputs: no copy of the weights and no rows padded to a tile an expert is
staged. A compile that passes is not a chip run.

The process's platform is the CPU here, where `grouped_matmul_form` says
"ragged_dot": the kernel is asked for by `impl="stream"`. libtpu is
touched only inside the `topo` fixture; the tests skip where no topology
can be described. `tests/bench/test_aot*.py` keep compiling the programs
the CPU's platform chooses.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bigdl_tpu.ops.grouped_matmul import grouped_matmul

# (rows, experts, K, N) of the programs that take the kernel
SHAPES = {"joyai_decode_gate_up": (512, 256, 2048, 768),
          "joyai_decode_down": (512, 256, 768, 2048),
          "trinity_decode_gate_up": (512, 128, 2048, 1024),
          "trinity_decode_down": (512, 128, 1024, 2048),
          "joyai_prefill_2048": (16384, 256, 2048, 768),
          "trinity_prefill_6144_down": (49152, 128, 1024, 2048)}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 - any failure means: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """An AOT compile for an absent chip can be written to the persistent
    cache but not read back; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_kernel_compiles_for_a_v5e(shape, one_chip):
    from bigdl_tpu.ops import grouped_matmul as gm

    m, e, k, n = SHAPES[shape]
    with pytest.MonkeyPatch.context() as on_a_tpu:
        on_a_tpu.setattr(gm.jax, "devices", lambda: [type(
            "Device", (), {"platform": "tpu"})])
        assert gm.grouped_matmul_form(m, e, k, n, jnp.bfloat16) == "stream"

    def spec(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda a, w, sizes: grouped_matmul(a, w, sizes, impl="stream")
    ).lower(spec((m, k), jnp.bfloat16), spec((e, k, n), jnp.bfloat16),
            spec((e,), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "moe_grouped_matmul" in text
    assert "ragged-dot" not in text and "ragged_dot" not in text
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == 4 * m * n
    assert mem.temp_size_in_bytes < 2 * k * n * 2 + 4 * m * n


# The flash-attention kernels ride in this file because ONE worker holds
# libtpu: a second file of such compiles can land on another worker, where
# its fixture skips every test.
FLASH = {
    # gpt2m-train's own: 128 batch-heads, one cell a batch-head
    "train_cell_d64": (128, 1024, 1024, 64, jnp.bfloat16),
    # the longest sequence that is still one cell, at the widest head
    "s4096_d128": (4, 4096, 4096, 128, jnp.bfloat16),
    # several cells a batch-head: the walk under program_id's predicates
    "s8192_d64": (2, 8192, 8192, 64, jnp.bfloat16),
    # a head width that still pads, bottom-right alignment, float32
    "d80_cross_f32": (4, 512, 1024, 80, jnp.float32),
}


@pytest.mark.parametrize("shape", list(FLASH))
def test_the_flash_kernels_compile_for_a_v5e(shape, one_chip):
    """`jax.grad` of a causal call at the plan's own tiles: one forward
    and one fused backward Mosaic kernel, nothing refused by the chip's
    compiler (an unaligned slice, VMEM past the kernel's limit). A
    compile that passes is not a chip run."""
    from bigdl_tpu.ops.flash_attention import flash_attention

    bh, sq, sk, dim, dtype = FLASH[shape]
    q = jax.ShapeDtypeStruct((bh, sq, dim), dtype, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((bh, sk, dim), dtype, sharding=one_chip)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True,
                               impl="pallas").astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "flash_fwd" in text and "flash_bwd_fused" in text
