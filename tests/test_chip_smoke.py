"""chip_smoke.py's control flow, tiny, on CPU (ISSUE 21): every leg
function runs end to end with `impl="interpret"` / `"xla"` passed
explicitly, so a typo costs a CPU second instead of a chip minute; plus
the guards the hardware path relies on — `main()` refuses a non-TPU
backend, the compile cache lands where the rule says, an unknown device
has no peaks, and a backend error is never answered "cpu"."""

import importlib.util
import json
import os

import jax
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

# heads=4 so the same model also serves over a 4-way tensor-parallel mesh
TINY_LM = dict(vocab=64, dim=32, layers=1, heads=4, seq=64)
TINY_SERVE = dict(slots=2, buckets=(16, 64), block_size=4, new_tokens=3)
TINY_BILSTM = dict(batch=8, seq=2, hidden=128)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def cache_config():
    """setup_compile_cache() writes a process-wide jax config value;
    put it back so the rest of the session is untouched."""
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def _legs(capsys):
    return [json.loads(line) for line in
            capsys.readouterr().out.splitlines() if line.startswith("{")]


@pytest.fixture(scope="module")
def clock(smoke):
    return smoke.CompileClock()


@pytest.fixture(scope="module")
def trained(smoke, clock):
    """(model, flash tiles) from ONE tiny train leg, shared by the
    tests below — the legs hand the model on exactly as main() does.
    (attention: the jnp reference, CPU's own path; the flash kernel
    runs interpreted in the kernel leg.)"""
    return smoke.train_leg(clock, **TINY_LM, batch=4, steps=3,
                           attn_impl="reference", expect_attn="reference")


def test_train_serve_kernel_legs_tiny(smoke, clock, trained, capsys):
    model, blocks = trained
    results, requests = smoke.serve_leg(clock, model, **TINY_SERVE)
    assert len(results) == len(smoke.PROMPT_FRACS)
    assert {r.temperature > 0 for r in requests} == {True, False}
    smoke.kernel_leg(clock, model, impl="interpret", flash_blocks=blocks,
                     bilstm=TINY_BILSTM)
    legs = {row["leg"]: row for row in _legs(capsys)}
    assert set(legs) == {"serve", "kernels"}
    assert all(row["ok"] and row["asserted"] for row in legs.values())
    assert legs["serve"]["compile_s"] > 0
    assert legs["serve"]["attn_form"] == "heads"      # the toy widths
    assert {"flash", "fused_bilstm", "int8_linear_err"} \
        <= set(legs["kernels"])


def test_train_leg_fails_on_the_wrong_attention(smoke, clock):
    """On the chip the run must fail if attention silently resolved to
    anything but the Mosaic kernel; here: CPU auto-resolves to
    'reference', which is not the 'pallas' the leg expects."""
    with pytest.raises(smoke.SmokeFailure, match="'reference'"):
        smoke.train_leg(clock, **TINY_LM, batch=2, steps=1)      # auto


def test_mesh_leg_tiny(smoke, clock, trained, capsys):
    assert jax.device_count() >= 4
    capsys.readouterr()
    smoke.mesh_leg(clock, trained[0], n=4, lm=TINY_LM, batch=4, steps=2,
                   serve=TINY_SERVE, attn_impl="reference",
                   expect_attn="reference")
    train, serve = _legs(capsys)
    assert train["leg"] == "mesh_train[data=4]"
    assert len(set(train["placement"]["largest_spanning"]
                   ["shard_devices"])) == 4
    assert serve["leg"] == "mesh_serve[model=4]" and serve["tp"] == 4
    assert len(serve["placement"]["pool_devices"]) == 4
    # heads shard over the mesh: each device holds H/4 of a pool row
    assert serve["placement"]["pool_k0_shard"][-1] * 4 \
        == serve["placement"]["pool_k0_global"][-1]
    assert serve["placement"]["pool_k0_shard"][:-1] \
        == serve["placement"]["pool_k0_global"][:-1]


def test_main_refuses_a_cpu_backend(smoke, capsys, cache_config):
    assert smoke.main() != 0
    out, err = capsys.readouterr()
    assert "no TPU" in err and "'cpu'" in err and "JAX_PLATFORMS" in err
    rows = [json.loads(line) for line in out.splitlines()]
    assert [r["leg"] for r in rows] == ["device"]      # it ran no leg
    assert rows[0]["platform"] == "cpu"
    assert not any("ok" in r for r in rows)            # and no result


def test_compile_cache_rule(monkeypatch, cache_config):
    from bigdl_tpu.utils.engine import setup_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert setup_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before  # untouched
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    first = setup_compile_cache()
    assert first == os.path.join(ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    assert setup_compile_cache() == first                  # a fixed path


def test_unknown_device_has_no_peaks():
    from bigdl_tpu.utils.engine import (DEVICE_PEAKS, bf16_utilization,
                                        device_peaks)

    assert device_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert all(p["source"] for p in DEVICE_PEAKS.values())
    with pytest.raises(KeyError, match="TPU v9000"):
        device_peaks("TPU v9000")
    with pytest.raises(KeyError, match="cpu"):
        device_peaks()                  # this process's device: no entry
    assert bf16_utilization(1e12) is None    # and no CPU "utilization"


def test_backend_error_is_never_answered_cpu(monkeypatch):
    from bigdl_tpu.ops.flash_attention import _default_impl as flash
    from bigdl_tpu.ops.fused_rnn import _default_platform as rnn

    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", broken)
    for helper in (flash, rnn):
        with pytest.raises(RuntimeError, match="Unable to initialize"):
            helper()
