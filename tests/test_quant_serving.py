"""Quantized serving layout (ISSUE 17): the serving/quant.py int8
repack (structure preservation, dequant error bound, bytes win), the
int8-weight/bf16-KV engine end to end under the TOLERANCE contract
(lossy by design — the fp32 bitwise pins stay fp32-scoped and are
re-run untouched by test_kv_pool/test_tp_serving/test_speculative),
per-engine constructor gating (the layout is a ctor arg, never env; tp
engines refuse the lossy one — their pins are bitwise; how decode
attends the cache is no option at all), the #buckets+1 compile
contract re-run with quant armed, and
the router refusing cross-layout-family failover."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import obs
from bigdl_tpu.models.transformer import build_lm
from bigdl_tpu.serving import EngineRouter, InferenceEngine, Request
from bigdl_tpu.serving.quant import (QuantWeight, params_bytes,
                                     quantize_serving_params)
from bigdl_tpu.utils import faults

_LM = None


def _lm():
    global _LM
    if _LM is None:
        _LM = build_lm(vocab_size=61, dim=32, num_heads=2, num_layers=2,
                       max_len=64)
        _LM.build(jax.random.PRNGKey(0))
    return _LM


def _engine(**kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 32)
    kw.setdefault("prefill_buckets", (8,))
    kw.setdefault("block_size", 4)
    return InferenceEngine(_lm(), **kw)


def _quant_kw():
    return dict(weight_dtype="int8", cache_dtype=jnp.bfloat16)


@pytest.fixture(autouse=True)
def _clean_plan():
    faults.set_plan(None)
    yield
    faults.set_plan(None)


class TestRepack:
    def test_structure_and_leaf_types(self):
        model = _lm()
        sp = model.serving_params(model.variables)
        qp = quantize_serving_params(sp)
        assert isinstance(qp["embed"], QuantWeight)
        assert qp["embed"].q.dtype == jnp.int8
        # per-ROW embed scales: one per vocab row (gather-then-scale)
        assert qp["embed"].scale.shape == (61, 1)
        for bp, qbp in zip(sp["blocks"], qp["blocks"]):
            for k in ("wq", "wk", "wv", "wo", "w1", "w2"):
                assert isinstance(qbp[k], QuantWeight)
                assert qbp[k].shape == bp[k].shape
            for k in bp:
                if not isinstance(qbp[k], QuantWeight):
                    assert qbp[k] is bp[k]  # biases/LN pass through

    def test_dequant_error_bound(self):
        model = _lm()
        sp = model.serving_params(model.variables)
        qp = quantize_serving_params(sp)
        w = sp["blocks"][0]["wq"]
        dq = qp["blocks"][0]["wq"].deq()
        # symmetric per-channel: |err| <= scale/2 = max|w|/254
        bound = float(jnp.abs(w).max()) / 254 + 1e-7
        assert float(jnp.abs(dq - w).max()) <= bound

    def test_requires_serving_layout(self):
        model = _lm()
        with pytest.raises(ValueError, match="serving"):
            quantize_serving_params(model.variables["params"])

    def test_bytes_win(self):
        model = _lm()
        sp = model.serving_params(model.variables)
        ratio = params_bytes(sp) / params_bytes(
            quantize_serving_params(sp))
        assert ratio >= 2.5  # ~4x on gemms, diluted by fp32 scales


class TestQuantEngine:
    def _run(self, **kw):
        eng = _engine(**kw)
        res = eng.run([Request(id=i, prompt=[3 + i, 7, 11 + i],
                               max_new_tokens=6) for i in range(4)])
        return eng, {r.id: r.tokens for r in res}

    def test_tolerance_contract_vs_fp32(self):
        _, ref = self._run()
        eng, toks = self._run(**_quant_kw())
        assert set(toks) == set(ref)
        assert all(len(toks[i]) == len(ref[i]) for i in ref)
        # the documented contract (lmdecode_quant row): first-token
        # agreement (pure function of the prompt) on most requests,
        # agreed-prefix fraction well above noise
        first = sum(toks[i][0] == ref[i][0] for i in ref)
        assert first >= len(ref) - 1
        agreed = horizon = 0
        for i in ref:
            for a, b in zip(ref[i], toks[i]):
                if a != b:
                    break
                agreed += 1
            horizon += len(ref[i])
        assert agreed / horizon >= 0.25

    def test_health_and_layout_family(self):
        eng, _ = self._run(**_quant_kw())
        h = eng.health()
        assert h["weight_dtype"] == "int8"
        assert h["cache_dtype"] == "bfloat16"
        assert h["attn_form"] == "heads"      # chosen from the shape
        assert eng.layout_family == "int8/bfloat16"
        assert _engine().layout_family == "fp32/float32"

    def test_pool_bytes_gauge_reflects_cache_dtype(self):
        def gauge(eng):
            key = (f"serving_kv_pool_bytes{{engine={eng.obs_name},"
                   f"tp=1}}")
            return obs.provenance("serving_kv_pool_bytes")[
                "metrics"][key]

        # 7-token prompts (inside the 8 bucket) so the radix tree
        # RETAINS a block after the run ((7-1)//4 = 1 reusable block
        # per chain) — the gauge reports retained + live pool bytes
        prompt = [3, 7, 11, 13, 2, 5, 8]
        e32 = _engine()
        eq = _engine(**_quant_kw())
        for eng in (e32, eq):
            eng.run([Request(id=i, prompt=list(prompt),
                             max_new_tokens=4) for i in range(2)])
        # same retained block count, half the bytes per block (bf16)
        b32, bq = gauge(e32), gauge(eq)
        assert b32 > 0 and bq > 0
        assert bq * 2 == b32

    def test_compile_contract_with_quant_armed(self):
        from bigdl_tpu.serving.engine import _TRACES

        model = build_lm(vocab_size=53, dim=32, num_heads=2,
                         num_layers=2, max_len=32)
        model.build(jax.random.PRNGKey(1))

        def engine():
            return InferenceEngine(model, slots=2, max_len=32,
                                   prefill_buckets=(4, 8),
                                   block_size=4, **_quant_kw())

        # prompts hitting BOTH buckets (len 3 -> 4, len 6 -> 8)
        reqs = lambda: [Request(id=i, prompt=[2 + i, 5, 9] if i == 0
                                else [2 + i, 5, 9, 4, 6, 8],
                                max_new_tokens=4) for i in range(3)]
        before = dict(_TRACES)
        engine().run(reqs())
        # the quant layout is its own executable family: #buckets + 1
        assert _TRACES["prefill"] == before["prefill"] + 2
        assert _TRACES["decode"] == before["decode"] + 1
        # pool growth over the same model compiles NOTHING more
        mid = dict(_TRACES)
        engine().run(reqs())
        assert dict(_TRACES) == mid


class TestGating:
    def test_ctor_rejects_unknown_layout(self):
        with pytest.raises(ValueError, match="weight_dtype"):
            _engine(weight_dtype="fp16")
        # how decode attends the cache is chosen from the shape
        # (ops/kv_cache.paged_attention_form): the constructor has no
        # attn_impl to set
        with pytest.raises(TypeError, match="attn_impl"):
            _engine(attn_impl="xla")

    def test_tp_mesh_refuses_the_lossy_layout(self):
        # a 1-device mesh exercises the guard without multi-device
        # XLA flags: the refusal is about the LAYOUT, not the degree
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("model",))
        with pytest.raises(ValueError, match="tp"):
            _engine(tp_mesh=mesh, weight_dtype="int8")

    def test_router_refuses_cross_family_failover(self):
        """An fp32 engine dies mid-decode with only an int8 survivor:
        the router must NOT reroute (the survivor's tokens are not the
        ones the dead engine would have produced) — requests fail, the
        loss is counted, and nothing lands on the quant engine."""
        e0 = _engine(step_timeout_s=0.05)
        eq = _engine(**_quant_kw())
        router = EngineRouter([e0, eq])
        faults.set_plan(faults.FaultPlan("serve_slow@1"))
        try:
            out = router.run([Request(prompt=[1, 2, 3],
                                      max_new_tokens=4, seed=1)])
        finally:
            faults.set_plan(None)
        assert e0.degraded is not None
        assert [r.status for r in out] == ["failed"]
        assert router.stats["failover_lost"] == 1
        assert router.stats["failover"] == 0
        assert eq.stats["requests_done"] == 0

    def test_router_failover_within_family_still_works(self):
        e0 = _engine(step_timeout_s=0.05)
        e1 = _engine()
        router = EngineRouter([e0, e1])
        faults.set_plan(faults.FaultPlan("serve_slow@1"))
        try:
            out = router.run([Request(prompt=[1, 2, 3],
                                      max_new_tokens=4, seed=1)])
        finally:
            faults.set_plan(None)
        assert e0.degraded is not None
        assert [r.status for r in out] == ["done"]
        assert router.stats["failover"] == 1
