"""Calibrated fleet simulator (ISSUE 20): BENCH-artifact calibration
(committed rows only, provenance attached), the modeled-cost algebra,
InferenceEngine surface parity + determinism, the degrade() chaos
hook, and THE honesty gate — the sim-vs-real divergence test that
keeps the cost model within a bench_compare-style tolerance of a real
tiny fleet on the identical trace."""

import importlib.util
import json
import os
import sys

import pytest

from bigdl_tpu import obs
from bigdl_tpu.serving.engine import Request
from bigdl_tpu.serving.sim import CostModel, SimulatedEngine


@pytest.fixture(autouse=True)
def _fresh_obs():
    prev = obs.set_enabled(True)
    obs.reset_all()
    yield
    obs.reset_all()
    obs.set_enabled(prev)


def _loadgen():
    mod = sys.modules.get("bigdl_loadgen")  # one shared module object
    if mod is not None:
        return mod
    path = os.path.join(os.path.dirname(__file__), "..", "scripts",
                        "loadgen.py")
    spec = importlib.util.spec_from_file_location("bigdl_loadgen", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["bigdl_loadgen"] = mod
    spec.loader.exec_module(mod)
    return mod


def _bench_artifact(path, tail_rows):
    path.write_text(json.dumps(
        {"tail": "\n".join(json.dumps(r) if isinstance(r, dict)
                           else str(r) for r in tail_rows)}))
    return str(path)


# ----------------------------------------------------------- cost model

def test_calibration_reads_committed_rows_only(tmp_path):
    """Row admission is the bench_compare rule: a dict with a string
    "metric" and numeric "value" on one tail line — garbage lines,
    wrong-shaped rows, and unparseable artifacts are ignored, never
    fatal. The anchor is the MEDIAN lm-throughput row; the recorded
    cross-round spread becomes the divergence tolerance's floor."""
    m = CostModel.CALIBRATION_METRIC + "[tpu]"
    p1 = _bench_artifact(tmp_path / "BENCH_r01.json", [
        {"metric": m, "value": 100.0},
        {"metric": "unrelated_row", "value": 1.0},
        "not json at all {",
        {"metric": 123, "value": 4.0},          # non-string metric
        {"metric": "no_value_row"},
    ])
    p2 = _bench_artifact(tmp_path / "BENCH_r02.json", [
        {"metric": m, "value": 120.0},
        {"metric": CostModel.INT8_METRIC + "[tpu]", "value": 900.0,
         "int8_vs_bf16_speedup": 2.0},
    ])
    p3 = str(tmp_path / "BENCH_r03.json")
    with open(p3, "w") as f:
        f.write("{torn json")                    # unparseable artifact
    cm = CostModel.from_bench_artifacts([p1, p2, p3])
    med = 110.0                                  # median of 100, 120
    fwd = med * CostModel.TRAIN_FWD_FACTOR
    assert cm.base_prefill_ms == pytest.approx(1e3 / fwd)
    assert cm.base_decode_ms == pytest.approx(
        1e3 / (fwd * CostModel.DECODE_EFFICIENCY))
    assert cm.int8_speedup == 2.0
    assert cm.spread_frac == pytest.approx((120 - 100) / 2 / med)
    prov = cm.provenance()
    assert len(prov["sources"]) == 3             # 2 lm rows + int8
    assert prov["factors"]["train_fwd_factor"] == 3.0
    with pytest.raises(ValueError, match="no committed calibration"):
        CostModel.from_bench_artifacts([p3])


def test_calibration_from_repo_artifacts():
    """The default glob finds the repo's committed BENCH_r0*.json —
    the simulator must never invent latencies from thin air."""
    cm = CostModel.from_bench_artifacts()
    assert cm.base_decode_ms > 0 and cm.base_prefill_ms > 0
    assert all(s["artifact"].startswith("BENCH_r0")
               for s in cm.sources)
    assert len(cm.sources) >= 1


def test_cost_algebra():
    cm = CostModel(base_decode_ms=1.0, base_prefill_ms=0.1,
                   int8_speedup=2.0, sources=[], spread_frac=0.1)
    # context growth: cost doubles at the reference bucket
    assert cm.decode_ms(bucket=int(cm.CONTEXT_REF)) \
        == pytest.approx(2 * cm.decode_ms(bucket=0))
    # tp divides compute; int8 divides by the committed speedup
    assert cm.decode_ms(bucket=128, tp=4) \
        == pytest.approx(cm.decode_ms(bucket=128) / 4)
    assert cm.decode_ms(bucket=128, layout_family="int8/bfloat16") \
        == pytest.approx(cm.decode_ms(bucket=128) / 2.0)
    # speculative accept a → (1+a) tokens per target-priced round
    assert cm.decode_ms(bucket=128, spec_accept=0.5) \
        == pytest.approx(cm.decode_ms(bucket=128) / 1.5)
    assert cm.decode_ms(bucket=128, spec_accept=9.0) \
        == pytest.approx(cm.decode_ms(bucket=128) / 2.0)  # clamped
    # prefill is linear in prompt length
    assert cm.prefill_ms(32) == pytest.approx(2 * cm.prefill_ms(16))
    with pytest.raises(ValueError, match="positive"):
        CostModel(base_decode_ms=0.0, base_prefill_ms=0.1,
                  int8_speedup=1.0, sources=[], spread_frac=0.0)


# ------------------------------------------------------------ the engine

def _sim_engine(clk, **kw):
    cm = kw.pop("cost_model", None) or CostModel(
        base_decode_ms=1.0, base_prefill_ms=0.1, int8_speedup=1.0,
        sources=[], spread_frac=0.1)
    kw.setdefault("slots", 2)
    kw.setdefault("pacing", "per_step")
    return SimulatedEngine(cm, clock=lambda: clk["t"], **kw)


def _drive(eng, reqs, clk, step_dt=0.25, max_rounds=500):
    got = {}
    ids = [eng.submit(r) for r in reqs]
    rounds = 0
    while len(got) < len(ids):
        rounds += 1
        assert rounds < max_rounds, "sim engine stalled"
        clk["t"] = round(clk["t"] + step_dt, 9)
        for res in eng.step():
            got[res.id] = res
    return [got[i] for i in ids]


def test_engine_surface_and_validation():
    clk = {"t": 0.0}
    with pytest.raises(ValueError, match="clock"):
        SimulatedEngine(CostModel(base_decode_ms=1.0,
                                  base_prefill_ms=0.1,
                                  int8_speedup=1.0, sources=[],
                                  spread_frac=0.0), clock=None)
    with pytest.raises(ValueError, match="pacing"):
        _sim_engine(clk, pacing="warp")
    eng = _sim_engine(clk, obs_label="simT")
    h = eng.health()
    assert h["state"] == "ok" and h["attn_form"] == "simulated"
    assert h["slots"] == 2 and h["queue_depth"] == 0
    assert eng.obs_name == "simT"
    # one sim_calibration provenance event per engine construction
    cal = [e for e in obs.get_event_log().events()
           if e["kind"] == "sim_calibration"
           and e["engine"] == "simT"]
    assert len(cal) == 1 and cal[0]["decode_ms_per_token"] > 0


def test_deterministic_tokens_across_replays():
    """Two engines over one model, same trace: identical statuses,
    identical token streams — no RNG object anywhere in the sim."""
    reqs = [dict(prompt=[1 + i, 2 + i, 3 + i], max_new_tokens=4,
                 temperature=0.8, seed=31 + i) for i in range(6)]
    runs = []
    for _ in range(2):
        clk = {"t": 0.0}
        eng = _sim_engine(clk)
        runs.append(_drive(eng, [Request(**r) for r in reqs], clk))
    assert [r.status for r in runs[0]] == ["done"] * 6
    assert [list(r.tokens) for r in runs[0]] \
        == [list(r.tokens) for r in runs[1]]
    assert all(len(r.tokens) == 4 for r in runs[0])
    assert all(r.ttft_s is not None and r.latency_s is not None
               for r in runs[0])


def test_overload_policy_and_degrade_chaos_hook():
    clk = {"t": 0.0}
    eng = _sim_engine(clk, slots=1, max_queue=2,
                      overload_policy="reject", obs_label="simO")
    for i in range(2):
        eng.submit(Request(prompt=[1, 2, 3], max_new_tokens=3,
                           seed=i))
    from bigdl_tpu.serving.engine import OverloadError
    with pytest.raises(OverloadError):
        eng.submit(Request(prompt=[1, 2, 3], max_new_tokens=3, seed=9))
    # the chaos hook: every queued/in-flight request parks as 'failed'
    # in completed (the router failover harvest) + one engine_degraded
    failed = eng.degrade("chaos_watchdog")
    assert eng.degraded == "chaos_watchdog"
    assert len(failed) == 2
    assert {r.status for r in eng.completed.values()} == {"failed"}
    ev = [e for e in obs.get_event_log().events()
          if e["kind"] == "engine_degraded" and e["engine"] == "simO"]
    assert len(ev) == 1 and ev[0]["reason"] == "chaos_watchdog"
    from bigdl_tpu.serving.engine import EngineDegraded
    with pytest.raises(EngineDegraded):
        eng.submit(Request(prompt=[1], max_new_tokens=1, seed=0))


# -------------------------------------------------- sim-vs-real honesty

def test_divergence_vs_real_fleet():
    """THE calibration honesty gate: the identical 24-request trace
    through a REAL tiny fleet and a simulated one (per_step pacing —
    structural parity mode). Terminal counts and goodput tokens must
    agree EXACTLY (scheduling structure is modeled, not approximated);
    virtual latency/makespan must agree within a bench_compare-style
    tolerance — max(0.25, 1.5x the calibration rows' recorded
    cross-round spread). If the cost constants drift from what the
    control plane actually does, this is the test that fails."""
    lg = _loadgen()
    reports = {}
    for mode in ("real", "sim"):
        trace = lg.make_trace(24, seed=3, arrival="poisson", rate=6.0)
        if mode == "real":
            router, asc, clk = lg.build_fleet(1, slots=4)
        else:
            router, asc, clk = lg.build_sim_fleet(1, slots=4,
                                                  pacing="per_step")
        reports[mode] = lg.replay(router, trace, clock=clk)
    real, sim = reports["real"], reports["sim"]
    assert sim["by_status"] == real["by_status"] == {"done": 24}
    assert sim["goodput_tokens"] == real["goodput_tokens"]
    tol = max(0.25, 1.5 * CostModel.from_bench_artifacts().spread_frac)
    for key in ("latency_p50_s", "latency_p99_s", "ttft_p50_s",
                "makespan_s"):
        rv, sv = real[key], sim[key]
        assert rv is not None and sv is not None, key
        rel = abs(sv - rv) / max(abs(rv), 1e-9)
        assert rel <= tol, (key, rv, sv, rel, tol)


@pytest.mark.slow
def test_scenario_scale_replay_is_deterministic():
    """Duplicate coverage of the scenario_chaos drill at 10x its
    size (slow tier): a ~1.4k-request chaos_smoke day, two full
    replays through the simulated fleet, report JSON byte-identical."""
    lg = _loadgen()
    from bigdl_tpu.serving import TenantSpec
    from bigdl_tpu.serving.scenarios import compile_scenario

    digests = []
    for _ in range(2):
        trace = compile_scenario("chaos_smoke", scale=10.0)
        fc = trace["fleet"]
        router, asc, clk = lg.build_sim_fleet(
            fc["engines"], slots=fc["slots"],
            max_queue=fc["max_queue"],
            overload_policy=fc["overload_policy"], pacing=fc["pacing"],
            tenant_specs=[TenantSpec(**kw) for kw in trace["tenants"]])
        report = lg.replay(router, trace, clock=clk)
        digests.append(json.dumps(report, sort_keys=True))
    assert digests[0] == digests[1]
    rep = json.loads(digests[0])
    assert rep["requests"] == 960 + 480
    assert rep["scenario"]["fired"]["chaos"] == 2
