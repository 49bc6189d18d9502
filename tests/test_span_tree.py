"""One span tree per scheduling round and per train step (ISSUE 24).

The tracer records causality (`args.id`, `args.parent`); the serving
engine opens `round > admit > prefill, ensure_blocks, decode_step >
upload / dispatch / fetch, emit` under the router's `router_step`, the
optimizers `dispatch > h2d_place`; counts ride on the spans' args; the
compile listener adds `compile`. With the tracer off none of it exists
and no device array is touched. CPU, tiny sizes."""

import threading

import jax
import numpy as np
import pytest

from bigdl_tpu import obs


@pytest.fixture(autouse=True)
def _fresh_obs():
    prev = obs.set_enabled(True)
    obs.reset_all()
    yield
    obs.reset_all()
    obs.set_enabled(prev)


def _tiny_lm():
    from bigdl_tpu.models.transformer import build_lm

    m = build_lm(vocab_size=50, dim=32, num_heads=2, num_layers=1,
                 max_len=64)
    m.build(jax.random.PRNGKey(0))
    return m


def _requests(lens=(3, 10, 6, 12, 5), new=(3, 5, 2, 4, 1)):
    from bigdl_tpu.serving import Request

    rng = np.random.RandomState(0)
    return [Request(prompt=list(rng.randint(1, 50, n)), max_new_tokens=k)
            for n, k in zip(lens, new)]


def _spans(name=None):
    return [e for e in obs.get_tracer().events(name) if e["ph"] == "X"]


def _children(parent):
    return [e for e in _spans() if e["args"].get("parent")
            == parent["args"]["id"]]


def _end(e):
    return e["ts"] + e["dur"]


@pytest.fixture
def served():
    """Five requests through a router in front of a two-slot engine, the
    tracer on: (engine, results)."""
    from bigdl_tpu.serving import EngineRouter, InferenceEngine

    obs.set_tracer(obs.SpanTracer(enabled=True))
    eng = InferenceEngine(_tiny_lm(), slots=2, prefill_buckets=(8, 16))
    results = EngineRouter([eng]).run(_requests())
    assert all(r.status == "done" for r in results)
    return eng, results


# ------------------------------------------------------------- the tracer

def test_nested_spans_record_id_and_parent():
    tr = obs.set_tracer(obs.SpanTracer(enabled=True))
    with tr.span("a") as a:
        with tr.span("b") as b:
            assert tr.current() == b.id
        tr.complete("c", "host", 0.0, 1.0)
    with tr.span("d"):
        pass
    by = {e["name"]: e["args"] for e in tr.events()}
    assert by["a"] == {"id": a.id} and by["d"].get("parent") is None
    assert by["b"] == {"id": b.id, "parent": a.id}
    assert by["c"]["parent"] == a.id            # complete(): same rule
    assert len({v["id"] for v in by.values()}) == 4
    assert tr.current() is None


def test_parent_is_handed_across_a_thread():
    """A closure that runs on another thread has no open span there:
    the parent comes explicitly, as the watchdog's dispatch gets it."""
    tr = obs.set_tracer(obs.SpanTracer(enabled=True))
    with tr.span("outer") as outer:
        parent = tr.current()

        def work():
            with tr.span("orphan"):
                pass
            with tr.span("child", parent=parent):
                pass

        th = threading.Thread(target=work)
        th.start()
        th.join()
    by = {e["name"]: e["args"] for e in tr.events()}
    assert "parent" not in by["orphan"]
    assert by["child"]["parent"] == outer.id


def test_span_clock_overrides_the_tracers_for_one_span():
    tr = obs.set_tracer(obs.SpanTracer(enabled=True, clock=lambda: 5.0))
    ticks = iter((100.0, 100.25))
    with tr.span("mine", clock=lambda: next(ticks)):
        pass
    with tr.span("theirs"):
        pass
    tr.instant("mark", ts=42.0)
    by = {e["name"]: e for e in tr.events()}
    assert by["mine"]["ts"] == 100.0e6 and by["mine"]["dur"] == 0.25e6
    assert by["theirs"]["ts"] == 5.0e6
    assert by["mark"]["ts"] == 42.0e6


def test_span_set_adds_counts_and_the_null_span_ignores_them():
    tr = obs.set_tracer(obs.SpanTracer(enabled=True))
    with tr.span("x", args={"a": 1}) as sp:
        sp.set(b=2)
    assert tr.events("x")[0]["args"] == {"a": 1, "b": 2, "id": sp.id}
    off = obs.SpanTracer()
    with off.span("x") as sp:
        sp.set(b=2)
        assert sp.id is None and off.current() is None
    assert off.events() == []


# ------------------------------------------------- one tree a round

def test_every_round_has_one_decode_step_and_ordered_children(served):
    eng, _ = served
    rounds = _spans("round")
    assert len(rounds) == eng.stats["decode_steps"]
    for r in rounds:
        kids = sorted(_children(r), key=lambda e: e["ts"])
        names = [k["name"] for k in kids]
        assert names == ["admit", "ensure_blocks", "decode_step", "emit"]
        for a, b in zip(kids, kids[1:]):
            assert _end(a) <= b["ts"]               # no overlap
        assert r["ts"] <= kids[0]["ts"] and _end(kids[-1]) <= _end(r)
        assert r["args"]["attn_impl"] == "xla"
        # the counts a reader uses, and no second copy of any of them
        assert r["args"]["attn_form"] == "heads"    # dim 32: toy widths
        assert set(r["args"]) == {"id", "parent", "attn_impl", "attn_form",
                                  "admitted", "emitted"}


def test_decode_step_holds_upload_dispatch_fetch(served):
    eng, _ = served
    # the step's nine host operands: seven (slots,) vectors of 4 B an
    # entry, the bool `poison` and the block table. `upload` says their
    # bytes though the call places them (PR 41: the transfer is inside
    # `dispatch`)
    nine_nbytes = eng.slots * (7 * 4 + 1) + eng._table.nbytes
    for d in _spans("decode_step"):
        kids = sorted(_children(d), key=lambda e: e["ts"])
        assert [k["name"] for k in kids] == ["upload", "dispatch", "fetch"]
        for a, b in zip(kids, kids[1:]):
            assert _end(a) <= b["ts"]
        assert kids[0]["args"]["bytes"] == nine_nbytes
        assert d["args"]["active"] >= 1


def test_prefill_is_under_an_admit_of_that_round(served):
    by_id = {e["args"]["id"]: e for e in _spans()}
    prefills = _spans("prefill")
    assert len(prefills) == 5
    seen = set()
    for p in prefills:
        admit = by_id[p["args"]["parent"]]
        assert admit["name"] == "admit"
        rnd = by_id[admit["args"]["parent"]]
        assert rnd["name"] == "round"
        assert p["args"]["request"] in rnd["args"]["admitted"]
        assert p["args"]["fenced"] is True
        assert p["args"]["bucket"] in (8, 16)
        seen.add(p["args"]["request"])
    assert len(seen) == 5


def test_rounds_hang_under_router_step_and_submits_are_roots(served):
    steps = {e["args"]["id"] for e in _spans("router_step")}
    assert all(r["args"]["parent"] in steps for r in _spans("round"))
    subs = _spans("submit")
    assert sorted(s["args"]["request"] for s in subs) == list(range(5))
    assert all("parent" not in s["args"] for s in subs)


def test_emitted_ids_are_the_per_token_stamps(served):
    _, results = served
    emitted = [rid for r in _spans("round") for rid in r["args"]["emitted"]]
    for res in results:
        assert emitted.count(res.id) == len(res.tokens)
    assert len(emitted) == sum(len(r.tokens) for r in results)


def test_first_token_fires_once_and_equals_ttft():
    """On a bare engine, whose results carry the engine's own times (a
    router restamps them from its own admission)."""
    from bigdl_tpu.serving import InferenceEngine

    obs.set_tracer(obs.SpanTracer(enabled=True))
    eng = InferenceEngine(_tiny_lm(), slots=2, prefill_buckets=(8, 16))
    results = eng.run(_requests())
    marks = obs.get_tracer().events("first_token")
    assert all(e["ph"] == "i" for e in marks)
    assert sorted(e["args"]["request"] for e in marks) == \
        sorted(r.id for r in results)
    queued = {e["args"]["request"]: e for e in _spans("queued")}
    for e in marks:
        res = next(r for r in results if r.id == e["args"]["request"])
        assert e["args"]["ttft_s"] == res.ttft_s
        # stamped where t_first is: on the submit stamp's clock
        assert (e["ts"] - queued[res.id]["ts"]) / 1e6 == \
            pytest.approx(res.ttft_s, abs=1e-6)


def test_a_retried_round_has_one_decode_step_per_attempt(monkeypatch):
    """The failed attempt's span ends at its exception, with no child;
    the round's other children are as ever."""
    from bigdl_tpu.serving import InferenceEngine

    obs.set_tracer(obs.SpanTracer(enabled=True))
    eng = InferenceEngine(_tiny_lm(), slots=2, prefill_buckets=(8, 16),
                          step_retries=1, retry_backoff_s=0.0)
    real, calls = eng._dispatch_and_fetch, []

    def flaky(*a, **kw):
        calls.append(1)
        if len(calls) == 1:
            raise RuntimeError("transient")
        return real(*a, **kw)

    monkeypatch.setattr(eng, "_dispatch_and_fetch", flaky)
    results = eng.run(_requests(lens=(3,), new=(2,)))
    assert results[0].status == "done" and eng.stats["retries"] == 1
    first = min(_spans("round"), key=lambda e: e["ts"])
    kids = sorted(_children(first), key=lambda e: e["ts"])
    assert [k["name"] for k in kids] == [
        "admit", "ensure_blocks", "decode_step", "decode_step", "emit"]
    assert _children(kids[2]) == [] and len(_children(kids[3])) == 3


def test_watchdog_thread_keeps_the_tree():
    """With a step budget the dispatch runs on the watchdog's thread:
    upload / dispatch / fetch still hang under decode_step."""
    from bigdl_tpu.serving import InferenceEngine

    obs.set_tracer(obs.SpanTracer(enabled=True))
    eng = InferenceEngine(_tiny_lm(), slots=2, prefill_buckets=(8, 16),
                          step_timeout_s=60.0)
    eng.run(_requests((3, 6), (2, 2)))
    steps = _spans("decode_step")
    assert steps
    for d in steps:
        kids = _children(d)
        assert sorted(k["name"] for k in kids) == \
            ["dispatch", "fetch", "upload"]
        assert {k["tid"] for k in kids} != {d["tid"]}


def test_injected_clock_times_every_span_of_the_round():
    """A drill's engine clock is the clock of its spans (the tracer here
    keeps the default one, which never reads 1,000 s)."""
    from bigdl_tpu.serving import InferenceEngine

    t = {"now": 1000.0}

    def clock():
        t["now"] += 0.001
        return t["now"]

    obs.set_tracer(obs.SpanTracer(enabled=True, clock=lambda: 5.0))
    eng = InferenceEngine(_tiny_lm(), slots=1, prefill_buckets=(8,),
                          clock=clock)
    eng.run(_requests((3,), (2,)))
    for name in ("round", "admit", "prefill", "decode_step", "fetch",
                 "emit", "queued", "request[done]"):
        assert all(e["ts"] >= 1000.0e6 for e in _spans(name)), name
    assert _spans("round")


def test_tracer_off_records_nothing_and_never_waits(monkeypatch):
    """The default: no event, no `block_until_ready` reached, and the
    #buckets + 1 compile contract as before."""
    from bigdl_tpu.serving import InferenceEngine
    from bigdl_tpu.serving import engine as engine_mod

    calls = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: calls.append(1) or real(x))
    eng = InferenceEngine(_tiny_lm(), slots=2, prefill_buckets=(8, 16))
    before = dict(engine_mod._TRACES)
    res = eng.run(_requests())
    assert all(r.status == "done" for r in res)
    assert obs.get_tracer().events() == []
    assert calls == []
    # the same traffic with the tracer on: fenced, and nothing new traced
    obs.set_tracer(obs.SpanTracer(enabled=True))
    traced = dict(engine_mod._TRACES)
    res2 = eng.run(_requests())
    assert [r.tokens for r in res2] == [r.tokens for r in res]
    assert len(calls) == 5                          # one per prefill
    assert dict(engine_mod._TRACES) == traced
    assert {k: traced[k] - before[k] for k in traced
            if traced[k] != before.get(k, 0)}.keys() <= \
        {"prefill", "decode"}
    assert eng.stats["prefill_traces"] <= 2
    assert eng.stats["decode_traces"] <= 1


# ---------------------------------------------- one tree a train step

def _optimizer(batch=32):
    from bigdl_tpu import nn
    from bigdl_tpu.dataset import DataSet
    from bigdl_tpu.dataset.mnist import synthetic_mnist
    from bigdl_tpu.models import lenet
    from bigdl_tpu.optim import Optimizer, SGD, Trigger

    model = lenet.build(10).build(jax.random.PRNGKey(7))
    return (Optimizer(model, DataSet.array(synthetic_mnist(batch * 3)),
                      nn.ClassNLLCriterion(), batch_size=batch)
            .set_optim_method(SGD(learningrate=0.01))
            .set_end_when(Trigger.max_iteration(3)))


@pytest.mark.parametrize("mesh", [False, True], ids=["local", "distri"])
def test_dispatch_contains_h2d_place(mesh):
    from bigdl_tpu.parallel import make_mesh

    obs.set_tracer(obs.SpanTracer(enabled=True))
    opt = _optimizer()
    if mesh:
        opt.set_mesh(make_mesh({"data": 8}))
    opt.optimize()
    dispatches = _spans("dispatch")
    places = _spans("h2d_place")
    # the mesh loop places one batch ahead, inside the dispatch of the
    # step before: the end trigger leaves one placed and never fed
    n_places = 4 if mesh else 3
    assert len(dispatches) == 3 and len(places) == n_places
    by_id = {d["args"]["id"]: d for d in dispatches}
    for p in places:
        d = by_id[p["args"]["parent"]]
        assert d["ts"] <= p["ts"] and _end(p) <= _end(d)
    snap = obs.get_registry().snapshot()["metrics"]
    phases = {s["labels"]["phase"]: s["count"]
              for s in snap["training_phase_seconds"]["series"]}
    assert phases["dispatch_s"] == 3 and phases["h2d_place_s"] == n_places


# ------------------------------------------------ the compile listener

def test_compile_listener_counts_and_spans():
    """One listener for the process: a backend compile increments
    `xla_compiles_total{cache}` and, traced, leaves a `compile` span."""
    import jax.numpy as jnp

    obs.set_tracer(obs.SpanTracer(enabled=True))
    with obs.get_tracer().span("outer") as outer:
        jax.jit(lambda x: x * 3 + 41)(jnp.arange(7)).block_until_ready()
    fam = obs.get_registry().snapshot()["metrics"]["xla_compiles_total"]
    assert sum(s["value"] for s in fam["series"]) >= 1
    assert {s["labels"]["cache"] for s in fam["series"]} <= {"hit", "miss"}
    spans = _spans("compile")
    assert spans and spans[-1]["args"]["cache"] in ("hit", "miss")
    assert spans[-1]["args"]["parent"] == outer.id
    assert spans[-1]["dur"] > 0
    n = len(spans)
    obs.install_compile_listener()              # idempotent
    jax.jit(lambda x: x * 5 + 43)(jnp.arange(7)).block_until_ready()
    assert len(_spans("compile")) == n + 1


def test_compile_counter_is_scraped_but_not_bundled(tmp_path):
    """How many executables a process built is its caches' state: the
    scrape shows the counter, a flight-recorder bundle (byte-identical
    across runs of a drill) leaves it out at the source."""
    import json

    import jax.numpy as jnp

    rec = obs.FlightRecorder(str(tmp_path)).install()
    jax.jit(lambda x: x * 5 + 43)(jnp.arange(3)).block_until_ready()
    reg = obs.get_registry()
    assert "xla_compiles_total" in reg.render_prometheus()
    assert "xla_compiles_total" in reg.snapshot()["metrics"]
    assert "xla_compiles_total" not in \
        reg.snapshot(process_state=False)["metrics"]
    obs.emit_event("engine_degraded", engine="e0", reason="test")
    assert len(rec.bundles) == 1
    with open(tmp_path / rec.bundles[0] / "registry.json") as f:
        assert "xla_compiles_total" not in f.read()


def test_compile_listener_honors_the_kill_switch():
    import jax.numpy as jnp

    obs.set_enabled(False)
    obs.set_tracer(obs.SpanTracer(enabled=True))
    jax.jit(lambda x: x * 7 + 47)(jnp.arange(7)).block_until_ready()
    assert "xla_compiles_total" not in \
        obs.get_registry().snapshot()["metrics"]
    assert obs.get_tracer().events() == []
