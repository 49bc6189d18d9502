"""What an admission is made of (ISSUE 40): the `admit` span of the round's
tree keeps its place and its `prefill` child, and holds four kinds of leaf
in a category of their own ("serving.admit": `queue_expire`, `queue_pop`,
`seat_prepare`, `seat_commit`), so that the readers of the "serving" tree
read what they read before; `admit` counts its queue (`queued`, `admitted`,
and `scanned`: since ISSUE 46 the entries its expiry and its pops EXAMINED,
the head of a line a pop and the top of the expiry heap a round, where it
was the whole queue a walk) and `prefill` says where its launch ended
(`launched_s`). With the tracer off none of it exists. CPU, tiny sizes."""

import jax
import numpy as np
import pytest

from benchmarks.harness import span_tree as st
from bigdl_tpu import obs
from bigdl_tpu.obs import spans as spans_mod

DETAIL = {"queue_expire", "queue_pop", "seat_prepare", "seat_commit"}
# PERF.md §3's table, the rows of category "serving" (`first_token` is an
# instant, `queued` and `request[<status>]` are `complete()` spans)
SERVING = {"submit", "router_step", "round", "admit", "prefill",
           "ensure_blocks", "decode_step", "upload", "dispatch", "fetch",
           "emit", "queued", "request[done]", "first_token"}


@pytest.fixture(autouse=True)
def _fresh_obs():
    prev = obs.set_enabled(True)
    obs.reset_all()
    yield
    obs.reset_all()
    obs.set_enabled(prev)


@pytest.fixture(scope="module")
def lm():
    from bigdl_tpu.models.transformer import build_lm

    m = build_lm(vocab_size=50, dim=32, num_heads=2, num_layers=1,
                 max_len=64)
    m.build(jax.random.PRNGKey(0))
    return m


def _requests(lens=(3, 10, 6, 12, 5), new=(3, 5, 2, 4, 1), priority=None):
    from bigdl_tpu.serving import Request

    rng = np.random.RandomState(0)
    return [Request(prompt=list(rng.randint(1, 50, n)), max_new_tokens=k,
                    priority=(priority[i] if priority else 0))
            for i, (n, k) in enumerate(zip(lens, new))]


def _engine(lm, **kw):
    from bigdl_tpu.serving import InferenceEngine

    args = dict(slots=2, prefill_buckets=(8, 16))
    args.update(kw)
    return InferenceEngine(lm, **args)


def _x(name=None):
    return [e for e in obs.get_tracer().events(name) if e["ph"] == "X"]


def _end(e):
    return e["ts"] + e["dur"]


@pytest.fixture
def served(lm):
    """Five requests through a router in front of a two-slot engine, the
    tracer on."""
    from bigdl_tpu.serving import EngineRouter

    obs.set_tracer(obs.SpanTracer(enabled=True))
    eng = _engine(lm)
    results = EngineRouter([eng]).run(_requests())
    assert all(r.status == "done" for r in results)
    return eng


def test_every_detail_span_is_a_leaf_under_an_admit(served):
    by_id = {e["args"]["id"]: e for e in _x()}
    parents = {e["args"].get("parent") for e in _x()}
    detail = [e for e in _x() if e["cat"] == "serving.admit"]
    assert {e["name"] for e in detail} == DETAIL
    for e in detail:
        admit = by_id[e["args"]["parent"]]
        assert admit["name"] == "admit" and admit["cat"] == "serving"
        assert admit["ts"] <= e["ts"] and _end(e) <= _end(admit)
        assert e["args"]["id"] not in parents       # nothing hangs under it
    # and the tree the benchmark knows is as it was: prefill under admit
    for p in _x("prefill"):
        assert by_id[p["args"]["parent"]]["name"] == "admit"


def test_an_admit_holds_its_parts_in_order_without_overlap(served):
    for admit in _x("admit"):
        kids = sorted((e for e in _x() if e["args"].get("parent")
                       == admit["args"]["id"] and e["ts"] >= admit["ts"]),
                      key=lambda e: e["ts"])
        names = [k["name"] for k in kids]
        assert names[0] == "queue_expire"
        seated = ["queue_pop", "seat_prepare", "prefill", "seat_commit"]
        assert names[1:] == seated * admit["args"]["admitted"]
        for a, b in zip(kids, kids[1:]):
            assert _end(a) <= b["ts"]
        for pop, prep, pre, com in zip(*(kids[1 + i::4] for i in range(4))):
            assert pop["args"]["request"] == prep["args"]["request"] == \
                pre["args"]["request"] == com["args"]["request"]
            assert prep["args"]["prefix_tokens"] == \
                pre["args"]["prefix_tokens"]
            # the placed `block_ids`: int32, one a fresh block
            assert prep["args"]["placed_bytes"] == \
                4 * prep["args"]["new_blocks"] > 0


def test_the_serving_category_holds_the_names_it_held(served):
    events = obs.get_tracer().events()
    assert {e["name"] for e in events if e["cat"] == "serving"} == SERVING
    assert {e["name"] for e in events if e["cat"] == "serving.admit"} \
        == DETAIL
    assert not DETAIL & set(st.HOST_SPANS)


def test_the_old_readers_read_what_they_read(served):
    """`round_host_share` and `fenced_prefill_share` over the "serving"
    category are what they are over every span with the detail spans
    filtered out, and NOT what a reader would get with them left in."""
    serving = st.program_spans("serving")
    every = st.program_spans()
    kept = [s for s in every if s["name"] not in DETAIL]
    rounds = [s for s in serving if s["name"] == "round"]
    window = (min(s["t0"] for s in rounds) - 1e-3,
              max(s["t1"] for s in rounds) + 1e-3)
    assert len(every) > len(kept) == len(serving)
    host = st.round_host_share(serving, window)
    assert 0 < host < 100
    assert st.round_host_share(kept, window) == host
    assert st.fenced_prefill_share(kept, window) == \
        st.fenced_prefill_share(serving, window) > 0
    # in the tree's category they would have taken time out of `admit`
    assert st.round_host_share(every, window) < host


def test_scanned_is_the_queue_walk_counted_by_hand(lm):
    """Seven queued, two free slots, priorities known, no request with an
    expiry: the expiry examines 0 (its heap is empty), each pop 1, the head
    of the highest priority's line; then one slot frees at a time."""
    obs.set_tracer(obs.SpanTracer(enabled=True))
    eng = _engine(lm)
    reqs = _requests(lens=(3, 4, 5, 6, 3, 4, 5), new=(1, 2, 1, 2, 1, 2, 1),
                     priority=(0, 2, 0, 1, 2, 0, 1))
    ids = [eng.submit(r) for r in reqs]
    eng.step()
    first = _x("admit")[0]["args"]
    assert (first["queued"], first["scanned"], first["admitted"]) == \
        (7, 0 + 1 + 1, 2)
    pops = _x("queue_pop")
    assert [p["args"]["queued"] for p in pops] == [7, 6]
    # highest priority first, FIFO within one
    assert [p["args"]["request"] for p in pops] == [ids[1], ids[4]]
    expire = _x("queue_expire")[0]["args"]
    assert (expire["queued"], expire["expired"]) == (7, 0)
    eng.run()
    for a in _x("admit"):
        a = a["args"]
        # one entry a pop, and no free slot or an empty queue: nothing
        assert a["queued"] >= a["scanned"] == a["admitted"]
    assert sum(a["args"]["admitted"] for a in _x("admit")) == \
        eng.stats["prefill_calls"] == 7
    assert len(_x("queue_pop")) == 7


def test_an_expired_request_is_counted_and_not_scanned_twice(lm):
    t = {"now": 100.0}
    obs.set_tracer(obs.SpanTracer(enabled=True))
    eng = _engine(lm, clock=lambda: t["now"])
    reqs = _requests(lens=(3, 4, 5), new=(1, 1, 1))
    reqs[1].max_queue_wait_s = 1.0
    for r in reqs:
        eng.submit(r)
    t["now"] += 2.0
    eng.step()
    expire = _x("queue_expire")[0]["args"]
    assert (expire["queued"], expire["expired"]) == (3, 1)
    admit = _x("admit")[0]["args"]
    # the expiry examines the 1 entry of its heap (due, and then the heap
    # is empty) and takes the request out of the MIDDLE of the line, where
    # its entry stays, dead; the first pop examines the head and the dead
    # entry that it uncovers, which goes with it; the second pop the head
    assert (admit["queued"], admit["scanned"], admit["admitted"]) == \
        (3, 1 + 2 + 1, 2)


def test_admitted_sums_to_the_prefill_calls(served):
    admits = _x("admit")
    assert sum(a["args"]["admitted"] for a in admits) == \
        served.stats["prefill_calls"] == len(_x("prefill")) == 5
    by_round = {r["args"]["id"]: r for r in _x("round")}
    for a in admits:
        assert a["args"]["admitted"] == \
            len(by_round[a["args"]["parent"]]["args"]["admitted"])
        assert set(a["args"]) == {"id", "parent", "queued", "scanned",
                                  "admitted"}


def test_launched_s_lies_inside_the_prefill(served):
    prefills = _x("prefill")
    assert prefills
    for p in prefills:
        assert p["args"]["fenced"] is True
        assert 0 <= p["args"]["launched_s"] <= p["dur"] / 1e6


def test_a_failed_seating_still_closes_seat_prepare(lm, monkeypatch):
    """No block to be had: `_admit_into` returns False from inside
    `seat_prepare`, which records all the same, and no `prefill` or
    `seat_commit` follows it."""
    obs.set_tracer(obs.SpanTracer(enabled=True))
    eng = _engine(lm)
    monkeypatch.setattr(eng, "_alloc_blocks", lambda n, protect=None: None)
    rid = eng.submit(_requests(lens=(5,), new=(2,))[0])
    eng.step()
    (prep,) = _x("seat_prepare")
    assert prep["args"] == {"request": rid, "evicted_blocks": 0,
                            "evict_visits": 0, "id": prep["args"]["id"],
                            "parent": _x("admit")[0]["args"]["id"]}
    assert not _x("prefill") and not _x("seat_commit")
    admit = _x("admit")[0]["args"]
    # the one pop examined the one entry; the requeue examines nothing
    assert (admit["queued"], admit["scanned"], admit["admitted"]) == (1, 1, 0)
    assert eng.queue_depth == 1                     # requeued at the front


def test_seat_prepare_counts_the_blocks_it_evicted(lm):
    """A pool of five usable blocks and one slot: the first prompt leaves
    three cached blocks behind, the second needs four fresh ones, so its
    seating evicts; the counter and the spans agree."""
    obs.set_tracer(obs.SpanTracer(enabled=True))
    eng = _engine(lm, slots=1, block_size=4, max_len=20, pool_blocks=6)
    for r in _requests(lens=(13, 13), new=(2, 2)):
        eng.run([r])
    evicted = [e["args"]["evicted_blocks"] for e in _x("seat_prepare")]
    assert evicted[0] == 0 and evicted[1] > 0
    assert sum(evicted) == eng.stats["pool_evictions"]
    # the index hands over a chain leaf first: one entry a victim
    visits = [e["args"]["evict_visits"] for e in _x("seat_prepare")]
    assert visits == evicted
    assert sum(visits) == eng.stats["pool_eviction_visits"]
    for e in _x("ensure_blocks"):
        assert set(e["args"]) == {"id", "parent", "evicted_blocks",
                                  "evict_visits"}


def test_the_speculative_mirror_seats_under_whatever_is_open(lm):
    """`SpeculativeEngine` calls `_admit_into` on its draft directly: the
    parts hang under the span that is open there, leaves all the same."""
    from bigdl_tpu.serving import SpeculativeEngine

    obs.set_tracer(obs.SpanTracer(enabled=True))
    eng = SpeculativeEngine(_engine(lm), _engine(lm), k=2)
    res = eng.run(_requests(lens=(5, 7), new=(3, 3)))
    assert all(r.status == "done" for r in res)
    by_id = {e["args"]["id"]: e for e in _x()}
    parents = {e["args"].get("parent") for e in _x()}
    preps = _x("seat_prepare")
    assert len(preps) == len(_x("prefill")) == len(_x("seat_commit")) == 4
    for e in preps + _x("seat_commit"):
        assert e["args"]["id"] not in parents
        parent = by_id.get(e["args"].get("parent"))
        assert parent is None or parent["name"] not in DETAIL | {"prefill"}


def test_tracer_off_no_event_no_wait_no_args(lm, monkeypatch):
    """The default: nothing recorded, `block_until_ready` never reached,
    and no span site hands the shared no-op span an argument."""
    calls = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: calls.append("wait") or real(x))
    monkeypatch.setattr(spans_mod._NullSpan, "set",
                        lambda self, **kw: calls.append(kw))
    monkeypatch.setattr(spans_mod._NullSpan, "elapsed",
                        lambda self: calls.append("elapsed"),
                        raising=False)
    eng = _engine(lm)
    res = eng.run(_requests())
    assert all(r.status == "done" for r in res)
    assert eng.stats["prefill_calls"] == 5
    assert obs.get_tracer().events() == []
    assert calls == []
