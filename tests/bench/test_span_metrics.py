"""The span-tree readers (ISSUE 24): each on a recorded list of events with
a known answer, then through the toy cells of tests/bench/tiny/ in
test_rehearsal.py's way: the readers are files of benchmarks/, found by the
names a manifest gives them, and nothing that was there changes. The toy
manifest is tests/bench/tiny/BENCHMARK.json plus the nine new entries,
pointed at the toy cells, written into the temporary root only."""

import json
import os

import pytest

from bench_helpers import REPO, run_tiny, tiny_root, tree_hashes  # noqa: F401
from benchmarks.harness import manifest as mf
from benchmarks.harness import span_tree as st
from benchmarks.harness.runner import load_part

NEW = {
    "chat_prefill_share": ["tiny-chat"],
    "chat_decode_fetch_p50": ["tiny-chat"],
    "chat_round_host_share": ["tiny-chat"],
    "chat_itl_p99": ["tiny-chat"],
    "chat_first_token_p90": ["tiny-chat"],
    "backlog_prefill_share": ["tiny-backlog"],
    "backlog_decode_fetch_p50": ["tiny-backlog"],
    "backlog_round_host_share": ["tiny-backlog"],
    "train_h2d_place_share": ["tiny-train", "tiny-dp4"],
}


def _x(name, t0, t1, sid, parent=None, cat="serving", **args):
    args = dict(args, id=sid)
    if parent is not None:
        args["parent"] = parent
    return {"name": name, "cat": cat, "ph": "X", "ts": t0 * 1e6,
            "dur": (t1 - t0) * 1e6, "pid": 1, "tid": 1, "args": args}


def _recorded_events():
    """Two rounds of 1 s under one router_step each, window [10, 12.5]:
    round 1 admits request 7 (fenced prefill 0.2 s inside a 0.3 s admit);
    both rounds fetch for 0.5 s; a third round straddles the window's end.
    Host time: submit 0.01; round 1: router_step self 0.1, round self
    0.02, admit self 0.1, ensure 0.01, upload 0.03, dispatch 0.04, emit
    0.05; round 2: router_step 0.1, round 0.27 (1 - .01 - .6 - .12),
    admit 0.0 (absent), ensure 0.01, upload 0.03, dispatch 0.04, emit
    0.12 -> 0.35 + 0.57 = 0.92, + submit 0.01 = 0.93 s of 2.5 s."""
    ev = [
        _x("submit", 9.5, 9.51, 1, request=7),           # before the window
        _x("submit", 10.0, 10.01, 2, request=8),
        _x("router_step", 10.1, 11.2, 10),
        _x("round", 10.15, 11.15, 11, 10, emitted=[3, 4], admitted=[7],
           attn_impl="xla"),
        _x("admit", 10.15, 10.45, 12, 11),
        # ends inside admit, began long before: not a contained child
        _x("queued", 9.5, 10.2, 13, 12, request=7),
        _x("prefill", 10.2, 10.4, 14, 12, request=7, fenced=True),
        _x("ensure_blocks", 10.45, 10.46, 15, 11),
        _x("decode_step", 10.46, 11.08, 16, 11),
        _x("upload", 10.46, 10.49, 17, 16, bytes=100),
        _x("dispatch", 10.49, 10.53, 18, 16),
        _x("fetch", 10.53, 11.03, 19, 16),
        _x("emit", 11.08, 11.13, 20, 11),
        _x("router_step", 11.2, 12.3, 30),
        _x("round", 11.25, 12.25, 31, 30, emitted=[3, 7, 7], admitted=[],
           attn_impl="xla"),
        _x("ensure_blocks", 11.25, 11.26, 32, 31),
        _x("decode_step", 11.26, 11.86, 33, 31),
        _x("upload", 11.26, 11.29, 34, 33, bytes=100),
        _x("dispatch", 11.29, 11.33, 35, 33),
        _x("fetch", 11.33, 11.83, 36, 33),
        _x("emit", 11.86, 11.98, 37, 31),
        _x("round", 12.3, 13.3, 41, None, emitted=[3], admitted=[]),
        # an unfenced prefill (a program from before the fence) is not read
        _x("prefill", 12.31, 12.32, 42, 41, request=9),
    ] + [_first_token(ts, rid, ttft) for ts, rid, ttft in (
        (9.9, 2, 9.0), (11.15, 7, 1.65), (12.25, 8, 0.4), (12.6, 9, 7.0))]
    return ev


def _first_token(ts, request, ttft_s):
    return {"name": "first_token", "cat": "serving", "ph": "i", "s": "t",
            "ts": ts * 1e6, "pid": 1, "tid": 1,
            "args": {"request": request, "ttft_s": ttft_s}}


def _recorded():
    return st.spans_of(_recorded_events(), "serving")


WINDOW = (10.0, 12.5)


class _Ctx:
    def __init__(self, spans=()):
        self.record = {"window": WINDOW, "window_s": 2.5, "spans": [
            (s["name"], s["t0"], s["t1"]) for s in spans]}
        self.lines = []

    def out(self, line):
        self.lines.append(line)


def test_fenced_prefill_share_known_answer():
    assert st.fenced_prefill_share(_recorded(), WINDOW) == \
        pytest.approx(100 * 0.2 / 2.5)
    unfenced = [s for s in _recorded() if not s["args"].get("fenced")]
    assert st.fenced_prefill_share(unfenced, WINDOW) is None


def test_self_times_skip_children_that_began_before_their_parent():
    own = st.self_times(_recorded())
    assert own[12] == pytest.approx(0.3 - 0.2)     # admit less prefill only
    assert own[11] == pytest.approx(1.0 - 0.3 - 0.01 - 0.62 - 0.05)
    assert own[16] == pytest.approx(0.62 - 0.03 - 0.04 - 0.5)


def test_round_host_share_known_answer():
    assert st.round_host_share(_recorded(), WINDOW) == \
        pytest.approx(100 * 0.93 / 2.5)
    no_rounds = [s for s in _recorded() if s["name"] != "round"]
    assert st.round_host_share(no_rounds, WINDOW) is None


def test_inter_token_gaps_known_answer():
    """Request 3: tokens at 11.15, 12.25 (13.3 is past the window);
    request 7: two tokens of one round, 12.25 and 12.25; request 4: one
    token, no gap."""
    gaps = sorted(st.inter_token_gaps(_recorded(), WINDOW))
    assert gaps == pytest.approx([0.0, 1.1])
    ctx = _Ctx()
    assert st.itl_p99_ms(ctx, _recorded()) == pytest.approx(1100.0)
    assert "2 in the window" in ctx.lines[0]
    assert st.itl_p99_ms(ctx, []) is None


def test_first_token_p90_known_answer():
    """Two first tokens in the window (1.65 s and 0.4 s); the one before
    it and the one after it are not read."""
    ctx = _Ctx()
    assert st.first_token_p90_ms(ctx, _recorded_events()) == \
        pytest.approx(1650.0)
    assert "2 in the window" in ctx.lines[0] and "1025.0" in ctx.lines[0]
    assert st.first_token_p90_ms(ctx, _recorded_events()[:-4]) is None


def test_report_known_answer():
    """The printed tree: per-name shares, what the children cover, rounds
    by admissions, upload bytes."""
    ctx = _Ctx()
    assert st.host_share(ctx, _recorded()) == pytest.approx(100 * 0.93 / 2.5)
    table, cover, rounds = ctx.lines
    assert "fetch 2 500.000 40.00 40.00" in table
    assert "admit 1 300.000 12.00 4.00" in table
    assert "decode_step 91.94% 91.94%" in cover     # .57 of .62 and of .6
    assert "round 73.00% 73.00%" in cover           # .98 of 1 and .73 of 1
    assert rounds.endswith("attn_impl xla; round p50_ms by admissions: "
                           "0: 1000.0 (1), 1: 1000.0 (1); "
                           "upload p50 100 bytes")
    ctx = _Ctx()
    assert st.host_share(ctx, []) is None and ctx.lines == []


@pytest.mark.parametrize("name,expected", [
    ("chat_decode_fetch_p50", 500.0), ("backlog_decode_fetch_p50", 500.0),
    ("train_h2d_place_share", None)])
def test_readers_on_the_drivers_span_list(name, expected):
    value = load_part(REPO, "layer_metrics", name).read(_Ctx(_recorded()))
    assert value == (None if expected is None else pytest.approx(expected))


@pytest.mark.parametrize("name", sorted(NEW))
def test_every_reader_returns_none_without_the_tracer(name):
    """`--trace 0`, or a program that records no such span: nothing to
    read, and no error."""
    from bigdl_tpu import obs

    obs.set_tracer(None)
    assert load_part(REPO, "layer_metrics", name).read(_Ctx()) is None


def test_the_manifest_names_the_readers_with_the_layers_it_had():
    manifest = mf.load(REPO)
    assert mf.problems(manifest, REPO) == []
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    old_layers = {m["layer"] for m in manifest["per_layer"]
                  if m["name"] not in NEW}
    for name in NEW:
        assert by_name[name]["layer"] in old_layers
        assert by_name[name]["source"] == "program_span"
        assert os.path.isfile(os.path.join(
            REPO, "benchmarks", "layer_metrics", f"{name}.py"))
    # in their order among themselves, wherever later PRs append theirs
    assert [m["name"] for m in manifest["per_layer"] if m["name"] in NEW] == [
        "chat_prefill_share", "backlog_prefill_share",
        "chat_decode_fetch_p50", "backlog_decode_fetch_p50",
        "chat_round_host_share", "backlog_round_host_share",
        "chat_itl_p99", "train_h2d_place_share", "chat_first_token_p90"]


@pytest.fixture(scope="module")
def span_root(tiny_root):
    """The toy root with the nine readers named by its manifest."""
    root, before = tiny_root
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    real = {m["name"]: m for m in mf.load(REPO)["per_layer"]}
    moves = {"tiny-chat": "serve_tpot_mean",
             "tiny-backlog": "serve_throughput",
             "tiny-train": "train_throughput"}
    for name, cells in NEW.items():
        manifest["per_layer"].append(dict(
            real[name], layer="tiny", moves=moves[cells[0]],
            workloads=cells))
    with open(path, "w") as f:
        json.dump(manifest, f)
    assert mf.problems(mf.load(root), root) == []
    after = tree_hashes(os.path.join(root, "benchmarks"))
    assert {k: after[k] for k in before} == before, "an existing file changed"
    return root


@pytest.mark.parametrize("workload", ["tiny-chat", "tiny-backlog"])
def test_serve_rehearsal_reports_the_span_metrics(span_root, workload):
    result, lines = run_tiny(span_root, workload, seed=2 ** 31 + 24,
                             trace=True)
    assert result["correct"] is True
    got = result["metrics"]
    prefix = workload.split("-")[1]
    share = got[f"{prefix}_prefill_share"]["value"]
    host = got[f"{prefix}_round_host_share"]["value"]
    fetch = got[f"{prefix}_decode_fetch_p50"]["value"]
    assert 0 < share < 100 and 0 < host < 100 and fetch > 0
    # fetch is inside decode_step, round by round
    assert fetch <= got[f"{prefix}_decode_step_p50"]["value"]
    if workload == "tiny-chat":
        assert got["chat_itl_p99"]["value"] > 0
        assert any(l.startswith("inter-token gaps: ") for l in lines)
        assert got["chat_first_token_p90"]["value"] > 0
        assert any(l.startswith("first tokens: ") for l in lines)
    else:
        assert "chat_itl_p99" not in got
        assert "chat_first_token_p90" not in got
    # the run prints its span tree beside the host share
    assert sum(l.startswith("span tree: ") for l in lines) == 3


@pytest.mark.parametrize("workload", ["tiny-train", "tiny-dp4"])
def test_train_rehearsal_reports_h2d_place_inside_dispatch(span_root,
                                                           workload):
    result, _ = run_tiny(span_root, workload, seed=2 ** 31 + 24, trace=True)
    assert result["correct"] is True
    place = result["metrics"]["train_h2d_place_share"]["value"]
    assert 0 < place <= result["metrics"]["train_dispatch_share"]["value"]


def test_untraced_rehearsal_has_no_span_metric(span_root):
    from bigdl_tpu import obs

    obs.set_tracer(None)        # a run is a process: no tracer left over
    result, _ = run_tiny(span_root, "tiny-chat", seed=2 ** 31 + 25)
    assert not set(result["metrics"]) & set(NEW)
    assert obs.get_tracer().events() == []
