"""The admission readers (ISSUE 40): each on a recorded list of events with
a known answer, None without the tracer and on the spans of a program from
before the new args, then through the toy cells of tests/bench/tiny/ in
test_span_metrics.py's way: the readers are files of benchmarks/, found by
the names a manifest gives them, and nothing that was there changes."""

import json
import os

import pytest

from bench_helpers import REPO, run_tiny, tiny_root, tree_hashes  # noqa: F401
from benchmarks.harness import admission as ad
from benchmarks.harness import manifest as mf
from benchmarks.harness import span_tree as st
from benchmarks.harness.runner import load_part

NEW = {
    "backlog_admission_host_ms": ["tiny-backlog"],
    "backlog_queue_scanned_per_admission": ["tiny-backlog"],
    "chat_admission_host_ms": ["tiny-chat"],
}
WINDOW = (10.0, 13.0)


def _x(name, t0, t1, sid, parent=None, cat="serving", **args):
    args = dict(args, id=sid)
    if parent is not None:
        args["parent"] = parent
    return {"name": name, "cat": cat, "ph": "X", "ts": t0 * 1e6,
            "dur": (t1 - t0) * 1e6, "pid": 1, "tid": 1, "args": args}


def _part(name, t0, t1, sid, parent, **args):
    return _x(name, t0, t1, sid, parent, cat="serving.admit", **args)


def _recorded_events(counted=True):
    """Three rounds in the window [10, 13], a fourth straddles its end.
    Round 1 seats two (admit 10.0-10.5): expiry 0.02, pops 0.03 + 0.01,
    seat_prepare 0.05 + 0.04, prefills 0.15 (launched after 0.05) and
    0.10 (launched after 0.02), seat_commit 0.03 + 0.02, so admit's own
    0.05. Round 2 seats nobody (admit 0.04: expiry 0.03, own 0.01). Round
    3 seats one whose prefill is unfenced (admit 0.2: expiry 0.02, pop
    0.02, prepare 0.06, prefill 0.04, commit 0.03, own 0.03).
    `counted=False`: a program from before the counts and the parts."""
    def admit(t0, t1, sid, parent, **counts):
        return _x("admit", t0, t1, sid, parent,
                  **(counts if counted else {}))

    def prefill(t0, t1, sid, parent, launched=None, **args):
        if counted and launched is not None:
            args["launched_s"] = launched
        return _x("prefill", t0, t1, sid, parent, **args)

    ev = [
        _x("round", 10.0, 11.0, 1, attn_impl="xla", admitted=[7, 8]),
        admit(10.0, 10.5, 2, 1, queued=100, scanned=297, admitted=2),
        _x("queued", 9.0, 10.11, 3, 2, request=7),  # began before: no child
        prefill(10.11, 10.26, 4, 2, launched=0.05, request=7, fenced=True),
        prefill(10.34, 10.44, 5, 2, launched=0.02, request=8, fenced=True),
        _x("round", 11.0, 12.0, 20, attn_impl="xla", admitted=[]),
        admit(11.0, 11.04, 21, 20, queued=98, scanned=98, admitted=0),
        _x("round", 12.0, 12.9, 30, attn_impl="xla", admitted=[9]),
        admit(12.0, 12.2, 31, 30, queued=98, scanned=196, admitted=1),
        prefill(12.1, 12.14, 32, 31, request=9),
        _x("round", 12.95, 13.5, 40, attn_impl="xla", admitted=[10]),
        admit(12.95, 13.2, 41, 40, queued=97, scanned=194, admitted=1),
    ]
    if counted:
        ev += [
            _part("queue_expire", 10.0, 10.02, 50, 2, queued=100, expired=0),
            _part("queue_pop", 10.03, 10.06, 51, 2, queued=100, request=7),
            _part("seat_prepare", 10.06, 10.11, 52, 2, request=7,
                  evicted_blocks=15),
            _part("seat_commit", 10.26, 10.29, 53, 2, request=7),
            _part("queue_pop", 10.29, 10.30, 54, 2, queued=99, request=8),
            _part("seat_prepare", 10.30, 10.34, 55, 2, request=8),
            _part("seat_commit", 10.44, 10.46, 56, 2, request=8),
            _part("queue_expire", 11.0, 11.03, 57, 21, queued=98, expired=0),
            _part("queue_expire", 12.0, 12.02, 58, 31, queued=98, expired=0),
            _part("queue_pop", 12.02, 12.04, 59, 31, queued=98, request=9),
            _part("seat_prepare", 12.04, 12.10, 60, 31, request=9),
            _part("seat_commit", 12.14, 12.17, 61, 31, request=9),
        ]
    return ev


def _spans(counted=True, cat=None):
    return st.spans_of(_recorded_events(counted), cat)


class _Ctx:
    def __init__(self):
        self.record = {"window": WINDOW, "window_s": 3.0, "spans": []}
        self.lines = []

    def out(self, line):
        self.lines.append(line)


def test_breakdown_known_answer():
    b = ad.breakdown(_spans(), WINDOW)
    assert (b["admitted"], b["scanned"], b["evicted"], b["rounds"]) == \
        (3, 591, 15, 3)
    assert b["total_s"] == pytest.approx(0.5 + 0.04 + 0.2)
    want = {"queue_expire": 0.02 + 0.03 + 0.02,
            "queue_pop": 0.03 + 0.01 + 0.02,
            "seat_prepare": 0.05 + 0.04 + 0.06,
            "prefill launch": 0.05 + 0.02 + 0.04,   # the unfenced: all of it
            "prefill wait": 0.10 + 0.08,
            "seat_commit": 0.03 + 0.02 + 0.03}
    for name, s in want.items():
        assert b["parts"][name] == pytest.approx(s), name
    assert list(b["parts"]) == list(ad.PARTS) + ["admit's own"]
    assert b["parts"]["admit's own"] == pytest.approx(
        0.74 - sum(want.values()))
    assert sum(b["parts"].values()) == pytest.approx(b["total_s"])
    assert b["host_s"] == pytest.approx(0.74 - 0.18)


def test_host_ms_known_answer_and_its_line():
    ctx = _Ctx()
    assert ad.host_ms(ctx, _spans()) == pytest.approx(1e3 * 0.56 / 3)
    line, each = ctx.lines
    assert each == (
        "admission: p50 ms (longest) of one: queue_expire 20.000 (30.000), "
        "queue_pop 20.000 (30.000), seat_prepare 50.000 (60.000), prefill "
        "launch 40.000 (50.000), prefill wait 80.000 (100.000), seat_commit "
        "30.000 (30.000); evicted blocks an admission: 5.00")
    assert line.startswith("admission: 3 admitted in 3 rounds; ms an "
                           "admission: queue_expire 23.333, queue_pop "
                           "20.000, seat_prepare 50.000, prefill launch "
                           "36.667, prefill wait 60.000, seat_commit "
                           "26.667, admit's own 30.000 = ")
    assert line.endswith(" = 246.667 (host, all but the wait: 186.667); "
                         "ms a round: 246.667")
    # the parts of the line add up to its total
    parts = line.split("ms an admission: ")[1].split(" = ")[0]
    assert sum(float(p.rsplit(" ", 1)[1]) for p in parts.split(", ")) == \
        pytest.approx(246.667, abs=0.01)


def test_scanned_per_admission_known_answer():
    assert ad.scanned_per_admission(_spans(), WINDOW) == \
        pytest.approx(591 / 3)


def test_a_fenced_prefill_that_does_not_say_where_its_launch_ended():
    """All of it is wait: the host's share cannot be overstated."""
    spans = _spans()
    for s in spans:
        s["args"].pop("launched_s", None)
    b = ad.breakdown(spans, WINDOW)
    assert b["parts"]["prefill wait"] == pytest.approx(0.25)
    assert b["parts"]["prefill launch"] == pytest.approx(0.04)


def test_nothing_admitted_in_the_window_reads_none():
    spans = [s for s in _spans() if s["t0"] >= 11.0]
    assert ad.host_ms(_Ctx(), spans) == pytest.approx(1e3 * 0.24)
    only_idle = [s for s in _spans() if 11.0 <= s["t0"] < 12.0]
    ctx = _Ctx()
    assert ad.host_ms(ctx, only_idle) is None and ctx.lines == []
    assert ad.scanned_per_admission(only_idle, WINDOW) is None


def test_a_program_from_before_the_counts_reads_none():
    """The parent commit's spans: `admit` carries no `admitted`, there are
    no parts: nothing to read, and no error."""
    ctx = _Ctx()
    old = _spans(counted=False)
    assert ad.window_admits(old, WINDOW) == []
    assert ad.breakdown(old, WINDOW) is None
    assert ad.host_ms(ctx, old) is None and ctx.lines == []
    assert ad.scanned_per_admission(old, WINDOW) is None


def test_the_serving_readers_do_not_see_the_parts():
    """The category is what keeps them reading what they read."""
    serving = _spans(cat="serving")
    assert not [s for s in serving if s["cat"] != "serving"]
    assert st.round_host_share(serving, WINDOW) == pytest.approx(
        st.round_host_share([s for s in _spans()
                             if s["cat"] == "serving"], WINDOW))
    assert st.round_host_share(_spans(), WINDOW) < \
        st.round_host_share(serving, WINDOW)


@pytest.mark.parametrize("name", sorted(NEW))
def test_every_reader_returns_none_without_the_tracer(name):
    """`--trace 0`: nothing to read, and no error."""
    from bigdl_tpu import obs

    obs.set_tracer(None)
    assert load_part(REPO, "layer_metrics", name).read(_Ctx()) is None


def test_the_manifest_names_the_readers():
    manifest = mf.load(REPO)
    assert mf.problems(manifest, REPO) == []
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    backlog = [c["name"] for c in manifest["workloads"]
               if c["name"].endswith("-serve-backlog")]
    for name in NEW:
        m = by_name[name]
        assert m["layer"] == by_name["backlog_round_host_share"]["layer"]
        assert (m["source"], m["better"]) == ("program_span", "lower")
        assert os.path.isfile(os.path.join(
            REPO, "benchmarks", "layer_metrics", f"{name}.py"))
        if name.startswith("backlog_"):
            assert (m["moves"], m["workloads"]) == ("serve_throughput",
                                                    backlog)
        else:
            assert (m["moves"], m["workloads"]) == ("serve_tpot_mean",
                                                    ["gpt2m-serve-chat"])
    # appended, in this order, after everything the manifest had
    assert [m["name"] for m in manifest["per_layer"]][-3:] == [
        "backlog_admission_host_ms", "chat_admission_host_ms",
        "backlog_queue_scanned_per_admission"]


@pytest.fixture(scope="module")
def admission_root(tiny_root):
    """The toy root with the three readers named by its manifest."""
    root, before = tiny_root
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    real = {m["name"]: m for m in mf.load(REPO)["per_layer"]}
    moves = {"tiny-chat": "serve_tpot_mean",
             "tiny-backlog": "serve_throughput"}
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
def test_serve_rehearsal_reports_the_admission_metrics(admission_root,
                                                       workload):
    result, lines = run_tiny(admission_root, workload, seed=2 ** 31 + 40,
                             trace=True)
    assert result["correct"] is True
    got = result["metrics"]
    prefix = workload.split("-")[1]
    assert got[f"{prefix}_admission_host_ms"]["value"] > 0
    line, each = [l for l in lines if l.startswith("admission: ")]
    assert each.startswith("admission: p50 ms (longest) of one: queue_expire")
    parts, rest = line.split("ms an admission: ")[1].split(" = ")
    total = float(rest.split(" ")[0])
    assert sum(float(p.rsplit(" ", 1)[1]) for p in parts.split(", ")) == \
        pytest.approx(total, rel=0.02)
    assert [p.rsplit(" ", 1)[0] for p in parts.split(", ")] == \
        list(ad.PARTS) + ["admit's own"]
    # the host's share is the total less the wait
    host = float(rest.split("wait: ")[1].split(")")[0])
    assert host == pytest.approx(
        got[f"{prefix}_admission_host_ms"]["value"], abs=1e-3)
    assert 0 < host <= total
    if workload == "tiny-backlog":
        # the whole backlog is queued before the window: every admission
        # walks what is left of it twice, the expiry and the pop
        assert got["backlog_queue_scanned_per_admission"]["value"] >= 2
        assert "chat_admission_host_ms" not in got
    else:
        assert "backlog_queue_scanned_per_admission" not in got
        assert "backlog_admission_host_ms" not in got


def test_untraced_rehearsal_has_no_admission_metric(admission_root):
    from bigdl_tpu import obs

    obs.set_tracer(None)        # a run is a process: no tracer left over
    result, lines = run_tiny(admission_root, "tiny-backlog",
                             seed=2 ** 31 + 41)
    assert not set(result["metrics"]) & set(NEW)
    assert not [l for l in lines if l.startswith("admission: ")]
