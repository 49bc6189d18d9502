"""The reduction from a profiler trace to numbers, on a synthetic trace with
known answers and on the small traces recorded on a v5e
(benchmarks/testdata/, made by benchmarks/tools/record_trace.py)."""

import json
import os

import numpy as np
import pytest

from benchmarks.harness import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmarks", "testdata")

NAMES = {
    1: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
    2: "%all-reduce.2 = f32[8]{0} all-reduce(f32[8]{0} %x), replica_groups={}",
    3: '%k.3 = bf16[8,128]{1,0:T(8,128)(2,1)S(1)} custom-call(bf16[8,128]{1,0} %q), custom_call_target="tpu_custom_call"',
    4: "%while.4 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t), condition=%c, body=%b",
    5: "%all-reduce-start.5 = f32[8]{0} all-reduce-start(f32[8]{0} %x)",
    6: "bench_trace_begin", 7: "bench_trace_end",
    8: "jit_step(123)",
}


def _plane(name, lines):
    meta = "".join(
        f'event_metadata {{ key: {k} value {{ id: {k} name: "'
        + v.replace('"', '\\"') + '" } }\n' for k, v in NAMES.items())
    body = ""
    for i, (line, events) in enumerate(lines.items()):
        evs = "".join(
            f"events {{ metadata_id: {m} offset_ps: {int(a * 1000)} "
            f"duration_ps: {int((b - a) * 1000)} }}\n" for m, a, b in events)
        body += f'lines {{ id: {i} name: "{line}" timestamp_ns: 0\n{evs}}}\n'
    return f'planes {{ name: "{name}"\n{body}{meta}}}\n'


def _profile(text):
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(text)


@pytest.fixture(scope="module")
def synthetic():
    """Window [1000, 11000] ns. Device 0: a while op 1000-9000 that only
    contains others; fusion 1000-3000; kernel 3000-5000; all-reduce
    5000-6000 with nothing over it (exposed); fusion 8000-9000 under an
    async all-reduce 7500-9500 (1000 hidden, 1000 exposed). One whole
    execution of jit_step 1000-9000. Device 1: fusion 2000-4000 only, and
    an execution cut by the window's end. Host: the two markers."""
    dev0 = _plane("/device:TPU:0", {
        "XLA Modules": [(8, 1000, 9000)],
        "XLA Ops": [(4, 1000, 9000), (1, 1000, 3000), (3, 3000, 5000),
                    (2, 5000, 6000), (1, 8000, 9000)],
        "Async XLA Ops": [(5, 7500, 9500)]})
    dev1 = _plane("/device:TPU:1", {
        "XLA Modules": [(8, 10000, 12000)],
        "XLA Ops": [(1, 2000, 4000), (1, 500, 900)]})
    host = _plane("/host:CPU", {"python3": [(6, 1000, 1010),
                                            (7, 11000, 11010)]})
    return _profile(dev0 + dev1 + host)


def test_interval_arithmetic():
    assert tr.union([(3, 5), (0, 2), (1, 4)]) == [(0, 5)]
    assert tr.length(tr.union([(0, 1), (2, 3)])) == 2
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5),
                                                          (7, 10)]
    assert tr.gaps([(2, 3)], 0, 5) == [(0, 2), (3, 5)]
    assert tr.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]


def test_the_sweep_gives_each_gap_what_the_plain_loop_gave_it():
    """The loop that asked every span about every gap stays here as the
    reference: spans that nest, overlap without nesting, begin long before
    (a request's), tie in length, touch a point with an end, or miss."""
    rng = np.random.RandomState(36)
    starts = rng.uniform(0, 1000, 400)
    spans = [(f"s{i % 7}", float(a), float(a + d)) for i, (a, d) in
             enumerate(zip(starts, rng.choice([1, 5, 5, 40, 300], 400)))]
    spans += [("tie_b", 10.0, 20.0), ("tie_a", 10.0, 20.0),
              ("request", -500.0, 600.0)]
    points = sorted(rng.uniform(-20, 1400, 900).tolist() + [10.0, 20.0])

    def plain(at):
        cover = [(e - s, name) for name, s, e in spans if s <= at <= e]
        return min(cover)[1] if cover else "unattributed"

    got = tr.shortest_span_over(points, spans)
    assert got == [plain(at) for at in points]
    assert {"unattributed", "request", "tie_a"} <= set(got)
    assert tr.shortest_span_over([1.0, 2.0], []) == ["unattributed"] * 2
    assert tr.shortest_span_over([], spans) == []


def test_labels():
    assert tr.op_label(NAMES[1]) == "fusion.1:fusion"
    assert tr.op_label(NAMES[3]) == "k.3:custom-call:tpu_custom_call"
    assert tr.is_custom_call(tr.op_label(NAMES[3]))
    assert tr.op_label(NAMES[4]).endswith(tr.CONTAINERS)
    assert tr.COLLECTIVE.search(tr.op_label(NAMES[5]))


def test_synthetic_trace_reduces_to_known_numbers(synthetic):
    # host spans on a host clock where the begin marker read 50.0 s
    spans = [("step", 50.0, 50.0 + 6e-6), ("inner", 50.0 + 5.9e-6,
                                           50.0 + 7.2e-6),
             ("sleep", 50.0 + 8e-6, 50.0 + 10e-6)]
    r = tr.reduce_profile(synthetic, spans, begin_host=50.0)
    ns = 1e-9
    assert r["devices"] == 2
    assert r["window_s"] == pytest.approx(10000 * ns)
    # busy: device 0 the while op's 8000 ns, device 1 2000 ns
    assert r["busy_s"] == pytest.approx((8000 + 2000) / 2 * ns)
    assert r["custom_call_s"] == pytest.approx(2000 / 2 * ns)
    # collectives on device 0: 5000-6000 and 7500-9500 -> 3000 in flight,
    # of which 8000-9000 lies under a fusion: 2000 exposed
    assert r["collective_s"] == pytest.approx(3000 / 2 * ns)
    assert r["collective_exposed_s"] == pytest.approx(2000 / 2 * ns)
    ops = dict(r["device_ops"])
    assert "while.4:while" not in ops           # a container, not work
    assert ops["fusion.1:fusion"] == pytest.approx((3000 + 2000) / 2 * ns)
    assert r["main_module"] == {
        "name": "jit_step", "runs": 0.5, "time_s": pytest.approx(4000 * ns),
        "custom_call_s": pytest.approx(1000 * ns)}
    # device 0 is idle 9000-11000: its midpoint lies in "sleep"
    assert dict(r["idle_gaps"]) == {"sleep": pytest.approx(2000 * ns)}


def test_a_trace_without_device_planes_reports_none(synthetic):
    host_only = _profile(_plane("/host:CPU", {"python3": [(6, 0, 10)]}))
    assert tr.reduce_profile(host_only)["devices"] == 0


@pytest.mark.parametrize("name", sorted(
    f[:-len(".xplane.pb")] for f in os.listdir(DATA)
    if f.endswith(".xplane.pb")))
def test_recorded_trace(name):
    """A real v5e trace: the markers are found, the flash-attention kernels
    are seen as tpu_custom_call, busy time fits in the window, and the
    sleeps between steps take most of the idle time."""
    with open(os.path.join(DATA, f"{name}.json")) as f:
        meta = json.load(f)
    r = tr.reduce_file(os.path.join(DATA, f"{name}.xplane.pb"),
                       [tuple(s) for s in meta["spans"]],
                       meta["begin_host"])
    assert r["devices"] == meta["devices"]
    assert r["window_s"] == pytest.approx(
        meta["end_host"] - meta["begin_host"], rel=0.02)
    assert 0 < r["busy_s"] < r["window_s"]
    assert 0 < r["custom_call_s"] < r["busy_s"]
    assert any(tr.is_custom_call(k) for k, _ in r["device_ops"])
    assert r["idle_gaps"][0][0] == "fixture_sleep"
    if meta["devices"] > 1:
        assert 0 < r["collective_exposed_s"] <= r["collective_s"]
    else:
        assert r["collective_s"] == 0
