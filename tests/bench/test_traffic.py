"""The one generator of serving traffic: every seed gets the same work in
another order."""

import types

import numpy as np
import pytest

from bench_helpers import REPO
from benchmarks.harness import manifest as mf
from benchmarks.harness import traffic as tg
from benchmarks.harness.runner import load_part
from benchmarks.harness.stats import percentile, quartile_spread

MANIFEST = mf.load(REPO)
BACKLOG_CELLS = [c["name"] for c in MANIFEST["workloads"] if mf.load_json(
    REPO, mf.traffic_path(c)).get("arrival") == "backlog"]

MIX = {"arrival": "stratified_exponential", "rate_per_s": 5.0, "lead_s": 2.0, "tail_s": 3.0,
       "prompt_len": {"median": 192, "sigma": 0.7, "min": 32, "max": 768},
       "output_len": {"median": 48, "sigma": 0.75, "min": 16, "max": 192},
       "greedy_every": 2,
       "sampling": {"temperature": 0.8, "top_k": 40, "top_p": 0.95}}


def test_same_seed_same_traffic_other_seed_same_sizes():
    a = tg.make_arrivals(MIX, 2 ** 31 + 5, 10.0, 50257)
    b = tg.make_arrivals(MIX, 2 ** 31 + 5, 10.0, 50257)
    c = tg.make_arrivals(MIX, 9, 10.0, 50257)
    assert a == b and a != c
    for pick in (lambda x: len(x.prompt), lambda x: x.max_new_tokens):
        assert sorted(map(pick, a)) == sorted(map(pick, c))
    gaps = [np.diff([-MIX["lead_s"]] + [x.due_s for x in t])
            for t in (a, c)]
    assert np.allclose(sorted(gaps[0]), sorted(gaps[1]))
    # and block by block: any window holds the same work under every seed
    for lo in range(0, len(a), tg.BLOCK):
        blk = slice(lo, lo + tg.BLOCK)
        assert sorted(len(x.prompt) for x in a[blk]) == sorted(
            len(x.prompt) for x in c[blk])
        assert sorted(x.max_new_tokens for x in a[blk]) == sorted(
            x.max_new_tokens for x in c[blk])
    assert a[-1].due_s == pytest.approx(c[-1].due_s)


def test_sizes_follow_the_file():
    a = tg.make_arrivals(MIX, 1, 10.0, 50257)
    assert len(a) == tg.n_requests(MIX, 10.0) == 96     # 75 -> whole blocks
    prompts = [len(x.prompt) for x in a]
    assert min(prompts) >= 32 and max(prompts) <= 768
    assert abs(np.median(prompts) - 192) <= 8
    assert all(len(x.prompt) + x.max_new_tokens <= 1024 for x in a)
    assert a[0].due_s >= -2.0
    # a block of 32 arrivals always spans 32 / rate seconds
    assert a[31].due_s == pytest.approx(-2.0 + 32 / 5.0)
    assert a[-1].due_s == pytest.approx(-2.0 + 96 / 5.0)
    greedy = sum(x.temperature == 0.0 for x in a)
    assert abs(greedy - len(a) / 2) <= 1
    assert all(0 <= t < 50257 for x in a for t in x.prompt)


def test_backlog_is_queued_before_the_window():
    mix = dict(MIX, arrival="backlog", backlog_requests_per_window_s=3)
    a = tg.make_arrivals(mix, 1, 4.0, 100)
    assert len(a) == 32 and all(x.due_s == -float("inf") for x in a)


def _answer_tokens(cell):
    """(requests, answer tokens) the cell's backlog holds at the manifest's
    `run_seconds`: the same under every seed."""
    mix = mf.load_json(REPO, mf.traffic_path(mf.cell_of(MANIFEST, cell)))
    a = tg.make_arrivals(mix, 2 ** 31 + 36, MANIFEST["run_seconds"], 50257)
    return len(a), sum(x.max_new_tokens for x in a)


def test_chat_backlog_is_2464_requests_deep():
    """48 requests a second of window: 77 blocks of 32, each block 1,965
    answer tokens. Less the lead's 64 x 64 and the 2,850 or so seated at
    the close, over 51 s: dry above 2,830 tokens/s (benchmarks/README.md)."""
    assert _answer_tokens("gpt2m-serve-backlog") == (2464, 151305)


# answer tokens a second of window that a backlog holds at the least; a
# cell that a later PR adds is held to the general floor
FLOORS = {"joyai-flash-serve-backlog": 5000,
          "trinity-mini-serve-backlog": 5000}


@pytest.mark.parametrize("cell", BACKLOG_CELLS)
def test_no_backlog_is_thinner_than_2800_answer_tokens_a_second(cell):
    """A later edit cannot thin a backlog unseen: a cell whose queue can be
    emptied inside the window refuses the PR that makes the program fast
    enough to do it (`backlog_never_dry`). The expert cells serve 900 to
    1,000 tokens/s and hold over 5,000."""
    per_s = _answer_tokens(cell)[1] / MANIFEST["run_seconds"]
    assert per_s >= FLOORS.get(cell, 2800)


def test_every_backlog_cell_reports_what_it_has_left():
    metric = next(m for m in MANIFEST["per_layer"]
                  if m["name"] == "backlog_queue_left")
    assert set(metric["workloads"]) == set(BACKLOG_CELLS) >= set(FLOORS)


@pytest.mark.parametrize("arrival,record,expected", [
    ("backlog", {"counters": {"queued_at_close": 1280,
                              "requests_submitted": 2464}}, 51.948),
    ("backlog", {"counters": {"queued_at_close": 64,
                              "requests_submitted": 1248}}, 5.128),
    # a chat cell, a train cell, a run whose record lacks the counter
    ("stratified_exponential", {"counters": {
        "queued_at_close": 3, "requests_submitted": 224}}, None),
    (None, {"counters": {"steps": 230}}, None),
    ("backlog", {"counters": {"requests_submitted": 2464}}, None),
    ("backlog", {}, None)])
def test_backlog_queue_left_reads_the_drivers_counters(arrival, record,
                                                       expected):
    ctx = types.SimpleNamespace(
        traffic={} if arrival is None else {"arrival": arrival},
        record=record)
    value = load_part(REPO, "layer_metrics", "backlog_queue_left").read(ctx)
    assert value == (None if expected is None
                     else pytest.approx(expected, abs=1e-3))


def test_statistics():
    assert percentile([5, 1, 3, 2, 4], 0.9) == 5
    assert percentile(list(range(1, 101)), 0.9) == 90
    assert percentile([], 0.5) is None
    assert quartile_spread([10, 10, 10, 10, 10, 10]) == 0
    assert quartile_spread([9, 10, 10, 10, 10, 11]) == pytest.approx(0.05)


def test_one_variable_only_can_be_set_from_a_traffic_file(monkeypatch):
    import os

    from benchmarks.harness import phases

    monkeypatch.setenv(phases.PREMAPPED_VAR, "0")   # restored afterwards
    monkeypatch.delenv(phases.PREMAPPED_VAR)
    mix = {"tpu_premapped_buffer_mib": None,
           "process_env": {"XLA_FLAGS_FROM_DATA": "1"}}
    assert phases.set_process_env(mix) == {phases.PREMAPPED_VAR: None}
    assert "XLA_FLAGS_FROM_DATA" not in os.environ
    assert phases.set_process_env({}) == {
        phases.PREMAPPED_VAR: str(64 * 2 ** 20)}
    monkeypatch.setenv(phases.PREMAPPED_VAR, "123")     # the environment's
    assert phases.set_process_env({"tpu_premapped_buffer_mib": 512}) == {
        phases.PREMAPPED_VAR: "123"}
