"""Perf-regression sentinel (ISSUE 11): pure-parse guard over a
multi-round BENCH_r0*.json trajectory — the check_tier1_budget.py-style
CI usage. The history must gate clean at the recorded spreads (the
documented ~25% host variance never pages), a synthetic 2x slowdown
must flag with a nonzero exit, and the --format json verdict must be
machine-readable.

The history is built in a temp dir: rounds 1-4 from the rows below
(the July driver artifacts' values — fixture data from another stack,
not a measurement of this one), round 5 is the committed BENCH_r05.json
verbatim."""

import importlib.util
import json
import os
import shutil

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _load():
    path = os.path.join(ROOT, "scripts", "bench_compare.py")
    spec = importlib.util.spec_from_file_location("bench_compare", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def bc():
    return _load()


def _row(name, unit, value, step_ms=None):
    row = {"metric": f"{name}_per_sec_per_chip[tpu]", "value": value,
           "unit": f"{unit}/sec"}
    if step_ms is not None:
        row["step_ms"] = step_ms
    return row


_RESNET = "resnet50_bf16_train_images"
_EARLIER_ROUNDS = {
    1: [_row(_RESNET, "images", 2262.18)],
    2: [_row(_RESNET, "images", 2265.25)],
    3: [_row(_RESNET, "images", 2230.76, 114.76),
        _row("inception_v1_bf16_train_images", "images", 4217.02, 60.71),
        _row("vgg16_bf16_train_images", "images", 1350.97, 94.75),
        _row("bilstm_sst_train_samples", "samples", 11147.28, 11.48),
        _row("transformer_lm_43m_train_tokens", "tokens", 160375.71,
             102.16),
        _row("transformer_lm_186m_train_tokens", "tokens", 49714.72,
             329.56)],
    4: [_row(_RESNET, "images", 2542.22, 100.7),
        _row("resnet50_bf16_train_diskpipe_images", "images", 31.91,
             8022.97),
        _row("inception_v1_bf16_train_images", "images", 4240.33, 60.37),
        _row("vgg16_bf16_train_images", "images", 1309.32, 97.76),
        _row("bilstm_sst_train_samples", "samples", 15050.47, 8.5),
        _row("transformer_lm_43m_train_tokens", "tokens", 196146.75,
             83.53),
        _row("transformer_lm_186m_train_tokens", "tokens", 59270.82,
             276.43)],
}


@pytest.fixture(scope="module")
def history_glob(tmp_path_factory):
    d = tmp_path_factory.mktemp("bench_history")
    for n, rows in _EARLIER_ROUNDS.items():
        (d / f"BENCH_r{n:02d}.json").write_text(json.dumps({
            "n": n, "cmd": "python bench.py", "rc": 0,
            "tail": "\n".join(json.dumps(r) for r in rows) + "\n"}))
    shutil.copy(os.path.join(ROOT, "BENCH_r05.json"), d)
    return str(d / "BENCH_r*.json")


@pytest.fixture(scope="module")
def history(bc, history_glob):
    return bc.load_history(history_glob)


# --------------------------------------------------------------- parsing

def test_rows_from_text_skips_noise(bc):
    text = ("WARNING: some log line\n"
            '{"metric": "m_a", "value": 10.0, "unit": "x/s"}\n'
            '{"not_a_metric": 1}\n'
            "{torn json\n"
            '{"metric": "m_b", "value": 2.5, "unit": "x/s", '
            '"step_ms": 4.0, "step_ms_spread": [3.0, 5.0]}\n')
    rows = bc.rows_from_text(text)
    assert set(rows) == {"m_a", "m_b"}
    assert rows["m_b"]["step_ms_spread"] == [3.0, 5.0]


def test_load_rows_list_rejects_nonnumeric_values(bc, tmp_path,
                                                  history_glob):
    """A JSON-list candidate applies the same numeric-value admission
    as rows_from_text — garbage rows route to exit 2, not a TypeError
    inside compare()."""
    p = tmp_path / "rows.json"
    p.write_text(json.dumps([
        {"metric": "m_ok", "value": 3.0},
        {"metric": "m_null", "value": None},
        {"metric": "m_missing"},
        "not a row"]))
    assert set(bc.load_rows(str(p))) == {"m_ok"}
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps([{"metric": "m_null", "value": None}]))
    assert bc.main(["--fresh", str(bad),
                    "--history", history_glob]) == 2


def test_committed_history_loads(history):
    """Every driver artifact parses into metric rows."""
    assert len(history) >= 5
    tags = [tag for tag, _ in history]
    assert tags == sorted(tags, key=lambda t: int(t.split("_r")[1]
                                                  .split(".")[0]))
    assert all(rows for _, rows in history)


def test_spread_frac(bc):
    assert bc.spread_frac({"step_ms_spread": [3.0, 5.0],
                           "step_ms": 4.0}) == pytest.approx(0.25)
    assert bc.spread_frac({"step_ms": 4.0}) is None
    assert bc.spread_frac({"step_ms_spread": [3.0, 5.0]}) is None


# ------------------------------------------------------------ comparison

def test_committed_trajectory_gates_clean(bc, history):
    """THE acceptance pin: the newest committed round against the
    earlier ones flags NO regression at the recorded spreads — the
    r04->r05 BiLSTM dip (-8%, inside its recorded 46%-wide spread)
    must not page."""
    fresh_tag, fresh = history[-1]
    verdict = bc.compare(history[:-1], fresh)
    assert verdict["ok"], verdict["regressions"]
    assert verdict["checked"] >= 5
    bilstm = [r for r in verdict["rows"]
              if r["metric"].startswith("bilstm")]
    if bilstm:     # the noisy row widened its own tolerance
        assert bilstm[0]["threshold_frac"] > 0.25


def test_synthetic_2x_slowdown_flags(bc, history):
    """Halving a stable metric's throughput must flag it (and only
    it) as a regression."""
    fresh_tag, fresh = history[-1]
    target = "inception_v1_bf16_train_images_per_sec_per_chip[tpu]"
    assert target in fresh
    slowed = {m: dict(r) for m, r in fresh.items()}
    slowed[target]["value"] = fresh[target]["value"] / 2.0
    verdict = bc.compare(history[:-1], slowed)
    assert not verdict["ok"]
    assert [r["metric"] for r in verdict["regressions"]] == [target]
    reg = verdict["regressions"][0]
    assert reg["shortfall_frac"] == pytest.approx(0.5, abs=0.02)
    assert reg["threshold_frac"] < reg["shortfall_frac"]


def test_noise_widens_threshold_but_2x_still_flags(bc):
    """A row publishing a wide median-of-5 spread gets a wider
    tolerance — a dip inside it passes, a 2x slowdown still flags."""
    hist = [("r1", {"m": {"metric": "m", "value": 100.0,
                          "step_ms": 10.0, "step_ms_median_of": 5,
                          "step_ms_spread": [8.0, 12.0]}})]
    dip = {"m": {"metric": "m", "value": 70.0, "step_ms": 14.0}}
    v = bc.compare(hist, dip)
    assert v["ok"]                         # -30% < 1.5 * 20% spread
    halved = {"m": {"metric": "m", "value": 50.0, "step_ms": 20.0}}
    v2 = bc.compare(hist, halved)
    assert not v2["ok"]


def test_lmdecode_spec_row_parses_and_gates(bc):
    """ISSUE 15: the sentinel picks the new speculative-decoding row
    up — a bench line shaped like bench_lm_decode_spec's output parses
    into a metric row (extra provenance fields preserved), a
    within-tolerance wobble passes, and a 2x goodput collapse (e.g. a
    broken draft pinning accept_rate to 0) flags exactly that row."""
    spec_metric = ("transformer_lm_43m_decode_spec_goodput"
                   "_tokens_per_sec[cpu]")
    line = json.dumps({
        "metric": spec_metric, "value": 120.0, "unit": "tokens/sec",
        "vs_baseline": None, "target_only_tokens_per_sec": 60.0,
        "speedup_vs_target_only": 2.0, "k": 4, "accept_rate": 0.7,
        "tokens_bit_identical_to_target_only": True})
    rows = bc.rows_from_text("some warmup noise\n" + line + "\n")
    assert spec_metric in rows
    assert rows[spec_metric]["accept_rate"] == 0.7
    hist = [("r1", rows)]
    wobble = {spec_metric: {"metric": spec_metric, "value": 100.0}}
    assert bc.compare(hist, wobble)["ok"]      # -17% < the 25% floor
    collapsed = {spec_metric: {"metric": spec_metric, "value": 60.0}}
    verdict = bc.compare(hist, collapsed)
    assert not verdict["ok"]
    assert [r["metric"] for r in verdict["regressions"]] \
        == [spec_metric]


def test_lmdecode_spill_row_parses_and_gates(bc):
    """ISSUE 16: the sentinel picks the spill-tier row up — a bench
    line shaped like bench_lm_decode_spill's output parses into a
    metric row (tier provenance preserved), a within-tolerance wobble
    passes, and a 2x goodput collapse (e.g. re-admission silently
    falling back to re-prefill) flags exactly that row."""
    spill_metric = ("transformer_lm_43m_decode_spill_goodput"
                    "_tokens_per_sec[cpu]")
    line = json.dumps({
        "metric": spill_metric, "value": 90.0, "unit": "tokens/sec",
        "vs_baseline": None, "cold_cache_tokens_per_sec": 55.0,
        "speedup_vs_cold": 1.64, "spilled_blocks": 84,
        "readmitted_blocks": 30, "host_evictions": 0,
        "host_blocks_in_use": 61,
        "tokens_bit_identical_to_cold": True})
    rows = bc.rows_from_text("some warmup noise\n" + line + "\n")
    assert spill_metric in rows
    assert rows[spill_metric]["readmitted_blocks"] == 30
    hist = [("r1", rows)]
    wobble = {spill_metric: {"metric": spill_metric, "value": 75.0}}
    assert bc.compare(hist, wobble)["ok"]      # -17% < the 25% floor
    collapsed = {spill_metric: {"metric": spill_metric, "value": 45.0}}
    verdict = bc.compare(hist, collapsed)
    assert not verdict["ok"]
    assert [r["metric"] for r in verdict["regressions"]] \
        == [spill_metric]


# ----------------------------------------------------------------- CLI

def test_cli_fresh_latest_exits_zero(bc, capsys, history_glob):
    assert bc.main(["--fresh-latest", "--history", history_glob]) == 0
    out = capsys.readouterr().out
    assert "OK" in out and "metrics checked" in out


def test_cli_json_verdict_and_regression_exit(bc, tmp_path, capsys,
                                              history_glob):
    """--format json is machine-readable; a candidate file with a 2x
    slowdown exits 1 and names the metric in the verdict."""
    hist = bc.load_history(history_glob)
    _, latest = hist[-1]
    target = "transformer_lm_43m_train_tokens_per_sec_per_chip[tpu]"
    rows = [dict(r) for r in latest.values()]
    for r in rows:
        if r["metric"] == target:
            r["value"] = r["value"] / 2.0
    fresh = tmp_path / "fresh.jsonl"
    fresh.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    rc = bc.main(["--fresh", str(fresh), "--format", "json",
                  "--history", history_glob])
    assert rc == 1
    verdict = json.loads(capsys.readouterr().out)
    assert verdict["ok"] is False
    assert [r["metric"] for r in verdict["regressions"]] == [target]
    assert verdict["candidate"] == "fresh.jsonl"


def test_cli_usage_errors_exit_two(bc, tmp_path, capsys, history_glob):
    assert bc.main([]) == 2                        # no candidate
    assert bc.main(["--fresh-latest",
                    "--history",
                    str(tmp_path / "none_*.json")]) == 2
    empty = tmp_path / "empty.jsonl"
    empty.write_text("no rows here\n")
    assert bc.main(["--fresh", str(empty),
                    "--history", history_glob]) == 2
    assert bc.main(["--fresh", str(tmp_path / "missing.jsonl")]) == 2
    capsys.readouterr()
