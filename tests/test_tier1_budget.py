"""scripts/check_tier1_budget.py — pure text parsing + threshold
logic, so this runs in milliseconds (the actual budget check against a
real run is a standalone invocation; see CLAUDE.md)."""

import importlib.util
import os

import pytest

_SYNTHETIC = """\
============================= slowest durations ==============================
120.50s call     tests/test_models.py::test_resnet
  0.30s setup    tests/test_models.py::test_resnet
 45.25s call     tests/test_serving.py::TestEngine::test_matches_run_alone
  0.05s teardown tests/test_serving.py::TestEngine::test_matches_run_alone
not a duration line
12 passed in 166.2s
"""


def _load():
    path = os.path.join(os.path.dirname(__file__), "..", "scripts",
                        "check_tier1_budget.py")
    spec = importlib.util.spec_from_file_location("check_tier1_budget",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod

def test_parse_and_projection():
    m = _load()
    entries = m.parse_durations(_SYNTHETIC)
    assert len(entries) == 4
    assert entries[0] == (120.5, "call", "tests/test_models.py::test_resnet")
    # approx: the script's sum() is compensated on Python >= 3.12, a
    # left-to-right + chain is not — they differ in the last bit
    assert m.projected_runtime_s(entries, overhead_s=40.0) == \
        pytest.approx(40.0 + 120.5 + 0.3 + 45.25 + 0.05)
    top = m.slowest_tests(entries, top=1)
    assert top == [(120.8, "tests/test_models.py::test_resnet")]


def test_main_verdicts(tmp_path, capsys):
    m = _load()
    log = tmp_path / "t1.log"
    log.write_text(_SYNTHETIC)
    assert m.main(["--log", str(log), "--budget", "500"]) == 0
    assert m.main(["--log", str(log), "--budget", "100"]) == 1
    out = capsys.readouterr().out
    assert "OVER BUDGET" in out and "test_resnet" in out
    log.write_text("no durations here\n")
    assert m.main(["--log", str(log)]) == 2
    assert m.main(["--log", str(tmp_path / "missing.log")]) == 2


def _scaled_log(factor):
    """_SYNTHETIC with every duration multiplied by `factor`."""
    out = []
    for line in _SYNTHETIC.splitlines():
        e = _load().parse_durations(line)
        if e:
            secs, phase, test = e[0]
            out.append(f"{secs * factor:.2f}s {phase}     {test}")
        else:
            out.append(line)
    return "\n".join(out) + "\n"


def test_telemetry_delta(tmp_path, capsys):
    """ISSUE 5 satellite: the budget guard also fails when the
    telemetry-on suite adds >max-delta-pct over the BIGDL_OBS=off
    baseline durations."""
    m = _load()
    on, off = tmp_path / "on.log", tmp_path / "off.log"
    off.write_text(_SYNTHETIC)
    # +1% — within the 2% default limit
    on.write_text(_scaled_log(1.01))
    assert m.main(["--log", str(on), "--baseline-log", str(off),
                   "--budget", "500"]) == 0
    # +5% — over the limit (runtime budget itself still fine)
    on.write_text(_scaled_log(1.05))
    assert m.main(["--log", str(on), "--baseline-log", str(off),
                   "--budget", "500"]) == 1
    out = capsys.readouterr().out
    assert "OVER LIMIT" in out
    # a tighter explicit limit flips the verdict the other way too
    on.write_text(_scaled_log(1.01))
    assert m.main(["--log", str(on), "--baseline-log", str(off),
                   "--budget", "500", "--max-delta-pct", "0.5"]) == 1
    # unreadable/empty baseline is a usage error, not a pass
    assert m.main(["--log", str(on), "--baseline-log",
                   str(tmp_path / "missing.log"),
                   "--budget", "500"]) == 2
    off.write_text("nothing recorded\n")
    assert m.main(["--log", str(on), "--baseline-log", str(off),
                   "--budget", "500"]) == 2
    # pure function: delta math
    a = m.parse_durations(_SYNTHETIC)
    assert m.telemetry_delta_pct(a, a) == 0.0
