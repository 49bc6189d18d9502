"""BENCHMARK.json against the contract, as far as files can show it."""

import json
import os

import pytest

from benchmarks.harness import manifest as mf

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TINY = os.path.join(REPO, "tests", "bench", "tiny")


@pytest.fixture(scope="module")
def manifest():
    return mf.load(REPO)


def test_manifest_meets_the_contract(manifest):
    assert mf.problems(manifest, REPO) == []


def test_the_checker_finds_faults(manifest):
    bad = json.loads(json.dumps(manifest))
    bad["per_layer"][0]["moves"] = "no_such_metric"
    bad["workloads"][0]["chips"] = 2
    bad["end_to_end"][0]["unit"] = "records per second"
    found = " ".join(mf.problems(bad, REPO))
    for word in ("no_such_metric", "chips", "unit"):
        assert word in found


def test_one_four_chip_cell_and_every_config_used(manifest):
    cells = manifest["workloads"]
    assert sum(c["chips"] == 4 for c in cells) <= max(1, len(cells) // 4)
    assert {c["config"] for c in cells} == {
        c["name"] for c in manifest["configs"]}


@pytest.mark.parametrize("cell", [c["name"] for c in mf.load(REPO)["workloads"]])
def test_each_cell_resolves_to_files(manifest, cell):
    """Config -> family -> family, counts and reference files; traffic ->
    kind -> driver file; every per-layer metric -> reader file. Every cell
    reports setup_s, another end-to-end metric, and a per-layer metric whose
    `moves` the cell reports."""
    c = mf.cell_of(manifest, cell)
    config = mf.load_json(REPO, mf.config_of(manifest, c)["file"])
    traffic = mf.load_json(REPO, mf.traffic_path(c))
    for sub, name in (("families", config["family"]),
                      ("counts", config["family"]),
                      ("reference", config["family"]),
                      ("drivers", traffic["kind"])):
        assert os.path.isfile(os.path.join(
            REPO, "benchmarks", sub, f"{name}.py")), (sub, name)
    e2e = {m["name"] for m in mf.metrics_of(manifest, c, "end_to_end")}
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = mf.metrics_of(manifest, c, "per_layer")
    assert layer and all(m["moves"] in e2e for m in layer)
    kind = "train" if traffic["kind"] == "train" else "serve"
    assert kind in config["limits"], "the cell's comparison has limits"
    assert all(0 < v < 1 for v in config["limits"][kind].values()), \
        "a limit was left at its placeholder"


def test_the_toy_manifest_is_sound_too():
    """tests/bench/tiny adds cells as files only; laid over benchmarks/ it
    must meet the same contract (checked on the overlay in
    test_rehearsal.py; here: its own names and shapes)."""
    with open(os.path.join(TINY, "BENCHMARK.json")) as f:
        tiny = json.load(f)
    assert set(tiny) == mf.TOP_KEYS
    assert all(mf.NAME.match(w["name"]) for w in tiny["workloads"])
