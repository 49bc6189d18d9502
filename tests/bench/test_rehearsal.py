"""Each driver end to end on the CPU at a toy size, through cells that are
ADDED to a copy of benchmarks/ as files only (tests/bench/tiny/): a
configuration, traffic mixes and a per-layer metric that no existing file
names. A CPU run labels its device as the CPU and carries no device metric;
the command line refuses to run without a TPU at all."""

import json
import os
import subprocess
import sys

import pytest

from bench_helpers import REPO, run_tiny, tiny_root, tree_hashes  # noqa: F401
from benchmarks.harness import manifest as mf

DEVICE_METRICS = ("device_idle", "peak_hbm", "mfu", "roofline",
                  "kernel_share", "collective")


def _check_line(result, cell_metrics):
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["memory_peak_bytes"] is None
    assert "busy_s" not in result["device"] and "breakdown" not in result
    assert not [m for m in result["metrics"]
                if any(w in m for w in DEVICE_METRICS)]
    assert set(result["metrics"]) <= cell_metrics
    assert all(isinstance(v["value"], (int, float)) and v["unit"]
               for v in result["metrics"].values())
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0


def _setup_line(lines):
    return json.loads(next(l for l in lines if l.startswith("setup "))[6:])


def test_cells_were_added_as_files_only(tiny_root):
    root, before = tiny_root
    after = tree_hashes(os.path.join(root, "benchmarks"))
    assert {k: after[k] for k in before} == before, "an existing file changed"
    added = set(after) - set(before)
    assert "layer_metrics/tiny_rounds.py" in added and len(added) >= 8
    assert mf.problems(mf.load(root), root) == []


@pytest.mark.parametrize("workload,trace", [
    ("tiny-train", False), ("tiny-train", True), ("tiny-chat", False),
    ("tiny-chat", True), ("tiny-backlog", False), ("tiny-backlog", True),
    ("tiny-dp4", False)])
def test_rehearsal(tiny_root, workload, trace, capfd):
    root, _ = tiny_root
    manifest = mf.load(root)
    cell = mf.cell_of(manifest, workload)
    group = "per_layer" if trace else "end_to_end"
    names = {m["name"] for m in mf.metrics_of(manifest, cell, group)}
    result, lines = run_tiny(root, workload, seed=2 ** 31 + 11, trace=trace)
    _check_line(result, names)
    setup = _setup_line(lines)
    assert setup["window_compiles"]["requests"] == 0
    assert setup["setup_s"] == pytest.approx(
        sum(setup[p] for p in ("import_s", "backend_init_s", "build_s",
                               "compile_or_load_s", "warmup_s", "lead_s")))
    assert any(l.startswith("check ") and " <= limit " in l for l in lines)
    # each number compared beside its limit: last in the result's line, and
    # the last lines of standard error
    checks = result["checks"]
    assert list(result)[-1] == "checks" and all(c["ok"] for c in checks.values())
    assert any(c["limit"] is not None and c["value"] <= c["limit"]
               for c in checks.values())
    err = capfd.readouterr().err.strip().splitlines()[-len(checks):]
    assert [l.split(":")[0] for l in err] == [f"check {n}" for n in checks]
    if trace:
        # the metric that exists only as an added file was found by name
        assert result["metrics"]["tiny_rounds"]["value"] > 0
        if workload == "tiny-backlog":      # closed with requests queued
            assert 0 < result["metrics"]["backlog_queue_left"]["value"] < 100
    else:
        assert result["metrics"]["setup_s"]["value"] == setup["setup_s"]
        assert len(result["metrics"]) == len(names)


def test_second_run_finds_every_program_in_the_cache(tiny_root):
    """Another seed, the same programs: every compile request of set-up is
    served from the persistent cache, none reaches the window."""
    root, _ = tiny_root
    run_tiny(root, "tiny-train", seed=21)
    _, lines = run_tiny(root, "tiny-train", seed=22)
    compiles = _setup_line(lines)["setup_compiles"]
    assert compiles["requests"] > 0
    assert compiles["hits"] == compiles["requests"]
    assert compiles["misses"] == 0


def test_same_seed_same_inputs(tiny_root):
    root, _ = tiny_root
    a = run_tiny(root, "tiny-train", seed=5)[1]
    b = run_tiny(root, "tiny-train", seed=5)[1]
    c = run_tiny(root, "tiny-train", seed=6)[1]

    def losses(lines):
        return next(l for l in lines if l.startswith("reference:")).split(
            "program losses")[1]

    assert losses(a) == losses(b) != losses(c)


def test_the_command_refuses_to_run_without_a_tpu(tmp_path):
    """`python3 benchmarks/run.py` on this CPU-only machine: non-zero exit,
    no result line. The same in a directory that holds only BENCHMARK.json
    and the benchmark's own files (no program beside them)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "benchmarks/run.py", "--workload", "gpt2m-train",
           "--seed", "1", "--seconds", "1", "--trace", "0"]
    p = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0 and "no TPU" in p.stderr
    assert not [l for l in p.stdout.splitlines() if l.startswith("{")]
    import shutil

    bare = tmp_path / "bare"
    shutil.copytree(os.path.join(REPO, "benchmarks"), bare / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), bare)
    p = subprocess.run(cmd, cwd=bare, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert not [l for l in p.stdout.splitlines() if l.startswith("{")]
