"""Shared by the tests of the benchmark harness (tests/bench/): a temporary
root that holds a COPY of benchmarks/ plus the toy cells of
tests/bench/tiny/ ADDED as new files, and one in-process run of a cell on
the CPU (`require_chip=False`, which the command line cannot reach)."""

import hashlib
import json
import os
import shutil
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny")


def tree_hashes(root):
    out = {}
    for base, dirs, files in os.walk(root):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            path = os.path.join(base, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """benchmarks/ copied, then the toy configuration, traffic and metric
    files laid beside the real ones. Restores the JAX settings a run
    changes (cache directory and thresholds) when the module is done."""
    import jax

    root = str(tmp_path_factory.mktemp("bench_root"))
    shutil.copytree(os.path.join(REPO, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    before = tree_hashes(os.path.join(root, "benchmarks"))
    for sub in ("configs", "traffic", "layer_metrics"):
        for f in os.listdir(os.path.join(TINY, sub)):
            dst = os.path.join(root, "benchmarks", sub, f)
            assert not os.path.exists(dst), f"{f} would replace a file"
            shutil.copy(os.path.join(TINY, sub, f), dst)
    shutil.copy(os.path.join(TINY, "BENCHMARK.json"), root)
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    yield root, before
    from jax.experimental.compilation_cache import compilation_cache

    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
    from bigdl_tpu import obs

    obs.set_tracer(None)


def run_tiny(root, workload, seed=7, seconds=0.5, trace=False, **kw):
    """One run; returns (result object, every line it printed)."""
    from benchmarks.harness.runner import run_cell

    lines = []
    result = run_cell(root, workload, seed, seconds, trace,
                      time.perf_counter(), require_chip=False,
                      out=lines.append, **kw)
    assert json.loads(lines[-1]) == result
    return result, lines
