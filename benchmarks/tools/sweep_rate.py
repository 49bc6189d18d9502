"""Find the knee of an open-loop serving cell: the same cell at several
fixed rates, one fresh process each, from a scratch copy of the benchmark
under .bench_out/ (the committed files are not touched). Above the knee the
queue grows through the run: TTFT and the generator's backlog climb.

    python3 benchmarks/tools/sweep_rate.py --workload W --rates 2.4,3.2,4.0 [--seconds S] [--seed N]
"""

import argparse
import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    scratch = os.path.join(ROOT, ".bench_out", "sweep")
    shutil.rmtree(scratch, ignore_errors=True)
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(scratch, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    for name in ("bigdl_tpu",):         # the program, beside the benchmark
        os.symlink(os.path.join(ROOT, name), os.path.join(scratch, name))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = next(c for c in manifest["workloads"]
                if c["name"] == args.workload)
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    env = dict(os.environ)
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(ROOT, ".jax_cache"))
    for rate in args.rates.split(","):
        traffic["rate_per_s"] = float(rate)
        with open(os.path.join(scratch, "benchmarks", "traffic",
                               cell["traffic"] + ".json"), "w") as f:
            json.dump(traffic, f)
        with open(os.path.join(scratch, "BENCHMARK.json"), "w") as f:
            json.dump(manifest, f)
        p = subprocess.run(
            [sys.executable, "benchmarks/run.py", "--workload",
             args.workload, "--seed", str(args.seed), "--seconds",
             str(args.seconds), "--trace", "0"], cwd=scratch, env=env,
            capture_output=True, text=True)
        lines = p.stdout.strip().splitlines()
        serve = next((l for l in lines if l.startswith("serve ")), "")
        print(f"rate {rate}: rc={p.returncode} {lines[-1] if lines else ''}")
        print(f"   {serve}\n   {p.stderr[-400:] if p.returncode else ''}",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
