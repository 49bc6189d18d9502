"""Run a cell several times, each a fresh process, and summarise: the way
the bounds in BENCHMARK.json were measured (two sets of six runs, the same
seeds in both; a spread is the quartile distance over the median).

    python3 benchmarks/tools/series.py --workload W --seeds 11,12,13 \
        [--seconds S] [--trace 0|1] [--cache-dir DIR] [--label L]

The parent never imports JAX: a chip belongs to one process at a time.
Every run's lines go to chiprun_out/<label>.log, one JSON object a run to
chiprun_out/<label>.jsonl.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.harness.stats import quartile_spread  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--cache-dir")
    ap.add_argument("--label")
    ap.add_argument("--root", default=ROOT)
    args = ap.parse_args()
    with open(os.path.join(args.root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    seconds = args.seconds or manifest["run_seconds"]
    label = args.label or args.workload
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    env = dict(os.environ)
    if args.cache_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = os.path.abspath(args.cache_dir)
    rows = []
    with open(os.path.join(out_dir, f"{label}.log"), "a") as log, \
            open(os.path.join(out_dir, f"{label}.jsonl"), "a") as jl:
        for seed in args.seeds.split(","):
            cmd = manifest["command"] + [
                "--workload", args.workload, "--seed", seed, "--seconds",
                str(seconds), "--trace", str(args.trace)]
            t = time.time()
            p = subprocess.run(cmd, cwd=args.root, env=env,
                               capture_output=True, text=True)
            wall = time.time() - t
            lines = p.stdout.strip().splitlines()
            log.write(f"==== {cmd} rc={p.returncode} wall={wall:.1f}\n"
                      f"{p.stdout}\n---- stderr tail\n{p.stderr[-3000:]}\n")
            row = {"seed": int(seed), "rc": p.returncode, "wall_s": wall}
            for line in lines:
                if line.startswith("setup "):
                    row["setup"] = json.loads(line[6:])
                if line.startswith("check ") or line.startswith("serve ") \
                        or line.startswith("reference") \
                        or line.startswith("flash_attn"):
                    row.setdefault("notes", []).append(line)
            try:
                row["result"] = json.loads(lines[-1])
            except (IndexError, ValueError):
                row["result"] = None
                row["stderr"] = p.stderr[-1500:]
            jl.write(json.dumps(row) + "\n")
            jl.flush()
            rows.append(row)
            print(json.dumps(row), flush=True)
            if p.returncode != 0:       # a crash: the rest would crash too
                break
    good = [r for r in rows if r["result"]]
    names = sorted({k for r in good for k in r["result"]["metrics"]})
    for name in names:
        vals = [r["result"]["metrics"][name]["value"] for r in good
                if name in r["result"]["metrics"]]
        line = f"{label} {name}: n={len(vals)} median={statistics.median(vals)!r}"
        if len(vals) >= 3:
            line += f" spread={quartile_spread(vals):.5f}"
        print(line + f" values={vals}")
    for phase in ("import_s", "backend_init_s", "build_s",
                  "compile_or_load_s", "warmup_s", "lead_s"):
        vals = [r["setup"][phase] for r in rows if "setup" in r]
        if vals:
            print(f"{label} phase {phase}: median="
                  f"{statistics.median(vals):.3f} values="
                  f"{[round(v, 3) for v in vals]}")
    print(f"{label} correct: {[r['result'] and r['result']['correct'] for r in rows]}")
    return 0 if all(r["rc"] == 0 for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
