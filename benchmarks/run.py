"""Run one cell of the benchmark once.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A fresh process: finds no TPU (or too few chips) -> exits non-zero with no
result line, never falls back. Builds the cell from the seed, warms only the
cell's own shapes, measures for --seconds, checks the outputs against the
plain reference outside the window, and prints the contract's one JSON
object as the last line of its standard output. `setup_s` runs from the
first statement below to the first timed step of the window.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmarks.harness.runner import run_cell

    run_cell(ROOT, args.workload, args.seed, args.seconds, bool(args.trace),
             T0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
