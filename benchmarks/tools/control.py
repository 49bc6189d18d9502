"""Read a cell's control on the chip: one run of the cell (a short window
at the cell's own load and size), after which the reference is computed a
second time in the nearest precision below the one the configuration
states, and the numbers that `correct` compares are printed for that lower
precision beside the program's. The limits in the configuration files were
set between the two (PERF.md gives the readings).

    python3 benchmarks/tools/control.py --workload W --seed N --control fp8|bf16 [--seconds S]

A training cell's control needs no program: `--reference-only` computes the
reference and the lower precision alone, on one chip whatever the cell's
own number (a four-chip cell's control then costs one chip's time).
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--control", required=True, choices=("fp8", "bf16"))
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--reference-only", action="store_true")
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    from benchmarks.harness.runner import run_cell

    if args.reference_only:
        return reference_only(args)

    run_cell(ROOT, args.workload, args.seed, args.seconds, False, T0,
             control=args.control)
    return 0


def reference_only(args) -> int:
    from benchmarks.harness import check, manifest as mf
    from benchmarks.harness.phases import place_compile_cache
    from benchmarks.harness.runner import load_part

    manifest = mf.load(ROOT)
    cell = mf.cell_of(manifest, args.workload)
    config = mf.load_json(ROOT, mf.config_of(manifest, cell)["file"])
    traffic = mf.load_json(ROOT, mf.traffic_path(cell))
    place_compile_cache(ROOT)
    family = load_part(ROOT, "families", config["family"])
    steps = 3
    exact = family.reference_steps(args.seed, config, traffic,
                                   cell["chips"], steps)
    lower = family.reference_steps(args.seed, config, traffic,
                                   cell["chips"], steps, args.control)
    print(f"reference losses {exact['loss']}, control losses "
          f"{lower['loss']}, {time.perf_counter() - T0:.0f} s")
    print(f"control {args.control}: "
          f"{check.train_numbers(lower, exact)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
