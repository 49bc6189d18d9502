"""Perf-regression sentinel over the bench trajectory (ISSUE 11
tentpole, layer 3).

Rounds of `BENCH_r*.json` history accumulate and nothing else ever
compares them: a silent 2× decode slowdown would ship. (The repo
currently holds ONE round, `BENCH_r05.json` — July numbers from
another stack — so `--fresh-latest` has nothing to gate against until
a second round lands; `--fresh` against it still works.) This
script loads the committed trajectory plus a candidate run and flags
regressions with NOISE-AWARE thresholds, so the documented ~25% host
variance (CLAUDE.md; the round-4 BiLSTM row ranged 7.8–23.3k
samples/s run to run) never pages anyone:

* the trajectory is every `BENCH_r*.json` driver artifact (each holds
  the bench stdout in its "tail" — one JSON row per metric); the
  candidate is either a fresh `python bench.py | tee fresh.jsonl`
  capture, another BENCH-shaped artifact, or `--fresh-latest` (gate
  the newest committed round against the rest — the pure-parse CI
  mode, tests/test_bench_compare.py);
* per metric, the baseline is the MEDIAN of the trailing `--window`
  historical values (a single lucky round never becomes the bar);
* the threshold is `max(--min-rel floor, --spread-margin × the row's
  recorded median-of-N spread)`: rows that publish
  `step_ms_median_of`/`step_ms_spread` (the jitter-robust protocol,
  bench.py `_run(reps>1)`) widen their own tolerance by their own
  measured noise — relative spread half-width (hi-lo)/2/step_ms, the
  max over the candidate row and the history window;
* every metric's `value` is a throughput (higher is better): a
  candidate below `baseline × (1 - threshold)` is a regression, above
  `baseline × (1 + threshold)` an improvement, else stable.

Output: a machine-readable verdict (`--format json`) the driver/CI can
gate on — exit 0 clean, 1 on any flagged regression, 2 on usage/parse
trouble (the check_tier1_budget.py convention).

Usage:
    python scripts/bench_compare.py --fresh-latest            # CI gate
    python bench.py | tee /tmp/fresh.jsonl
    python scripts/bench_compare.py --fresh /tmp/fresh.jsonl
    python scripts/bench_compare.py --fresh-latest --format json
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import statistics
import sys
from typing import Dict, List, Optional, Tuple

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                         ".."))
DEFAULT_HISTORY_GLOB = os.path.join(REPO_ROOT, "BENCH_r*.json")


# ----------------------------------------------------------- row loading

def rows_from_text(text: str) -> Dict[str, dict]:
    """Metric rows from bench stdout (one JSON object per line; log
    noise and partial lines are ignored)."""
    out: Dict[str, dict] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            row = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(row, dict) and "metric" in row \
                and isinstance(row.get("value"), (int, float)):
            out[row["metric"]] = row
    return out


def load_rows(path: str) -> Dict[str, dict]:
    """Rows from a file: a BENCH_r*.json driver artifact (rows live in
    its "tail"), a raw JSONL capture of bench stdout, or a JSON list
    of rows."""
    with open(path) as f:
        text = f.read()
    try:
        obj = json.loads(text)
    except json.JSONDecodeError:
        return rows_from_text(text)
    if isinstance(obj, dict) and "tail" in obj:
        return rows_from_text(obj["tail"])

    def _valid(r):
        # same admission rule as rows_from_text: a row without a
        # numeric value can never be compared — dropping it here is
        # what routes an all-garbage candidate to the exit-2 path
        # instead of a TypeError inside compare()
        return isinstance(r, dict) and "metric" in r \
            and isinstance(r.get("value"), (int, float))

    if isinstance(obj, list):
        return {r["metric"]: r for r in obj if _valid(r)}
    if _valid(obj):
        return {obj["metric"]: obj}
    return rows_from_text(text)


def _round_key(path: str) -> Tuple:
    """Sort BENCH_r01 < BENCH_r02 < ... (numeric round order)."""
    m = re.search(r"_r(\d+)", os.path.basename(path))
    return (int(m.group(1)) if m else 0, os.path.basename(path))


def load_history(pattern: str) -> List[Tuple[str, Dict[str, dict]]]:
    """[(round tag, {metric: row}), ...] oldest first."""
    out = []
    for path in sorted(glob.glob(pattern), key=_round_key):
        rows = load_rows(path)
        if rows:
            out.append((os.path.basename(path), rows))
    return out


# ------------------------------------------------------------ comparison

def spread_frac(row: dict) -> Optional[float]:
    """Relative half-width of the row's recorded median-of-N spread:
    (hi - lo) / 2 / step_ms. None when the row didn't run the
    jitter-robust protocol."""
    spread = row.get("step_ms_spread")
    step = row.get("step_ms")
    if not (isinstance(spread, (list, tuple)) and len(spread) == 2
            and isinstance(step, (int, float)) and step > 0):
        return None
    lo, hi = float(spread[0]), float(spread[1])
    return max(hi - lo, 0.0) / 2.0 / float(step)


def compare(history: List[Tuple[str, Dict[str, dict]]],
            fresh: Dict[str, dict], *, min_rel: float = 0.25,
            spread_margin: float = 1.5, window: int = 3) -> dict:
    """The verdict. Per metric present in both the candidate and the
    history: baseline = median of the trailing `window` values,
    threshold = max(min_rel, spread_margin × worst recorded spread
    fraction), flag = candidate below baseline × (1 - threshold)."""
    hist_metrics = sorted({m for _, rows in history for m in rows})
    checked, regressions, improvements = [], [], []
    for metric in sorted(fresh):
        if metric not in hist_metrics:
            continue
        trail = [(tag, rows[metric]) for tag, rows in history
                 if metric in rows][-window:]
        values = [float(r["value"]) for _, r in trail]
        baseline = statistics.median(values)
        if baseline <= 0:
            continue
        fresh_row = fresh[metric]
        value = float(fresh_row["value"])
        noise = [f for f in
                 [spread_frac(fresh_row)]
                 + [spread_frac(r) for _, r in trail] if f is not None]
        threshold = max(min_rel,
                        spread_margin * max(noise) if noise else 0.0)
        ratio = value / baseline
        entry = {
            "metric": metric,
            "value": round(value, 4),
            "baseline": round(baseline, 4),
            "baseline_rounds": [tag for tag, _ in trail],
            "ratio": round(ratio, 4),
            "threshold_frac": round(threshold, 4),
            "noise_frac": round(max(noise), 4) if noise else None,
        }
        checked.append(entry)
        if ratio < 1.0 - threshold:
            entry["shortfall_frac"] = round(1.0 - ratio, 4)
            regressions.append(entry)
        elif ratio > 1.0 + threshold:
            improvements.append(entry)
    hist_only = sorted(set(hist_metrics) - set(fresh))
    fresh_only = sorted(set(fresh) - set(hist_metrics))
    return {
        "ok": not regressions,
        "checked": len(checked),
        "rows": checked,
        "regressions": regressions,
        "improvements": [e["metric"] for e in improvements],
        "new_metrics": fresh_only,
        "missing_metrics": hist_only,
        "params": {"min_rel": min_rel, "spread_margin": spread_margin,
                   "window": window},
    }


def render(verdict: dict, rounds: List[str], fresh_tag: str) -> str:
    lines = [f"bench-compare: {fresh_tag} vs "
             f"{', '.join(rounds)} — "
             f"{'OK' if verdict['ok'] else 'REGRESSION'} "
             f"({verdict['checked']} metrics checked)"]
    for e in verdict["rows"]:
        flag = "REGRESSED" if e in verdict["regressions"] else (
            "improved" if e["metric"] in verdict["improvements"]
            else "stable")
        noise = "" if e["noise_frac"] is None \
            else f" noise={e['noise_frac'] * 100:.0f}%"
        lines.append(
            f"  {e['metric']}: {e['value']:g} vs baseline "
            f"{e['baseline']:g} (x{e['ratio']:.3f}, "
            f"tol {e['threshold_frac'] * 100:.0f}%{noise}) — {flag}")
    if verdict["new_metrics"]:
        lines.append("  new (no history): "
                     + ", ".join(verdict["new_metrics"]))
    if verdict["missing_metrics"]:
        lines.append("  not in candidate: "
                     + ", ".join(verdict["missing_metrics"]))
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fresh", default=None,
                    help="candidate rows: bench stdout JSONL, a JSON "
                         "row list, or a BENCH_r*.json artifact")
    ap.add_argument("--fresh-latest", action="store_true",
                    help="gate the newest history round against the "
                         "earlier ones (pure-parse CI mode)")
    ap.add_argument("--history", default=DEFAULT_HISTORY_GLOB,
                    help="glob of BENCH_r*.json trajectory artifacts")
    ap.add_argument("--min-rel", type=float, default=0.25,
                    help="threshold floor — the documented ~25%% host "
                         "variance never pages")
    ap.add_argument("--spread-margin", type=float, default=1.5,
                    help="multiplier on a row's recorded median-of-N "
                         "spread fraction")
    ap.add_argument("--window", type=int, default=3,
                    help="trailing rounds the baseline median uses")
    ap.add_argument("--format", default="text",
                    choices=("text", "json"))
    args = ap.parse_args(argv)

    history = load_history(args.history)
    if args.fresh_latest:
        if len(history) < 2:
            print("bench-compare: --fresh-latest needs >= 2 history "
                  f"rounds (got {len(history)} from {args.history})",
                  file=sys.stderr)
            return 2
        fresh_tag, fresh = history[-1]
        history = history[:-1]
    elif args.fresh is not None:
        try:
            fresh = load_rows(args.fresh)
        except OSError as e:
            print(f"bench-compare: cannot read {args.fresh}: {e}",
                  file=sys.stderr)
            return 2
        fresh_tag = os.path.basename(args.fresh)
    else:
        print("bench-compare: pass --fresh <rows> or --fresh-latest",
              file=sys.stderr)
        return 2
    if not history:
        print(f"bench-compare: no history rounds match {args.history}",
              file=sys.stderr)
        return 2
    if not fresh:
        print(f"bench-compare: no metric rows in candidate "
              f"{fresh_tag}", file=sys.stderr)
        return 2

    verdict = compare(history, fresh, min_rel=args.min_rel,
                      spread_margin=args.spread_margin,
                      window=args.window)
    verdict["candidate"] = fresh_tag
    verdict["history_rounds"] = [tag for tag, _ in history]
    if args.format == "json":
        print(json.dumps(verdict, sort_keys=True))
    else:
        print(render(verdict, verdict["history_rounds"], fresh_tag))
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
