"""BENCHMARK.json: loading, the cell's files, and the contract's checks that
a test can run without the driver."""

from __future__ import annotations

import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
TRAFFIC_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")


def load(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_json(root: str, relative: str) -> dict:
    with open(os.path.join(root, relative)) as f:
        return json.load(f)


def cell_of(manifest: dict, workload: str) -> dict:
    for cell in manifest["workloads"]:
        if cell["name"] == workload:
            return cell
    raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; known: "
                     f"{[c['name'] for c in manifest['workloads']]}")


def config_of(manifest: dict, cell: dict) -> dict:
    for cfg in manifest["configs"]:
        if cfg["name"] == cell["config"]:
            return cfg
    raise SystemExit(f"workload {cell['name']!r} names config "
                     f"{cell['config']!r}, which BENCHMARK.json lacks")


def traffic_path(cell: dict) -> str:
    """A traffic mix is the data file `benchmarks/traffic/<traffic>.json`."""
    return f"benchmarks/traffic/{cell['traffic']}.json"


def metrics_of(manifest: dict, cell: dict, group: str) -> list:
    """The cell's metrics of `end_to_end` or `per_layer`: those that list
    the cell under `workloads`, and those that list nothing (every cell)."""
    return [m for m in manifest[group]
            if "workloads" not in m or cell["name"] in m["workloads"]]


def problems(manifest: dict, root: str) -> list:
    """Every breach of the contract a file check can find; [] when sound."""
    bad = []

    def need(ok, what):
        if not ok:
            bad.append(what)

    need(set(manifest) == TOP_KEYS, f"top-level keys {sorted(manifest)}")
    paths = manifest.get("paths", [])
    need(1 <= len(paths) <= 16 and all(PATH.match(p) for p in paths),
         "paths: 1 to 16 plain relative directories")
    need(isinstance(manifest.get("run_seconds"), int)
         and 1 <= manifest["run_seconds"] <= 51, "run_seconds in 1..51")
    cmd = manifest.get("command", [])
    need(0 < len(cmd) <= 32 and not any(
        w.startswith("/") or ".." in w.split("/") for w in cmd),
        "command: at most 32 words, nothing absolute or through ..")
    for word in cmd:
        if "/" in word or os.path.exists(os.path.join(root, word)):
            need(any(word.startswith(p.rstrip("/") + "/") for p in paths),
                 f"command names {word!r} outside paths")

    def unique(entries, what):
        names = [e.get("name") for e in entries]
        need(len(set(names)) == len(names), f"duplicate {what} names")
        for n in names:
            need(isinstance(n, str) and NAME.match(n), f"{what} name {n!r}")

    configs, cells = manifest.get("configs", []), manifest.get("workloads", [])
    e2e, layer = manifest.get("end_to_end", []), manifest.get("per_layer", [])
    unique(configs, "config"), unique(cells, "workload")
    unique(e2e + layer, "metric")
    need(1 <= len(configs) <= 24 and 1 <= len(cells) <= 24
         and 1 <= len(e2e) <= 16 and 1 <= len(layer) <= 128, "counts")

    files = set()
    for c in configs:
        need(set(c) == {"name", "source", "file", "reduced", "why"},
             f"config {c.get('name')}: keys {sorted(c)}")
        f = c.get("file", "")
        need(any(f.startswith(p.rstrip("/") + "/") for p in paths)
             and os.path.isfile(os.path.join(root, f)) and f not in files,
             f"config file {f!r} under paths, present, its own")
        files.add(f)
        need(any(w["config"] == c["name"] for w in cells),
             f"config {c.get('name')} has no cell")
        need(len(c.get("reduced", [])) <= 16
             and all(NAME.match(k) for k in c.get("reduced", [])),
             f"config {c.get('name')}: reduced")
        for key in ("why", "source"):
            need(_line(c.get(key)), f"config {c.get('name')}: {key}")
    pairs = set()
    for w in cells:
        need(set(w) == {"name", "config", "traffic", "chips", "why"},
             f"workload {w.get('name')}: keys {sorted(w)}")
        need(w.get("chips") in (1, 4), f"workload {w.get('name')}: chips")
        need(w.get("config") in {c["name"] for c in configs},
             f"workload {w.get('name')}: unknown config")
        need(isinstance(w.get("traffic"), str) and NAME.match(w["traffic"]),
             f"workload {w.get('name')}: traffic name")
        need(_line(w.get("why")), f"workload {w.get('name')}: why")
        need((w.get("config"), w.get("traffic")) not in pairs,
             f"workload {w.get('name')}: pair appears twice")
        pairs.add((w.get("config"), w.get("traffic")))
        tp = traffic_path(w)
        need(os.path.isfile(os.path.join(root, tp))
             and tp.endswith(TRAFFIC_SUFFIXES),
             f"workload {w.get('name')}: traffic file {tp}")
    four = sum(w.get("chips") == 4 for w in cells)
    need(four <= max(1, len(cells) // 4),
         f"{four} four-chip cells of {len(cells)}")

    cell_names = {w["name"] for w in cells}
    e2e_names = {m["name"] for m in e2e}

    def reported_in(metric):
        return set(metric.get("workloads", cell_names))

    for m in e2e:
        need(set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                        "source"},
             f"metric {m.get('name')}: keys {sorted(m)}")
        need(m.get("source") in ("host_clock", "device_trace"),
             f"metric {m.get('name')}: end-to-end source")
        need(isinstance(m.get("bound"), (int, float))
             and 0 < m["bound"] <= 0.1, f"metric {m.get('name')}: bound")
    need("setup_s" in e2e_names and "workloads" not in next(
        (m for m in e2e if m["name"] == "setup_s"), {"workloads": 1}),
        "setup_s in every cell")
    for m in layer:
        need(set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                        "layer", "moves"},
             f"metric {m.get('name')}: keys {sorted(m)}")
        need(m.get("source") in SOURCES, f"metric {m.get('name')}: source")
        need(_line(m.get("layer")), f"metric {m.get('name')}: layer")
        moved = next((e for e in e2e if e["name"] == m.get("moves")), None)
        need(moved is not None, f"metric {m.get('name')}: moves "
             f"{m.get('moves')!r} is no end-to-end metric")
        if moved is not None:
            need(reported_in(m) <= reported_in(moved),
                 f"metric {m['name']}: a cell of its does not report "
                 f"{moved['name']}")
        need(os.path.isfile(os.path.join(
            root, "benchmarks", "layer_metrics", f"{m.get('name')}.py")),
            f"metric {m.get('name')}: no reader file")
    for m in e2e + layer:
        need(isinstance(m.get("unit"), str) and UNIT.match(m["unit"]),
             f"metric {m.get('name')}: unit")
        need(m.get("better") in ("lower", "higher"),
             f"metric {m.get('name')}: better")
        need(reported_in(m) <= cell_names,
             f"metric {m.get('name')}: unknown workload")
    for w in cells:
        mine = [m for m in e2e if w["name"] in reported_in(m)]
        need(len(mine) >= 2, f"workload {w['name']}: setup_s and one more")
        need(any(w["name"] in reported_in(m) for m in layer),
             f"workload {w['name']}: no per-layer metric")
    return bad


def _line(text) -> bool:
    return (isinstance(text, str) and 1 <= len(text) <= 200
            and "\n" not in text and "\t" not in text)
