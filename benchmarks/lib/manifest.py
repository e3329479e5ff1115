"""BENCHMARK.json and the data files it names.

A cell (one entry of ``workloads``) resolves, by name alone, to
``configs/<config>.json``, ``traffic/<traffic>.json`` and, through the traffic
file's ``kind``, ``drivers/<kind>.py``.  A per-layer metric resolves to
``layer_metrics/<name>.py``.  Nothing here knows a cell, a configuration or a
metric by name: a later PR adds files and entries and edits none.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(ValueError):
    pass


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def deep_merge(base: Dict[str, Any], over: Dict[str, Any]) -> Dict[str, Any]:
    """``over`` laid on ``base``; nested dicts merge, anything else replaces."""
    out = dict(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = deep_merge(out[k], v)
        else:
            out[k] = v
    return out


class Manifest:
    def __init__(self, path: Optional[str] = None, bench_dir: str = BENCH_DIR):
        self.path = path or os.path.join(os.path.dirname(bench_dir),
                                         "BENCHMARK.json")
        self.bench_dir = bench_dir
        self.data = load_json(self.path)

    # -- lookups -------------------------------------------------------------
    def cell(self, name: str) -> Dict[str, Any]:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        known = [w["name"] for w in self.data["workloads"]]
        raise ManifestError(f"no workload {name!r} in {self.path}; "
                            f"known: {known}")

    def config(self, name: str) -> Dict[str, Any]:
        for c in self.data["configs"]:
            if c["name"] == name:
                return load_json(os.path.join(os.path.dirname(self.path),
                                              c["file"]))
        raise ManifestError(f"no config {name!r} in {self.path}")

    def traffic(self, name: str) -> Dict[str, Any]:
        return load_json(os.path.join(self.bench_dir, "traffic",
                                      name + ".json"))

    def end_to_end(self, cell: str) -> List[Dict[str, Any]]:
        """The end-to-end metrics the cell reports: those without a
        ``workloads`` key, and those that list it."""
        return [m for m in self.data["end_to_end"]
                if "workloads" not in m or cell in m["workloads"]]

    def per_layer(self, cell: str) -> List[Dict[str, Any]]:
        """The per-layer metrics the cell reports: those that list it, and
        those without a list whose ``moves`` the cell reports."""
        e2e = {m["name"] for m in self.end_to_end(cell)}
        out = []
        for m in self.data["per_layer"]:
            if "workloads" in m:
                if cell in m["workloads"]:
                    out.append(m)
            elif m["moves"] in e2e:
                out.append(m)
        return out


def load_module(path: str, name: str):
    """Import one file by path (metric names hold dots, so not by name)."""
    if not os.path.exists(path):
        raise ManifestError(f"{path} does not exist")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_driver(kind: str, bench_dir: str = BENCH_DIR):
    if not NAME_RE.match(kind):
        raise ManifestError(f"bad driver kind {kind!r}")
    return load_module(os.path.join(bench_dir, "drivers", kind + ".py"),
                       "benchmarks_driver_" + kind)


def load_layer_metric(name: str, bench_dir: str = BENCH_DIR):
    if not NAME_RE.match(name):
        raise ManifestError(f"bad metric name {name!r}")
    return load_module(
        os.path.join(bench_dir, "layer_metrics", name + ".py"),
        "benchmarks_metric_" + re.sub(r"[^A-Za-z0-9_]", "_", name))


def resolve_sizes(cfg: Dict[str, Any], rehearse: bool) -> Dict[str, Any]:
    """The configuration as run: the file itself, or (``--rehearse``) the file
    with its ``tiny`` block laid over it."""
    if not rehearse:
        return cfg
    if "tiny" not in cfg:
        raise ManifestError(f"config {cfg.get('name')!r} has no tiny block "
                            "to rehearse with")
    return deep_merge(cfg, cfg["tiny"])


# -- the contract's limits, checked by benchmarks/tests ------------------------

def validate(data: Dict[str, Any], root: str = ROOT) -> List[str]:
    """Every breach of the manifest's contract found, as text.  Not the
    driver's check — a copy of its stated rules, so a breach shows here, on
    the CPU, first."""
    bad: List[str] = []
    keys = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(data) != keys:
        bad.append(f"top-level keys {sorted(data)} != {sorted(keys)}")
        return bad

    def name_ok(s, what):
        if not isinstance(s, str) or not NAME_RE.match(s):
            bad.append(f"{what}: bad name {s!r}")

    def line_ok(s, what):
        if (not isinstance(s, str) or not 1 <= len(s) <= 200
                or "\n" in s or "\t" in s):
            bad.append(f"{what}: not 1..200 characters on one line")

    paths = data["paths"]
    if not 1 <= len(paths) <= 16:
        bad.append("paths: 1 to 16 directories")
    for p in paths:
        if (not re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", p)
                or p.startswith("/") or ".." in p.split("/")):
            bad.append(f"paths: bad path {p!r}")
    if not 1 <= len(data["command"]) <= 32:
        bad.append("command: 1 to 32 words")
    for w in data["command"]:
        line_ok(w, "command word")
    if not (isinstance(data["run_seconds"], int)
            and 1 <= data["run_seconds"] <= 51):
        bad.append("run_seconds: whole number 1..51")

    def under_paths(f):
        return any(f == p or f.startswith(p.rstrip("/") + "/") for p in paths)

    cfg_names, files = set(), set()
    for c in data["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            bad.append(f"config {c.get('name')}: keys {sorted(c)}")
            continue
        name_ok(c["name"], "config")
        line_ok(c["source"], f"config {c['name']} source")
        line_ok(c["why"], f"config {c['name']} why")
        if not under_paths(c["file"]):
            bad.append(f"config {c['name']}: file outside paths")
        if not os.path.exists(os.path.join(root, c["file"])):
            bad.append(f"config {c['name']}: file {c['file']} missing")
        if c["file"] in files:
            bad.append(f"config {c['name']}: file shared")
        files.add(c["file"])
        if len(c["reduced"]) > 16:
            bad.append(f"config {c['name']}: more than 16 reduced keys")
        for k in c["reduced"]:
            name_ok(k, f"config {c['name']} reduced")
        if c["name"] in cfg_names:
            bad.append(f"config {c['name']}: twice")
        cfg_names.add(c["name"])
    if not 1 <= len(data["configs"]) <= 24:
        bad.append("configs: 1 to 24")

    cells, pairs, used = set(), set(), set()
    for w in data["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            bad.append(f"workload {w.get('name')}: keys {sorted(w)}")
            continue
        for k in ("name", "config", "traffic"):
            name_ok(w[k], f"workload {k}")
        line_ok(w["why"], f"workload {w['name']} why")
        if w["chips"] not in (1, 4):
            bad.append(f"workload {w['name']}: chips {w['chips']}")
        if w["config"] not in cfg_names:
            bad.append(f"workload {w['name']}: unknown config")
        if w["name"] in cells or (w["config"], w["traffic"]) in pairs:
            bad.append(f"workload {w['name']}: twice")
        cells.add(w["name"])
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
    if not 1 <= len(data["workloads"]) <= 24:
        bad.append("workloads: 1 to 24")
    if cfg_names - used:
        bad.append(f"configs used by no cell: {sorted(cfg_names - used)}")
    four = sum(1 for w in data["workloads"] if w.get("chips") == 4)
    if four > max(1, len(data["workloads"]) // 4):
        bad.append("too many four-chip cells")

    metric_names, e2e = set(), {}
    for m in data["end_to_end"]:
        allowed = {"name", "unit", "better", "bound", "source", "workloads"}
        if not ({"name", "unit", "better", "bound", "source"} <= set(m)
                <= allowed):
            bad.append(f"end_to_end {m.get('name')}: keys {sorted(m)}")
            continue
        name_ok(m["name"], "end_to_end")
        if not UNIT_RE.match(m["unit"]):
            bad.append(f"end_to_end {m['name']}: unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            bad.append(f"end_to_end {m['name']}: better")
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"end_to_end {m['name']}: source {m['source']}")
        if not 0.01 <= m["bound"] <= 0.1:
            bad.append(f"end_to_end {m['name']}: bound {m['bound']}")
        for c in m.get("workloads", []):
            if c not in cells:
                bad.append(f"end_to_end {m['name']}: unknown cell {c}")
        if m["name"] in metric_names:
            bad.append(f"metric {m['name']}: twice")
        metric_names.add(m["name"])
        e2e[m["name"]] = m
    if "setup_s" not in e2e:
        bad.append("end_to_end: no setup_s")
    elif "workloads" in e2e["setup_s"]:
        bad.append("setup_s: every cell reports it")
    if not 1 <= len(data["end_to_end"]) <= 16:
        bad.append("end_to_end: 1 to 16")

    def reports(cell, metric):
        return "workloads" not in metric or cell in metric["workloads"]

    for m in data["per_layer"]:
        allowed = {"name", "unit", "better", "source", "layer", "moves",
                   "workloads"}
        if not (allowed - {"workloads"} <= set(m) <= allowed):
            bad.append(f"per_layer {m.get('name')}: keys {sorted(m)}")
            continue
        name_ok(m["name"], "per_layer")
        line_ok(m["layer"], f"per_layer {m['name']} layer")
        if not UNIT_RE.match(m["unit"]):
            bad.append(f"per_layer {m['name']}: unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            bad.append(f"per_layer {m['name']}: better")
        if m["source"] not in SOURCES:
            bad.append(f"per_layer {m['name']}: source {m['source']}")
        if m["moves"] not in e2e:
            bad.append(f"per_layer {m['name']}: moves {m['moves']!r}")
        else:
            for c in m.get("workloads", []):
                if c not in cells:
                    bad.append(f"per_layer {m['name']}: unknown cell {c}")
                elif not reports(c, e2e[m["moves"]]):
                    bad.append(f"per_layer {m['name']}: cell {c} does not "
                               f"report {m['moves']}")
        if m["name"] in metric_names:
            bad.append(f"metric {m['name']}: twice")
        metric_names.add(m["name"])
    if not 1 <= len(data["per_layer"]) <= 128:
        bad.append("per_layer: 1 to 128")

    for c in cells:
        mine = [m for m in data["end_to_end"] if reports(c, m)]
        if len([m for m in mine if m["name"] != "setup_s"]) < 1:
            bad.append(f"cell {c}: no end-to-end metric besides setup_s")
        names = {m["name"] for m in mine}
        layer = [m for m in data["per_layer"]
                 if (c in m["workloads"] if "workloads" in m
                     else m.get("moves") in names)]
        if not layer:
            bad.append(f"cell {c}: no per-layer metric")
    if len(json.dumps(data)) > 64 * 1024:
        bad.append("file over 64 KiB")
    return bad
